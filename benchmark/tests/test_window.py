"""The window's hook under the call pattern of both train loops, and
the iterator that keeps a stream's first batches."""

import numpy as np
import pytest

from benchmark.harness import window


def _drive(hook, *, k, log_every, save_every, ends, max_dispatches=200):
  """What `train_qtopt` and `train_eval_model` call on a hook, in their
  order: per dispatch `after_step`, then at a save step
  `after_checkpoint`; `train_qtopt` calls `end` on its way out of an
  exception, `train_eval_model` does not."""
  metrics = {"loss": np.float32(1.0)}
  step = window.resume_step(save_every, k)  # as the drivers resume
  try:
    for _ in range(max_dispatches):
      step += k
      hook.after_step(step, metrics)
      if step % save_every == 0:
        hook.after_checkpoint(step, {"w": np.zeros(2)}, "unused")
  finally:
    if ends:
      hook.end(step, None, "unused")


@pytest.mark.parametrize("loop_name,ends", [("train_qtopt", True),
                                            ("train_eval_model", False)])
def test_window_closes_on_a_whole_save_period(loop_name, ends):
  k, save_every = 2, 8
  hook = window.WindowHook(warm=3, seconds=0.0, period_steps=save_every,
                           compiles=window.CompileCounter(),
                           clock_start=0.0, loop_name=loop_name)
  with pytest.raises(window.WindowClosed):
    _drive(hook, k=k, log_every=4, save_every=save_every, ends=ends)
  resumed = window.resume_step(save_every, k)
  assert hook.step0 == resumed + 3 * k  # after the warm dispatches
  assert hook.step1 > hook.step0
  assert (hook.step1 - hook.step0) % save_every == 0
  assert hook.first_step == resumed + k
  assert hook.first_metrics == {"loss": 1.0}
  assert set(hook.first_state) == {"w"}  # the first dispatch's save
  # One save a period, each timed from its step's `after_step`.
  assert len(hook.checkpoint_stalls_ms) \
      == (hook.step1 - hook.step0) // save_every
  assert hook.setup_s is not None and not hook.compiles.armed


@pytest.mark.parametrize("loop_name", ["train_qtopt", "train_eval_model"])
def test_traced_dispatches_carry_the_loops_name(loop_name, monkeypatch):
  import jax
  monkeypatch.setattr(jax.profiler, "start_trace",
                      lambda *args, **kwargs: None)
  monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
  hook = window.WindowHook(warm=1, seconds=0.0, period_steps=4,
                           compiles=window.CompileCounter(),
                           clock_start=0.0, trace_dir="unused",
                           trace_dispatches=4, loop_name=loop_name)
  with pytest.raises(window.WindowClosed):
    _drive(hook, k=2, log_every=2, save_every=4, ends=False)
  names = {name for name, _, _ in hook.host_spans}
  assert names == {f"{loop_name}: wait for the feed, dispatch, log",
                   f"{loop_name}: checkpoint"}
  assert hook.trace_span[1] >= hook.trace_span[0]
  assert (hook.step1 - hook.step0) % 4 == 0


def test_first_checkpoint_off_the_first_dispatch_is_an_error():
  hook = window.WindowHook(warm=5, seconds=0.0, period_steps=4,
                           compiles=window.CompileCounter(),
                           clock_start=0.0)
  hook.after_step(2, {"loss": np.float32(0.0)})
  with pytest.raises(RuntimeError, match="not aligned"):
    hook.after_checkpoint(4, {}, "unused")


def test_resume_step_is_one_dispatch_short_of_a_save():
  for save_every, k in ((24, 12), (8, 4), (100, 10)):
    step = window.resume_step(save_every, k)
    assert step >= 10000 - k and (step + k) % save_every == 0


def test_kept_batches_survive_a_stream_that_reuses_its_buffer():
  """A stream that yields views of one buffer and then overwrites it
  (a gather straight into a ring slot): the kept batches are copies.
  A batch that owns its memory is kept as it is, uncopied."""
  buffer = np.zeros((4, 3), np.float32)
  owned = []

  def stream():
    for i in range(4):
      buffer[:] = i + 1
      own = np.full((2,), i + 1, np.float32)
      owned.append(own)
      yield {"view": buffer[1:3], "reshaped": buffer.reshape(3, 4),
             "own": own}

  kept = []
  for _ in window.KeepFirst(stream(), kept, keep=2, flatten=dict):
    pass
  assert len(kept) == 2
  for i, batch in enumerate(kept):
    assert np.all(batch["view"] == i + 1), batch
    assert np.all(batch["reshaped"] == i + 1)
    assert batch["view"].base is None
    assert batch["own"] is owned[i]  # nothing copied
  assert np.all(buffer == 4)


def test_until_closed_ends_on_the_hooks_signal_only():
  """The driver calls the loop itself inside `until_closed`: the
  hook's `WindowClosed` ends it and fills the set-up split; a loop that
  returns on its own is an error."""
  hook = window.hook_for(
      loop_name="train_qtopt",
      traffic={"warm_dispatches": 1, "trace_dispatches": 0},
      seconds=0.0, period_steps=4, clock_start=0.0, work_dir="unused",
      trace=False)
  marks = {}
  with window.until_closed(hook, "train_qtopt", marks):
    _drive(hook, k=2, log_every=2, save_every=4, ends=True)
  assert hook.trace_dir is None and hook.t1 >= hook.t0
  assert set(marks) == {"loop_start_to_first_dispatch_s",
                        "first_dispatch_to_window_s", "compile_cache"}
  with pytest.raises(RuntimeError, match="before the window closed"):
    with window.until_closed(hook, "train_qtopt", {}):
      pass
