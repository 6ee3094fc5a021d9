"""The train loop runs one dispatch ahead of its own bookkeeping
(ISSUE 31; `tensor2robot_tpu/train_loop.py`): a stub trainer with a
donated state and hooks that record what the loop tells them, in what
order, and a short `train_qtopt` against the same run in today's
order."""

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu import config as gin
from tensor2robot_tpu import telemetry, train_loop
from tensor2robot_tpu.hooks import Hook
from tensor2robot_tpu.telemetry import core as tcore
from tensor2robot_tpu.telemetry import metrics as tmetrics
from tensor2robot_tpu.telemetry import perf as perf_lib
from tensor2robot_tpu.utils import checkpoints as ckpt_lib

LOG_EVERY, SAVE_EVERY = 2, 4


def _reset():
  tcore.reset_for_tests()
  tmetrics.reset_for_tests()
  perf_lib.stop_resource_sampler()
  perf_lib.set_plane_enabled(None)
  gin.clear_config()


@pytest.fixture(autouse=True)
def clean_plane():
  _reset()
  yield
  _reset()


class Recorder(Hook):
  """Appends what the loop tells it to `events`, with the state it is
  handed read on the spot."""

  def __init__(self, events, raise_at=None):
    self.events, self._raise_at = events, raise_at

  def after_step(self, step, metrics):
    self.events.append(("after_step", step))
    if step == self._raise_at:
      raise RuntimeError(f"hook fails at {step}")

  def after_checkpoint(self, step, state, model_dir):
    self.events.append(("after_checkpoint", step,
                        float(np.asarray(state["w"])[0])))

  def end(self, step, state, model_dir):
    self.events.append(("end", step,
                        float(np.asarray(state["w"])[0])))


class OnlineRecorder(Recorder):
  drives_online_collection = True


class Online(Hook):
  drives_online_collection = True


@functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
def _k_steps(state, k):
  state = {"w": state["w"] + k, "step": state["step"] + k}
  return state, {"loss": state["w"].sum()}


@pytest.fixture
def events(monkeypatch):
  """What the stub trainer, its hooks, the writer and the logger did,
  in order."""
  events = []
  real_save = ckpt_lib.CheckpointWriter.save

  def save(self, step, state, *rest, **kwargs):
    events.append(("save", step, float(np.asarray(state["w"])[0])))
    return real_save(self, step, state, *rest, **kwargs)

  real_write = train_loop.MetricLogger.write

  def write(self, tag, step, scalars):
    events.append(("log", step, float(scalars["loss"])))
    return real_write(self, tag, step, scalars)

  monkeypatch.setattr(ckpt_lib.CheckpointWriter, "save", save)
  monkeypatch.setattr(train_loop.MetricLogger, "write", write)
  return events


def _stub_trainer(model_dir, events, *, hooks, max_train_steps, k=1,
                  boundary_work=False, stalls=None):
  """What the three trainers do around a `TrainLoop`, on a state whose
  `w` counts the steps taken. The state a dispatch is handed is deleted
  as soon as the next is enqueued, which is what donation does on a
  device that donates."""
  loop = train_loop.TrainLoop(
      str(model_dir), hooks, dispatch_span="stub.dispatch",
      steps_per_dispatch=k, max_train_steps=max_train_steps,
      log_every_steps=LOG_EVERY * k, save_checkpoints_steps=SAVE_EVERY * k,
      max_checkpoints_to_keep=5)
  state = {"w": jnp.zeros((4,), jnp.float32),
           "step": jnp.zeros((), jnp.int32)}
  resume = ckpt_lib.latest_step(str(model_dir))
  if resume is not None:
    state = ckpt_lib.restore_state(str(model_dir), like=state,
                                   step=resume)

  def own_scalars(scalars, steps, dt, stall_secs):
    if stalls is not None:
      stalls.append(stall_secs)  # one a record, in the records' order
    scalars["steps_per_sec"] = steps / max(dt, 1e-9)
    return "steps_per_sec"

  loop.begin(
      None, int(state["step"]), flops_per_step=None, devices=1,
      state=lambda: state, save_payload=lambda st: (st,),
      hook_state=lambda st: st, own_scalars=own_scalars,
      boundary_work=(
          (lambda step: events.append(
              ("boundary", step, float(np.asarray(state["w"])[0]))))
          if boundary_work else None))
  with loop:
    for _ in loop.dispatches():
      handed = state
      with loop.dispatch():
        state, metrics = _k_steps(state, k)
      events.append(("enqueue", loop.step + k))
      for leaf in jax.tree_util.tree_leaves(handed):
        if not leaf.is_deleted():
          leaf.delete()
      loop.after_dispatch(metrics)
  return state


def _after_work(step, k, last, boundary_work=False):
  """What follows the dispatch that ends at `step`, in today's order."""
  events = [("after_step", step)]
  if step % (LOG_EVERY * k) == 0 or step == last:
    events.append(("log", step, 4.0 * step))
  if step % (SAVE_EVERY * k) == 0 or step == last:
    events += [("save", step, float(step)),
               ("after_checkpoint", step, float(step))]
  if boundary_work:
    events.append(("boundary", step, float(step)))
  return events


def _expected(last, k, ahead, boundary_work=False):
  events, owed = [], []
  for step in range(k, last + 1, k):
    events.append(("enqueue", step))
    if ahead:
      events += owed
      owed = _after_work(step, k, last)
    else:
      events += _after_work(step, k, last, boundary_work)
  return events + owed + [("end", last, float(last))]


def _counts():
  return telemetry.registry().scalars("loop.dispatches.")


@pytest.mark.parametrize("k,last", [(1, 8), (1, 7), (2, 12)])
def test_after_work_trails_the_enqueue_by_one_dispatch(
    tmp_path, events, k, last):
  """Dispatch k + 1 is enqueued before anything waits on k; the stages
  of a dispatch come in today's order with that dispatch's step; the
  state the writer and `after_checkpoint` get is the one after exactly
  that step though the live one was donated; the last dispatch's
  record and save are written before `end`."""
  state = _stub_trainer(tmp_path, events, hooks=[Recorder(events)],
                        max_train_steps=last, k=k)
  assert events == _expected(last, k, ahead=True)
  assert float(state["w"][0]) == last
  n = last // k
  assert _counts() == {"loop.dispatches.drained": 1.0,
                       "loop.dispatches.ran_ahead": float(n - 1)}
  # What went to disk is the snapshot, not a later state.
  for step in ckpt_lib.list_steps(str(tmp_path)):
    restored = ckpt_lib.restore_state(
        str(tmp_path), like=jax.device_get(state), step=step)
    assert float(restored["w"][0]) == step == int(restored["step"])
  assert ckpt_lib.latest_step(str(tmp_path)) == last
  with open(os.path.join(tmp_path, "metrics_train.jsonl")) as f:
    records = [json.loads(line) for line in f]
  assert [(r["step"], r["payload"]["loss"]) for r in records] == [
      (e[1], e[2]) for e in events if e[0] == "log"]


@pytest.mark.parametrize("why", ["boundary_work", "online_collection"])
def test_a_run_that_needs_the_live_state_keeps_todays_order(
    tmp_path, events, why):
  """Work between dispatches reads the live state; hooks that drive
  online collection would gain K steps of sampling lead."""
  hook = (OnlineRecorder if why == "online_collection"
          else Recorder)(events)
  _stub_trainer(tmp_path, events, hooks=[hook], max_train_steps=8,
                boundary_work=why == "boundary_work")
  assert events == _expected(8, 1, ahead=False,
                             boundary_work=why == "boundary_work")
  assert _counts() == {"loop.dispatches.drained": 8.0}


def test_a_hook_that_raises_drops_the_pending_save(tmp_path, events):
  """`after_step(8)` raises with the save of 8 owed and 9 enqueued: the
  teardown runs, nothing of 8 is written, and a resume starts from the
  newest complete checkpoint."""
  with pytest.raises(RuntimeError, match="hook fails at 8"):
    _stub_trainer(tmp_path, events,
                  hooks=[Recorder(events, raise_at=8)],
                  max_train_steps=12)
  assert events[-4:] == [("after_step", 7), ("enqueue", 9),
                         ("after_step", 8), ("end", 9, 9.0)]
  assert [e for e in events if e[0] == "save"] == [("save", 4, 4.0)]
  assert ckpt_lib.latest_step(str(tmp_path)) == 4
  assert _counts() == {"loop.dispatches.drained": 1.0,
                       "loop.dispatches.ran_ahead": 8.0}
  crashed = len(events)
  state = _stub_trainer(tmp_path, events, hooks=[Recorder(events)],
                        max_train_steps=12)
  assert events[crashed] == ("enqueue", 5)
  assert float(state["w"][0]) == 12.0
  assert ckpt_lib.list_steps(str(tmp_path)) == [4, 8, 12]


@pytest.mark.parametrize("raise_at", [8, None])
def test_the_teardown_waits_for_the_dispatch_in_flight(
    tmp_path, events, monkeypatch, raise_at):
  """A loop that ends, on a hook's exception with dispatch 9 enqueued or
  at its last step, hands back a device that is done: it waited for the
  live state after the hooks' `end` and before it closed its services
  (ISSUE 35: the benchmark's check lost the device's memory to the
  program still in flight)."""
  waited = []

  class Jax:
    def __getattr__(self, name):
      return getattr(jax, name)

    def block_until_ready(self, tree):
      waited.append(float(np.asarray(tree["w"])[0]))
      events.append(("wait", waited[-1]))
      return jax.block_until_ready(tree)

  monkeypatch.setattr(train_loop, "jax", Jax())
  hooks = [Recorder(events, raise_at=raise_at)]
  if raise_at is None:
    _stub_trainer(tmp_path, events, hooks=hooks, max_train_steps=12)
  else:
    with pytest.raises(RuntimeError, match="hook fails at 8"):
      _stub_trainer(tmp_path, events, hooks=hooks, max_train_steps=12)
  last = 12.0 if raise_at is None else 9.0
  assert events[-2:] == [("end", int(last), last), ("wait", last)]


def test_the_teardown_shows_the_calls_error_not_a_deleted_states(
    tmp_path, events):
  """A jitted call that raises has taken its donated state with it; the
  teardown's wait finds it deleted and says nothing over that error."""
  loop = train_loop.TrainLoop(
      str(tmp_path), [], dispatch_span="stub.dispatch",
      steps_per_dispatch=1, max_train_steps=4, log_every_steps=LOG_EVERY,
      save_checkpoints_steps=SAVE_EVERY, max_checkpoints_to_keep=5)
  state = {"w": jnp.zeros((4,), jnp.float32),
           "step": jnp.zeros((), jnp.int32)}
  loop.begin(None, 0, flops_per_step=None, devices=1,
             state=lambda: state, save_payload=lambda st: (st,),
             hook_state=lambda st: st,
             own_scalars=lambda scalars, steps, dt, stall: None)
  with pytest.raises(ValueError, match="the call fails"):
    with loop:
      for _ in loop.dispatches():
        state["w"].delete()
        raise ValueError("the call fails")


class _BusyDevice:
  """`jax`, with a device that takes 0.2 s to finish a dispatch."""

  def __getattr__(self, name):
    return getattr(jax, name)

  def device_get(self, tree):
    time.sleep(0.2)
    return jax.device_get(tree)


@pytest.mark.parametrize("busy_device", [False, True])
def test_a_save_in_the_devices_shadow_is_no_stall(tmp_path, events,
                                                  monkeypatch,
                                                  busy_device):
  """`stall_secs` is what a record's interval lost to saves. Ahead of
  its after-work the loop saves while the next dispatch executes: what
  it then waits for the device anyway was not lost."""
  real_save = ckpt_lib.CheckpointWriter.save

  def slow_save(self, *args, **kwargs):
    time.sleep(0.05)
    return real_save(self, *args, **kwargs)

  monkeypatch.setattr(ckpt_lib.CheckpointWriter, "save", slow_save)
  if busy_device:
    monkeypatch.setattr(train_loop, "jax", _BusyDevice())
  stalls = []
  _stub_trainer(tmp_path, events, hooks=[], max_train_steps=12,
                stalls=stalls)
  stalls = dict(zip((2, 4, 6, 8, 10, 12), stalls, strict=True))
  # The saves of 4 and 8 follow the records of 4 and 8: they fall in
  # the intervals of the records of 6 and 10.
  for step in (6, 10):
    if busy_device:
      assert stalls[step] == 0.0
    else:
      assert stalls[step] >= 0.04
  assert stalls[2] == stalls[4] == stalls[8] == 0.0


def test_the_snapshot_compiles_once_with_the_runs_other_programs(
    tmp_path, events, monkeypatch):
  """`begin` warms the copy program on the state the run starts from:
  the snapshots of the run find it compiled (a compile at the first
  save would read as a warm-path recompile)."""
  train_loop._copy_on_device.clear_cache()
  compiled = []
  real = train_loop.TrainLoop._snapshot

  def snapshot(self, step):
    compiled.append(train_loop._copy_on_device._cache_size())
    return real(self, step)

  monkeypatch.setattr(train_loop.TrainLoop, "_snapshot", snapshot)
  _stub_trainer(tmp_path, events, hooks=[], max_train_steps=8)
  assert compiled == [1, 1]
  assert train_loop._copy_on_device._cache_size() == 1


def test_reader_of_the_two_counters():
  from benchmark.layer_metrics import loop_run_ahead_share
  assert loop_run_ahead_share.read({}) is None  # as on the parent
  tmetrics.counter("loop.dispatches.drained").inc(1)
  assert loop_run_ahead_share.read({}) == pytest.approx(0.0)
  tmetrics.counter("loop.dispatches.ran_ahead").inc(99)
  assert loop_run_ahead_share.read({}) == pytest.approx(99.0)


# Scalars of a log record that the clock or the machine decides.
_TIMED = ("grad_steps_per_sec", "input_wait_fraction", "perf.", "rsrc.",
          "compile_cache.", "replay_", "startup.")


def _qtopt_run(model_dir, hooks, **kwargs):
  from tensor2robot_tpu.research.qtopt import (
      GraspingQModel,
      QTOptLearner,
  )
  from tensor2robot_tpu.research.qtopt.train_qtopt import train_qtopt
  learner = QTOptLearner(
      GraspingQModel(image_size=16, torso_filters=(8,),
                     head_filters=(8, 8), dense_sizes=(16,),
                     action_dim=2),
      cem_population=8, cem_iterations=1, cem_elites=2)
  state = train_qtopt(
      learner=learner, model_dir=str(model_dir), prefill_random=True,
      max_train_steps=12, batch_size=16, log_every_steps=2,
      save_checkpoints_steps=4, seed=0, steps_per_dispatch=2,
      hooks=hooks, **kwargs)
  with open(os.path.join(model_dir, "metrics_train.jsonl")) as f:
    records = [json.loads(line) for line in f]
  return jax.device_get(state), [
      (r["step"], {key: value for key, value in r["payload"].items()
                   if not key.startswith(_TIMED)}) for r in records]


def test_train_qtopt_ahead_equals_todays_order_bit_for_bit(tmp_path):
  """Same programs, same rows, same order: the final state, every
  checkpoint and every record's learner scalars of a run that ran ahead
  equal those of the run in today's order (a hook that drives online
  collection keeps it)."""
  ahead, ahead_records = _qtopt_run(tmp_path / "ahead", [])
  assert _counts() == {"loop.dispatches.drained": 1.0,
                       "loop.dispatches.ran_ahead": 5.0}
  _reset()
  today, today_records = _qtopt_run(tmp_path / "today", [Online()])
  assert _counts() == {"loop.dispatches.drained": 6.0}
  assert ahead_records == today_records
  assert [step for step, _ in ahead_records] == [2, 4, 6, 8, 10, 12]
  assert len(ahead_records[0][1]) >= 3
  same = jax.tree_util.tree_map(
      lambda a, b: a.dtype == b.dtype and np.array_equal(a, b),
      ahead, today)
  assert all(jax.tree_util.tree_leaves(same))
  for step in (4, 8, 12):
    saved = [ckpt_lib.restore_state(str(tmp_path / name), like=ahead,
                                    step=step)
             for name in ("ahead", "today")]
    assert int(np.asarray(saved[0].step)) == step
    same = jax.tree_util.tree_map(np.array_equal, *saved)
    assert all(jax.tree_util.tree_leaves(same))


@pytest.mark.parametrize("hooks,asked,depth", [
    ([], None, 1), ([], 3, 2), ([], 1, 1),
    ([Online()], None, 1), ([Online()], 3, 3)])
def test_the_dispatch_in_flight_is_one_of_the_feeds_depth(
    tmp_path, monkeypatch, hooks, asked, depth):
  """`train_qtopt`'s prefetch depth counts the dispatches resident on
  the device ahead of compute; a run ahead keeps one of them in flight
  itself (a queue of two beside it held five in all: 13.7 GB of 16.9 in
  `qtopt_472.train`)."""
  from tensor2robot_tpu.data import prefetch
  depths = []
  real = prefetch.ShardedPrefetcher.__init__

  def init(self, iterator, sharding, buffer_size=2):
    depths.append(buffer_size)
    real(self, iterator, sharding, buffer_size=buffer_size)

  monkeypatch.setattr(prefetch.ShardedPrefetcher, "__init__", init)
  _qtopt_run(tmp_path, hooks, prefetch_buffer_size=asked)
  assert depths == [depth]


# --- a state that fills the chip (ISSUE 34) ---


def _expected_without_a_copy(last, k):
  """A run ahead whose save steps finish their own dispatch: what a
  save step owes comes right after its enqueue (and after what the
  dispatch before it still owed)."""
  events, owed = [], []
  for step in range(k, last + 1, k):
    events.append(("enqueue", step))
    events += owed
    owed = _after_work(step, k, last)
    if step % (SAVE_EVERY * k) == 0 or step == last:
      events, owed = events + owed, []
  return events + owed + [("end", last, float(last))]


@pytest.mark.parametrize("bytes_limit,copies", [
    (1 << 30, True),   # 20 B of state beside a GB
    (79, False),       # 2 x 20 B do not fit in half of 79 B
    (80, True),        # ... and just fit in half of 80 B
    (None, True),      # a runtime that reports no limit (a CPU)
])
def test_the_loop_decides_from_the_states_bytes_whether_to_copy_it(
    tmp_path, events, monkeypatch, bytes_limit, copies):
  """`begin` holds the state's bytes (4 floats and a counter: 20)
  against the device's `bytes_limit`, nothing else. Where a copy does
  not fit no copy is ever made, a save step's dispatch is finished and
  its live state saved before the next is enqueued, and between saves
  the loop runs ahead as it did."""
  monkeypatch.setattr(train_loop, "_bytes_limit",
                      lambda devices: bytes_limit)
  copied = []
  real_copy = train_loop._copy_on_device
  monkeypatch.setattr(
      train_loop, "_copy_on_device",
      lambda state: copied.append(1) or real_copy(state))
  last = 12
  state = _stub_trainer(tmp_path, events, hooks=[Recorder(events)],
                        max_train_steps=last)
  assert float(state["w"][0]) == last
  gauge = telemetry.registry().scalars("loop.state_copy_fits")
  assert gauge == {"loop.state_copy_fits": float(copies)}
  if copies:
    assert events == _expected(last, 1, ahead=True)
    assert len(copied) == 1 + last // SAVE_EVERY  # begin's, the saves'
    assert _counts() == {"loop.dispatches.drained": 1.0,
                         "loop.dispatches.ran_ahead": last - 1.0}
  else:
    assert events == _expected_without_a_copy(last, 1)
    assert copied == []
    # The first dispatch and each save step's successor found nothing
    # owed; the last save step has no successor.
    saves = last // SAVE_EVERY
    assert _counts() == {"loop.dispatches.drained": float(saves),
                         "loop.dispatches.ran_ahead":
                             float(last - saves)}
  # What went to disk is the state after exactly that step, either way.
  for step in ckpt_lib.list_steps(str(tmp_path)):
    restored = ckpt_lib.restore_state(
        str(tmp_path), like=jax.device_get(state), step=step)
    assert float(restored["w"][0]) == step


def test_state_copy_fits_reads_one_devices_share_of_the_state(
    monkeypatch):
  state = {"w": jnp.zeros((1024,), jnp.float32), "n": 3, "name": "x"}
  bytes_limit = train_loop._bytes_limit  # the real one
  for limit, fits in ((4 * 4096, True), (4 * 4096 - 2, False),
                      (None, True)):
    monkeypatch.setattr(train_loop, "_bytes_limit",
                        lambda devices, limit=limit: limit)
    assert train_loop.state_copy_fits(state) is fits
  assert train_loop.state_copy_fits({"n": 3}) is True
  # The runtime's own report, where it has one (a CPU has none).
  assert (bytes_limit(jax.devices()[:1]) or 1) > 0

  class Device:
    def __init__(self, stats):
      self._stats = stats

    def memory_stats(self):
      return self._stats

  assert bytes_limit([Device({"bytes_limit": 7}),
                      Device({"bytes_limit": 5}), Device(None)]) == 5
  assert bytes_limit([Device(None), Device({})]) is None
