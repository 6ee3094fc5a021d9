"""The stage spans of the train loop's thread, under each of the three
trainers (ISSUE 24, ISSUE 30; docs/OBSERVABILITY.md "Stage spans of
the train loop"), and of the feed thread, the counters and named scopes
beside them, and the benchmark readers' helper that cuts a window out
of the ring."""

import re
import time

import numpy as np
import pytest

from tensor2robot_tpu import config as gin
from tensor2robot_tpu import telemetry
from tensor2robot_tpu.hooks import Hook
from tensor2robot_tpu.telemetry import core as tcore
from tensor2robot_tpu.telemetry import flightrec
from tensor2robot_tpu.telemetry import metrics as tmetrics
from tensor2robot_tpu.telemetry import perf as perf_lib

K = 2
FEED_SPANS = {"feed.pull", "feed.sample", "replay.draw",
              "replay.gather", "feed.stack", "feed.device_put",
              "feed.queue_put"}
LOOP_STAGES = {"loop.snapshot", "loop.snapshot_free", "loop.after_step",
               "loop.log", "loop.log_sync", "loop.save", "loop.save_d2h",
               "loop.save_write", "loop.after_checkpoint"}
# Trainer -> (its dispatch span, the spans of its feed thread).
TRAINERS = {
    "train_qtopt": ("qtopt.dispatch", FEED_SPANS),
    # Batches from a generator, not drawn from a replay buffer.
    "train_eval_model": ("train.dispatch",
                         FEED_SPANS - {"replay.draw", "replay.gather"}),
    # Collects on the device: no feed, so no `loop.wait_feed` either.
    "train_anakin": ("anakin.dispatch", frozenset()),
}
CHILDREN = {"feed.sample": "feed.pull", "feed.stack": "feed.pull",
            "feed.buffer_wait": "feed.pull",
            "replay.draw": "feed.sample", "replay.gather": "feed.sample",
            "loop.log_sync": "loop.log", "loop.save_d2h": "loop.save",
            "loop.save_write": "loop.save",
            "loop.after_checkpoint": "loop.save"}


def _loop_spans(trainer):
  dispatch, feed = TRAINERS[trainer]
  return LOOP_STAGES | {dispatch} | (
      {"loop.wait_feed"} if feed else set())


def _children(trainer):
  """The nestings a trainer's run must show. `feed.buffer_wait` exists
  only where the prefetcher lends: `train_qtopt`'s `lending_ring`."""
  if trainer == "train_qtopt":
    return sorted(CHILDREN)
  spans = _loop_spans(trainer) | TRAINERS[trainer][1]
  return sorted(c for c in CHILDREN if c in spans)
SCOPES = ("torso", "cem_tower", "cem_pool", "q_head", "bellman_loss",
          "backward", "optimizer", "polyak")


def _reset():
  tcore.reset_for_tests()
  tmetrics.reset_for_tests()
  perf_lib.stop_resource_sampler()
  perf_lib.set_plane_enabled(None)
  gin.clear_config()


@pytest.fixture
def clean_plane():
  _reset()
  yield
  _reset()


def _learner(**kwargs):
  from tensor2robot_tpu.research.qtopt import (
      GraspingQModel,
      QTOptLearner,
  )
  return QTOptLearner(
      GraspingQModel(image_size=16, torso_filters=(8,),
                     head_filters=(8, 8), dense_sizes=(16,),
                     action_dim=2),
      cem_population=8, cem_iterations=1, cem_elites=2, **kwargs)


def _train(model_dir, **kwargs):
  from tensor2robot_tpu.research.qtopt.train_qtopt import train_qtopt
  args = dict(learner=_learner(), model_dir=str(model_dir),
              prefill_random=True, max_train_steps=16, batch_size=16,
              log_every_steps=4, save_checkpoints_steps=8, seed=0,
              steps_per_dispatch=K)
  args.update(kwargs)
  return train_qtopt(**args)


class MockHook(Hook):
  """Counts what the loop tells it."""

  def __init__(self):
    self.calls = []

  def begin(self, model, model_dir):
    self.calls.append("begin")

  def after_step(self, step, metrics):
    self.calls.append(("after_step", step))

  def after_checkpoint(self, step, state, model_dir):
    self.calls.append(("after_checkpoint", step))

  def end(self, step, state, model_dir):
    self.calls.append("end")


def _train_eval(model_dir, **kwargs):
  """The pose-env regression through `train_eval_model`, K=2."""
  from tensor2robot_tpu import train_eval
  from tensor2robot_tpu.data.random_input_generator import (
      RandomInputGenerator,
  )
  from tensor2robot_tpu.research.pose_env import PoseEnvRegressionModel
  args = dict(
      model=PoseEnvRegressionModel(
          image_size=16, filters=(8,), embedding_size=16,
          hidden_sizes=(16,)),
      model_dir=str(model_dir),
      input_generator_train=RandomInputGenerator(batch_size=8),
      max_train_steps=16, log_every_steps=4, save_checkpoints_steps=8,
      steps_per_dispatch=K, hooks=[MockHook()])
  args.update(kwargs)
  return train_eval.train_eval_model(**args)


def _train_anakin(model_dir):
  from tensor2robot_tpu.envs.rollout import train_anakin
  return train_anakin(
      learner=_learner(), model_dir=str(model_dir), env_family="pose",
      num_envs=8, rollout_length=2, train_batches_per_iter=K,
      batch_size=8, replay_capacity=64, max_train_steps=16,
      log_every_steps=4, save_checkpoints_steps=8, seed=0,
      hooks=[MockHook()])


RUNS = {"train_qtopt": _train, "train_eval_model": _train_eval,
        "train_anakin": _train_anakin}


@pytest.fixture(scope="module")
def ring_of(tmp_path_factory):
  """trainer -> the ring its stand-alone K=2 run leaves behind (each
  trainer runs once a module)."""
  rings = {}

  def get(trainer):
    if trainer not in rings:
      _reset()
      RUNS[trainer](tmp_path_factory.mktemp(trainer))
      tracer = telemetry.get_tracer()
      rings[trainer] = tracer.snapshot_spans(), {
          "role": tracer.role, "enabled": tracer.enabled,
          "path": tracer.trace_path, "dropped": tracer.spans_dropped,
          "stack_counts": telemetry.registry().scalars("feed.stack.")}
      _reset()
    return rings[trainer]

  return get


@pytest.fixture(scope="module")
def ring(ring_of):
  """The ring a stand-alone K=2 `train_qtopt` leaves behind."""
  return ring_of("train_qtopt")


@pytest.fixture(scope="module")
def lending_ring(tmp_path_factory):
  """The ring, and the `feed.stack.` and `feed.gather.` counters, of
  the same run on
  devices whose placement copies the bytes off the host, as an
  accelerator's does: the prefetcher then lends the stream's buffers
  (ISSUE 25). The CPU client may alias a host array, so here the copy
  is made for it."""
  import jax

  from tensor2robot_tpu.data import prefetch
  real = prefetch.device_put_batch
  _reset()
  with pytest.MonkeyPatch.context() as patch:
    patch.setattr(prefetch, "_copies_off_host", lambda sharding: True)
    patch.setattr(
        prefetch, "device_put_batch",
        lambda batch, sharding: real(
            jax.tree_util.tree_map(np.array, batch), sharding))
    _train(tmp_path_factory.mktemp("lending"))
  spans = telemetry.get_tracer().snapshot_spans()
  counts = {**telemetry.registry().scalars("feed.stack."),
            **telemetry.registry().scalars("feed.gather.")}
  _reset()
  return spans, counts


def _by_name(spans, name):
  return [s for s in spans if s["name"] == name]


class TestSpansOfARun:

  @pytest.mark.parametrize("trainer", sorted(TRAINERS))
  def test_the_trainer_configures_memory_mode(self, ring_of, trainer):
    _, state = ring_of(trainer)
    assert {key: state[key] for key in
            ("role", "enabled", "path", "dropped")} == {
                "role": "trainer", "enabled": True, "path": None,
                "dropped": 0}

  @pytest.mark.parametrize("trainer,name", [
      (trainer, name) for trainer, (_, feed) in sorted(TRAINERS.items())
      for name in sorted(feed | _loop_spans(trainer))])
  def test_every_stage_is_in_the_ring_on_its_thread(self, ring_of,
                                                    trainer, name):
    spans, _ = ring_of(trainer)
    dispatch, feed = TRAINERS[trainer]
    loop_tid = _by_name(spans, dispatch)[0]["tid"]
    found = _by_name(spans, name)
    assert found, name
    want = loop_tid
    if feed:
      feed_tid = _by_name(spans, "feed.device_put")[0]["tid"]
      assert loop_tid != feed_tid
      want = feed_tid if name in feed else loop_tid
    else:
      assert not [s for s in spans if s["name"].startswith("feed.")
                  or s["name"] == "loop.wait_feed"]
    assert {s["tid"] for s in found} == {want}

  @pytest.mark.parametrize("trainer,child", [
      (trainer, child) for trainer in sorted(TRAINERS)
      for child in _children(trainer)])
  def test_children_lie_inside_their_parents(self, ring_of,
                                             lending_ring, trainer,
                                             child):
    parent = CHILDREN[child]
    rings = [ring_of(trainer)[0]]
    if trainer == "train_qtopt":
      rings.append(lending_ring[0])
    assert _by_name(rings[-1], child)
    for spans in rings:
      parents = _by_name(spans, parent)
      for c in _by_name(spans, child):
        assert any(p["tid"] == c["tid"] and p["ts"] <= c["ts"]
                   and c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-9
                   for p in parents), (child, c)

  def test_seq_ties_feed_wait_and_dispatch(self, ring):
    spans, _ = ring
    dispatches = _by_name(spans, "qtopt.dispatch")
    assert [d["args"]["step"] for d in dispatches] == list(
        range(0, 16, K))
    assert [d["args"]["seq"] for d in dispatches] == list(range(8))
    assert all(d["args"]["k"] == K for d in dispatches)
    for d in dispatches:
      seq = d["args"]["seq"]
      of = lambda name: [s for s in _by_name(spans, name)  # noqa: E731
                         if s["args"]["seq"] == seq]
      assert sorted(s["args"]["i"] for s in of("feed.sample")) == [0, 1]
      for name in ("feed.pull", "feed.stack", "feed.device_put",
                   "feed.queue_put", "loop.wait_feed"):
        assert len(of(name)) == 1, (name, seq)
      # The feed made the batch before the loop got it, and the loop
      # got it before it dispatched it.
      put, = of("feed.queue_put")
      wait, = of("loop.wait_feed")
      assert put["ts"] <= wait["ts"] + wait["dur"] <= d["ts"] + 1e-9
    stack = _by_name(spans, "feed.stack")[0]
    gather = _by_name(spans, "replay.gather")[0]
    assert stack["args"]["bytes"] == K * gather["args"]["bytes"] > 0
    assert gather["args"]["rows"] == 16
    assert isinstance(gather["args"]["native"], bool)

  def test_lending_keeps_one_stack_span_a_dispatch(self, lending_ring):
    spans, counts = lending_ring
    stacks = _by_name(spans, "feed.stack")
    seqs = [s["args"]["seq"] for s in stacks]
    assert seqs == list(range(len(seqs))) and len(seqs) >= 8
    # The first dispatch, which gives the ring its shapes, is copied
    # into its slot; the replay buffer gathers every later batch
    # straight into its slice (ISSUE 29), and the stack copies nothing.
    gather = _by_name(spans, "replay.gather")[0]
    assert [s["args"]["bytes"] for s in stacks] == \
        [K * gather["args"]["bytes"]] + [0] * (len(seqs) - 1)
    for seq in range(8):  # the dispatches the loop consumed
      of = lambda name: [s for s in _by_name(spans, name)  # noqa: E731
                         if s["args"]["seq"] == seq]
      assert sorted(s["args"]["i"] for s in of("feed.sample")) == [0, 1]
      for name in ("feed.pull", "feed.stack", "feed.device_put",
                   "feed.queue_put", "loop.wait_feed"):
        assert len(of(name)) == 1, (name, seq)
    # Every dispatch went into the ring. Inside its pull, each but the
    # first waited, after its stack, for the arrays made from the one
    # before (one transfer out of the ring at a time), and each but the
    # two that found their slot new waited for the slot's readers
    # before the first batch was gathered into it.
    assert counts == {
        "feed.stack.reused_dispatches": float(len(seqs)),
        "feed.gather.copied_batches": float(K),
        "feed.gather.in_place_batches": float(K * (len(seqs) - 1))}
    waits = _by_name(spans, "feed.buffer_wait")
    assert sorted(w["args"]["seq"] for w in waits) == sorted(
        seqs[1:] + seqs[2:])
    pulls = {p["args"]["seq"]: p for p in _by_name(spans, "feed.pull")}
    by_seq = {s["args"]["seq"]: s for s in stacks}
    samples = _by_name(spans, "feed.sample")
    for seq in seqs[1:]:
      of = sorted((w for w in waits if w["args"]["seq"] == seq),
                  key=lambda w: w["ts"])
      pull, stack = pulls[seq], by_seq[seq]
      first = min(s["ts"] for s in samples if s["args"]["seq"] == seq)
      assert all(w["tid"] == pull["tid"] and pull["ts"] <= w["ts"]
                 and w["ts"] + w["dur"] <= pull["ts"] + pull["dur"] + 1e-9
                 for w in of)
      assert stack["ts"] + stack["dur"] <= of[-1]["ts"] + 1e-9
      if seq >= 2:
        assert len(of) == 2
        assert of[0]["ts"] + of[0]["dur"] <= first + 1e-9

  def test_stack_counters_add_up_to_the_dispatches(self, ring):
    spans, state = ring
    assert state["stack_counts"] == {
        "feed.stack.fresh_dispatches":
            float(len(_by_name(spans, "feed.stack")))}

  @pytest.mark.parametrize("trainer", sorted(TRAINERS))
  def test_loop_thread_is_named_from_first_to_last_dispatch(
      self, ring_of, trainer):
    from benchmark.harness import trace_reduce
    spans, _ = ring_of(trainer)
    dispatches = _by_name(spans, TRAINERS[trainer][0])
    assert [d["args"]["step"] for d in dispatches] == list(
        range(0, 16, K))
    assert all(d["args"]["k"] == K for d in dispatches)
    tid = dispatches[0]["tid"]
    t0 = dispatches[0]["ts"]
    t1 = dispatches[-1]["ts"] + dispatches[-1]["dur"]
    covered = trace_reduce.union_ns(
        (max(s["ts"], t0), min(s["ts"] + s["dur"], t1))
        for s in spans if s["tid"] == tid
        and s["name"] in _loop_spans(trainer)
        and s["ts"] < t1 and s["ts"] + s["dur"] > t0)
    assert covered / (t1 - t0) >= 0.95

  @pytest.mark.parametrize("trainer", sorted(TRAINERS))
  def test_after_work_carries_its_own_dispatchs_step(self, ring_of,
                                                     trainer):
    """The loop runs one dispatch ahead (ISSUE 31): the stages that
    follow a dispatch start once the next has been enqueued, under the
    step of the dispatch they belong to, which is how the benchmark's
    readers pick them; the snapshot alone lies between the two."""
    spans, _ = ring_of(trainer)
    ending_at = {d["args"]["step"] + K: d
                 for d in _by_name(spans, TRAINERS[trainer][0])}
    saves = ("loop.snapshot", "loop.snapshot_free", "loop.save",
             "loop.save_d2h", "loop.save_write",
             "loop.after_checkpoint")
    for name in sorted(LOOP_STAGES):
      found = _by_name(spans, name)
      every = (8 if name in saves
               else K if name == "loop.after_step" else 4)
      assert [s["args"]["step"] for s in found] == list(
          range(every, 17, every)), name
      for s in found:
        own = ending_at[s["args"]["step"]]
        after = ending_at.get(s["args"]["step"] + K)
        assert own["ts"] + own["dur"] <= s["ts"] + 1e-9
        if after is None:  # the last dispatch's: nothing to trail
          continue
        if name == "loop.snapshot":
          assert s["ts"] + s["dur"] <= after["ts"] + 1e-9
        else:
          assert after["ts"] + after["dur"] <= s["ts"] + 1e-9, name

  def test_span_window_of_a_run_that_ran_ahead(self, ring):
    """Records at 8 and 12 cover the dispatches from 4 to 10: one save
    and two log syncs, as when every dispatch was finished before the
    next."""
    from benchmark.layer_metrics import span_window
    spans, _ = ring
    window = span_window.select(spans, [8, 12], 4, K)
    assert window is not None and window["steps"] == 8
    picked = {name: [s["args"]["step"] for s in found]
              for name, found in window["spans"].items()
              if name.startswith("loop.") and name != "loop.wait_feed"}
    assert picked == {
        "loop.after_step": [6, 8, 10, 12],
        "loop.log": [8, 12], "loop.log_sync": [8, 12],
        "loop.snapshot": [8], "loop.snapshot_free": [8],
        "loop.save": [8], "loop.save_d2h": [8],
        "loop.save_write": [8], "loop.after_checkpoint": [8]}

  def test_k1_names_the_prefetchers_own_pull(self, clean_plane,
                                             tmp_path):
    _train(tmp_path, steps_per_dispatch=1, max_train_steps=4)
    spans = telemetry.get_tracer().snapshot_spans()
    samples = _by_name(spans, "feed.sample")
    assert samples and not _by_name(spans, "feed.stack")
    assert not _by_name(spans, "feed.pull")
    assert {s["args"]["i"] for s in samples} == {0}
    seqs = [d["args"]["seq"] for d in _by_name(spans, "qtopt.dispatch")]
    assert seqs == [0, 1, 2, 3]
    assert set(seqs) <= {s["args"]["seq"] for s in samples}


def test_callers_disabled_tracer_survives_train_qtopt(clean_plane,
                                                      tmp_path):
  telemetry.configure("bench_off_arm", enabled=False)
  _train(tmp_path, max_train_steps=4)
  tracer = telemetry.get_tracer()
  assert (tracer.role, tracer.enabled) == ("bench_off_arm", False)
  assert tracer.spans_recorded == 0 and not tracer.snapshot_spans()


def test_a_step_that_raises_still_tears_train_eval_down(
    clean_plane, tmp_path, monkeypatch):
  """`train_eval_model` ran its hooks' `end` inside its `try`: a step
  that raised never reached it (ISSUE 30). The teardown is the train
  loop's now: hooks, prefetcher, writer, (sentinel,) logger."""
  from tensor2robot_tpu import train_eval
  from tensor2robot_tpu.data import prefetch
  from tensor2robot_tpu.data.random_input_generator import (
      RandomInputGenerator,
  )
  from tensor2robot_tpu.utils import checkpoints
  torn_down = []
  for cls in (prefetch.ShardedPrefetcher, checkpoints.CheckpointWriter,
              train_eval.MetricLogger):
    def close(self, _real=cls.close, _name=cls.__name__):
      torn_down.append(_name)
      _real(self)
    monkeypatch.setattr(cls, "close", close)

  class LosesItsLabels(RandomInputGenerator):
    """The third batch comes without labels: the step that gets it
    fails where it traces the loss."""

    def create_dataset(self, mode, batch_size=None):
      for i, (features, labels) in enumerate(
          super().create_dataset(mode, batch_size)):
        yield features, (None if i == 2 else labels)

  hook = MockHook()
  hook.end = lambda *args: torn_down.append("end")
  with pytest.raises(Exception) as raised:
    _train_eval(tmp_path, steps_per_dispatch=1, hooks=[hook],
                input_generator_train=LosesItsLabels(batch_size=8))
  assert not isinstance(raised.value, AssertionError)
  # The second step's `after_step` was owed until the third was enqueued
  # (the loop runs one dispatch ahead): it goes with the run.
  assert hook.calls == ["begin", ("after_step", 1)]
  assert torn_down == ["end", "ShardedPrefetcher", "CheckpointWriter",
                       "MetricLogger"]


def test_sentinel_page_dumps_the_loops_spans(clean_plane, tmp_path):
  """A stand-alone trainer's flight record used to hold an empty
  `spans` list: nothing configured the tracer."""
  gin.bind_parameter("default_watches.host_rss_budget_bytes", 1.0)
  _train(tmp_path)
  dumps = flightrec.read_dumps(flightrec.flightrec_dir(str(tmp_path)))
  assert dumps and dumps[0]["reason"].startswith("sentinel page:")
  names = {s["name"] for s in dumps[0]["spans"]}
  assert {"loop.wait_feed", "loop.log_sync", "qtopt.dispatch",
          "feed.sample"} <= names


def test_timed_iterator_takes_one_measurement(clean_plane):
  """`input_wait_fraction` and the `loop.wait_feed` span are the same
  reading; the fraction works with the tracer off."""
  from tensor2robot_tpu.data import prefetch

  def slow():
    for i in range(3):
      time.sleep(0.01)
      yield i

  off = prefetch.TimedIterator(slow())
  assert list(off) == [0, 1, 2] and off.seq == 2
  assert off.wait_secs >= 0.03
  assert not telemetry.get_tracer().snapshot_spans()
  telemetry.configure("trainer")
  on = prefetch.TimedIterator(slow())
  assert list(on) == [0, 1, 2]
  spans = telemetry.get_tracer().snapshot_spans()
  assert [s["args"]["seq"] for s in spans] == [0, 1, 2]
  # The pull that found the stream dry is waited for, not a span.
  assert on.wait_secs == pytest.approx(sum(s["dur"] for s in spans),
                                       abs=0.01)


@pytest.mark.parametrize("path", ["native", "fallback"])
def test_gather_rows_counts_rows_by_path(clean_plane, path):
  from tensor2robot_tpu.utils import native
  if path == "native" and not native.native_available():
    pytest.skip("no native library on this machine")
  src = np.arange(40, dtype=np.float32).reshape(10, 4)
  if path == "fallback":
    src = src[:, :2]  # not contiguous: numpy serves it
  idx = np.array([7, 1, 3])
  np.testing.assert_array_equal(native.gather_rows(src, idx), src[idx])
  rows = telemetry.registry().scalars("native.gather_rows.")
  assert rows == {f"native.gather_rows.{path}_rows": 3.0}


def test_span_clock_is_the_harness_clock():
  """The tracer stamps `time.monotonic`; the benchmark harness takes
  the device trace's time zero with `time.perf_counter`. A span's `ts`
  less that zero is a time on the trace only while the two are one
  clock (CLOCK_MONOTONIC on Linux)."""
  mono = time.get_clock_info("monotonic")
  perf = time.get_clock_info("perf_counter")
  assert mono.implementation == perf.implementation
  assert mono.monotonic and perf.monotonic
  assert abs(time.monotonic() - time.perf_counter()) < 1e-3


@pytest.mark.parametrize("cem_inference", ["bf16", "int8"])
def test_named_scopes_reach_the_lowered_step(cem_inference):
  import jax

  from tensor2robot_tpu.specs import make_random_tensors
  learner = _learner(cem_inference=cem_inference)
  state = learner.create_state(jax.random.PRNGKey(0), batch_size=2)
  batch = make_random_tensors(learner.transition_specification(),
                              batch_size=4, seed=0)
  if learner.needs_calibration:
    learner.calibrate(state, batch)
  text = jax.jit(learner.train_step).lower(
      state, batch, jax.random.PRNGKey(1)).as_text(debug_info=True)
  names = re.findall(r'loc\("([^"]*/[^"]*)"', text)
  for scope in SCOPES:
    assert any(re.search(rf"(^|/){scope}/", name) for name in names), \
        scope
  # The online critic's pass is the backward scope's, torso included.
  assert any("/backward/" in name and "/torso/" in name
             for name in names)


# ---- the benchmark readers' helper, on made-up spans ----


def _span(name, ts, dur, tid, **args):
  return {"name": name, "ts": ts, "dur": dur, "tid": tid, "args": args}


def _made_up_run(dispatches=4, k=2, period=1.0):
  """`dispatches` cycles of `period` seconds on a loop thread (1) and
  a feed thread (2) that runs one cycle ahead; steps start at 100."""
  spans = []
  for seq in range(dispatches):
    step = 100 + seq * k
    f0 = seq * period  # feed cycle: 0.95 of 1.0 named
    for i in range(k):
      at = f0 + 0.2 * i
      spans.append(_span("replay.gather", at + 0.05, 0.1, 2, rows=8))
      spans.append(_span("feed.sample", at, 0.2, 2, seq=seq, i=i))
    if k > 1:  # the pull frees for 0.05 after the stack
      spans.append(_span("feed.stack", f0 + 0.4, 0.1, 2, seq=seq))
      spans.append(_span("feed.pull", f0, 0.55, 2, seq=seq))
    spans.append(_span("feed.device_put", f0 + 0.55, 0.3, 2, seq=seq))
    spans.append(_span("feed.queue_put", f0 + 0.85, 0.1, 2, seq=seq))
    l0 = (seq + 1) * period  # loop cycle: 0.9 of 1.0 named
    spans.append(_span("loop.wait_feed", l0, 0.5, 1, seq=seq))
    spans.append(_span("qtopt.dispatch", l0 + 0.5, 0.1, 1, step=step,
                       k=k, seq=seq))
    spans.append(_span("loop.log_sync", l0 + 0.65, 0.1 + 0.01 * seq, 1,
                       step=step + k))
    spans.append(_span("loop.log", l0 + 0.6, 0.3, 1, step=step + k))
  return spans


class TestSpanWindow:

  def _select(self, spans, steps, k=2):
    from benchmark.layer_metrics import span_window
    return span_window.select(spans, steps, k, k)

  def test_window_by_step_and_self_time_by_containment(self):
    spans = _made_up_run()
    # Records at steps 104 and 106 cover the dispatches from 102, 104.
    window = self._select(spans, [104, 106])
    assert window["steps"] == 4 and window["seqs"] == [1, 2]
    loop, feed = window["loop"], window["feed"]
    assert (loop["tid"], feed["tid"]) == (1, 2)
    assert loop["seconds"] == pytest.approx(2.0)
    assert loop["self_s"]["loop.log"] == pytest.approx(
        2 * 0.3 - 0.11 - 0.12)
    assert loop["self_s"]["loop.log_sync"] == pytest.approx(0.23)
    assert loop["unnamed_s"] == pytest.approx(2 * 0.1)
    assert feed["seconds"] == pytest.approx(2.0)
    assert feed["self_s"]["feed.sample"] == pytest.approx(4 * 0.1)
    assert feed["self_s"]["replay.gather"] == pytest.approx(4 * 0.1)
    assert feed["self_s"]["feed.pull"] == pytest.approx(2 * 0.05)
    assert feed["unnamed_s"] == pytest.approx(2 * 0.05)
    assert len(window["spans"]["loop.log_sync"]) == 2

  def test_readers_on_the_made_up_run(self, clean_plane):
    from benchmark.layer_metrics import (
        feed_device_put_ms_per_step,
        feed_native_gather_share,
        feed_queue_full_share,
        feed_sample_ms_per_step,
        feed_stack_ms_per_step,
        host_unnamed_share,
        loop_log_sync_ms,
        loop_save_ms,
        span_window,
    )
    run = {"span_window": self._select(_made_up_run(), [104, 106])}
    assert feed_sample_ms_per_step.read(run) == pytest.approx(200.0)
    assert feed_stack_ms_per_step.read(run) == pytest.approx(50.0)
    assert feed_device_put_ms_per_step.read(run) == pytest.approx(150.0)
    assert feed_queue_full_share.read(run) == pytest.approx(10.0)
    assert loop_log_sync_ms.read(run) == pytest.approx(115.0)
    assert loop_save_ms.read(run) is None  # no save in the window
    assert host_unnamed_share.read(run) == pytest.approx(10.0)  # loop
    assert feed_native_gather_share.read(run) is None  # nothing counted
    tmetrics.counter("native.gather_rows.native_rows").inc(30)
    tmetrics.counter("native.gather_rows.fallback_rows").inc(10)
    assert feed_native_gather_share.read(run) == pytest.approx(75.0)
    assert span_window.of_run(run) is run["span_window"]

  def test_stack_reuse_share_reads_the_two_counters(self, clean_plane):
    from benchmark.layer_metrics import feed_stack_reuse_share
    assert feed_stack_reuse_share.read({}) is None  # as on the parent
    tmetrics.counter("feed.stack.reused_dispatches").inc(3)
    assert feed_stack_reuse_share.read({}) == pytest.approx(100.0)
    tmetrics.counter("feed.stack.fresh_dispatches").inc(1)
    assert feed_stack_reuse_share.read({}) == pytest.approx(75.0)

  def test_gather_in_place_share_reads_the_two_counters(self,
                                                       clean_plane):
    from benchmark.layer_metrics import feed_gather_in_place_share
    assert feed_gather_in_place_share.read({}) is None  # the parent
    tmetrics.counter("feed.gather.copied_batches").inc(4)
    assert feed_gather_in_place_share.read({}) == pytest.approx(0.0)
    tmetrics.counter("feed.gather.in_place_batches").inc(12)
    assert feed_gather_in_place_share.read({}) == pytest.approx(75.0)

  def test_none_after_a_rolled_ring_never_a_partial_number(self):
    spans = _made_up_run()
    steps = [102, 104, 106]
    assert self._select(spans, steps)["steps"] == 6
    # The ring drops the oldest spans first: without the first
    # dispatch's feed spans, or its dispatch span, there is no window.
    assert self._select(spans[5:], steps) is None
    rolled = [s for s in spans if not (
        s["name"] == "qtopt.dispatch" and s["args"]["seq"] == 0)]
    assert self._select(rolled, steps) is None
    assert self._select([], steps) is None  # the parent has no spans
    assert self._select(spans, []) is None

  def test_unstacked_feed_has_neither_pull_nor_stack(self):
    window = self._select(_made_up_run(k=1), [101, 102], k=1)
    assert window["seqs"] == [0, 1] and window["steps"] == 2
    assert "feed.stack" not in window["spans"]
    assert window["feed"]["seconds"] == pytest.approx(2.0)

  def test_of_run_reads_this_processes_ring(self, clean_plane,
                                            monkeypatch):
    from benchmark.layer_metrics import host_unnamed_share, span_window
    monkeypatch.setattr(telemetry.get_tracer(), "snapshot_spans",
                        _made_up_run)
    run = {"records": [{"step": 104}, {"step": 106}], "k": 2,
           "config": {"train": {"log_every_steps": 2}}}
    assert span_window.of_run(run)["seqs"] == [1, 2]
    assert host_unnamed_share.read(run) == pytest.approx(10.0)
