"""Telemetry-plane tests (ISSUE 11): the ring, the registry, the
envelope, the merge, and the flight recorder — pinned contracts:

  * the span ring stays BOUNDED under multi-threaded churn and the
    recorded/flushed/dropped accounting stays consistent;
  * the merge tool produces ONE host-clock-ordered timeline with
    per-file clock offsets applied (the cross-process ordering pin);
  * a latched fleet error produces flight-recorder dumps from the
    crashing learner, the live host, AND the orchestrator (reusing the
    crash-policy harness of tests/test_fleet.py);
  * the whole telemetry package imports WITHOUT jax (actor/worker
    processes record spans — the IMP401 worker-safe property);
  * the tracing fast paths stay cheap (in-process; what tracing costs
    a step on the chip: PERF.md §6, PR 38);
  * every `metrics_<tag>.jsonl` record the tier-1 trainers produce is
    the unified `{step, wall, role, payload}` envelope.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tensor2robot_tpu.telemetry import core as tcore
from tensor2robot_tpu.telemetry import flightrec
from tensor2robot_tpu.telemetry import merge as merge_lib
from tensor2robot_tpu.telemetry import metrics as tmetrics
from tensor2robot_tpu.telemetry import records as trecords

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolated_telemetry():
  """Fresh process-global tracer/registry per test (both are
  process-wide singletons by design)."""
  tcore.reset_for_tests()
  tmetrics.reset_for_tests()
  yield
  tcore.reset_for_tests()
  tmetrics.reset_for_tests()


class TestSpanRing:

  def test_ring_bounds_under_churn(self):
    """Memory-mode ring: 8 threads × 5000 spans against capacity 512 —
    the ring never exceeds its bound, nothing crashes, and the
    recorded/dropped accounting closes."""
    tracer = tcore.Tracer().configure("churn", capacity=512)
    threads_n, per_thread = 8, 5000

    def hammer(i):
      for j in range(per_thread):
        with tracer.span("work", thread=i):
          pass

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(threads_n)]
    for t in threads:
      t.start()
    for t in threads:
      t.join()
    total = threads_n * per_thread
    assert tracer.spans_recorded == total
    assert tracer.pending <= 512
    # Everything beyond the ring aged out (memory mode never flushes).
    assert tracer.spans_dropped == total - tracer.pending
    # The survivors are well-formed span dicts.
    snap = tracer.snapshot_spans()
    assert len(snap) == tracer.pending
    assert all(s["name"] == "work" and s["role"] == "churn"
               for s in snap)

  def test_flush_to_file_with_meta_and_offset(self, tmp_path):
    tracer = tcore.Tracer().configure("host", trace_dir=str(tmp_path))
    with tracer.span("alpha", x=1):
      pass
    tracer.set_clock_offset(0.25)
    with tracer.span("beta"):
      pass
    tracer.close()
    lines = [json.loads(line) for line in
             open(tmp_path / "trace_host.jsonl")]
    metas = [r for r in lines if r["ph"] == "M"]
    spans = [r for r in lines if r["ph"] == "X"]
    # Configure wrote one meta, set_clock_offset another.
    assert len(metas) == 2
    assert metas[0]["clock_offset"] == 0.0
    assert metas[1]["clock_offset"] == 0.25
    assert [s["name"] for s in spans] == ["alpha", "beta"]
    assert spans[0]["args"] == {"x": 1}
    assert all(s["role"] == "host" and s["pid"] == os.getpid()
               for s in spans)

  def test_auto_flush_keeps_ring_small(self, tmp_path):
    tracer = tcore.Tracer().configure("w", trace_dir=str(tmp_path))
    for _ in range(3 * tcore.FLUSH_BATCH):
      tracer.event("tick")
    # File-backed tracers flush at FLUSH_BATCH: nothing dropped.
    assert tracer.spans_dropped == 0
    assert tracer.pending < tcore.FLUSH_BATCH
    tracer.close()
    spans = [json.loads(line) for line in open(tmp_path / "trace_w.jsonl")
             if json.loads(line)["ph"] == "X"]
    assert len(spans) == 3 * tcore.FLUSH_BATCH

  def test_span_fast_paths_are_cheap(self):
    """The in-process overhead pin: disabled spans must be ~free, enabled
    memory-mode spans micro-scale. Bounds are generous for loaded CI
    hosts — they catch a lock or an I/O call landing on the hot path,
    not microarchitecture."""
    tracer = tcore.Tracer()  # unconfigured = disabled
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
      with tracer.span("x"):
        pass
    disabled_us = (time.perf_counter() - t0) / n * 1e6
    tracer.configure("bench", capacity=1024)
    t0 = time.perf_counter()
    for _ in range(n):
      with tracer.span("x"):
        pass
    enabled_us = (time.perf_counter() - t0) / n * 1e6
    assert disabled_us < 5.0, f"disabled span {disabled_us:.2f}µs"
    assert enabled_us < 50.0, f"enabled span {enabled_us:.2f}µs"


class TestMetricsRegistry:

  def test_snapshot_schema_and_scalars(self):
    registry = tmetrics.MetricsRegistry()
    registry.counter("replay.adds").inc(64)
    registry.gauge("replay.fill").set(0.5)
    hist = registry.histogram("serving.bucket_8_ms")
    for value in (0.2, 0.4, 1.0, 3.0, 90.0):
      hist.observe(value)
    snap = registry.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["replay.adds"] == 64.0
    assert snap["gauges"]["replay.fill"] == 0.5
    h = snap["histograms"]["serving.bucket_8_ms"]
    assert set(h) >= {"bounds", "counts", "count", "sum", "min",
                      "max", "p50", "p95"}
    assert h["count"] == 5 and h["min"] == 0.2 and h["max"] == 90.0
    assert sum(h["counts"]) == 5
    # Quantiles are bucket-interpolated but must bracket sanely.
    assert 0.2 <= h["p50"] <= 3.0
    assert h["p95"] <= 100.0
    flat = registry.scalars()
    assert flat["replay.adds"] == 64.0
    assert "serving.bucket_8_ms_p50" in flat
    assert registry.scalars("replay.") == {
        "replay.adds": 64.0, "replay.fill": 0.5}

  def test_counter_exact_under_threads(self):
    counter = tmetrics.MetricsRegistry().counter("c")
    threads = [threading.Thread(
        target=lambda: [counter.inc() for _ in range(10_000)])
        for _ in range(8)]
    for t in threads:
      t.start()
    for t in threads:
      t.join()
    assert counter.value == 80_000.0

  def test_scalars_from_snapshot_prefix(self):
    registry = tmetrics.MetricsRegistry()
    registry.counter("actor.episodes").inc(3)
    flat = tmetrics.scalars_from_snapshot(registry.snapshot(),
                                          prefix="actor-1/")
    assert flat == {"actor-1/actor.episodes": 3.0}


class TestRecordEnvelope:

  def test_make_validate_normalize_roundtrip(self):
    record = trecords.make_record(7, {"loss": 0.5, "steps": 2.0},
                                  role="learner")
    assert trecords.validate_record(record) == []
    flat = trecords.normalize_record(record)
    assert flat["step"] == 7 and flat["role"] == "learner"
    assert flat["loss"] == 0.5

  def test_validator_rejects_malformed(self):
    assert trecords.validate_record([1, 2]) != []
    assert any("missing" in p for p in trecords.validate_record({}))
    bad = trecords.make_record(1, {"x": 1.0})
    bad["payload"]["y"] = "not-a-number"
    assert trecords.validate_record(bad) != []
    bad2 = trecords.make_record(1, {})
    bad2["extra"] = 1
    assert any("unexpected" in p for p in trecords.validate_record(bad2))

  def test_reader_normalizes_legacy_flat_records(self, tmp_path):
    path = tmp_path / "metrics_train.jsonl"
    path.write_text(
        json.dumps({"step": 5, "loss": 1.0}) + "\n" +
        json.dumps(trecords.make_record(10, {"loss": 0.5})) + "\n")
    records = trecords.read_records(str(path))
    assert [r["step"] for r in records] == [5, 10]
    assert [r["loss"] for r in records] == [1.0, 0.5]

  def test_metric_logger_emits_envelope(self, tmp_path):
    from tensor2robot_tpu.train_eval import MetricLogger

    logger = MetricLogger(str(tmp_path), role="anakin")
    logger.write("train", 4, {"loss": np.float32(0.25)})
    logger.close()
    raw = [json.loads(line) for line in
           open(tmp_path / "metrics_train.jsonl")]
    assert len(raw) == 1
    assert trecords.validate_record(raw[0]) == []
    assert raw[0]["role"] == "anakin"
    assert raw[0]["payload"] == {"loss": 0.25}


class TestMerge:

  def _write_trace(self, path, role, pid, offset, spans):
    with open(path, "w") as f:
      f.write(json.dumps({"ph": "M", "role": role, "pid": pid,
                          "wall0": 0.0, "mono0": 0.0,
                          "clock_offset": offset}) + "\n")
      for name, ts, dur in spans:
        f.write(json.dumps({"ph": "X", "name": name, "ts": ts,
                            "dur": dur, "pid": pid, "tid": 1,
                            "role": role}) + "\n")

  def test_cross_process_merge_ordering_with_offsets(self, tmp_path):
    """Two processes with skewed clocks: the merge subtracts each
    file's handshake offset, so the timeline interleaves in HOST-clock
    order — the property that makes 'is the learner input-starved or
    the host slow' answerable from one screen."""
    # Host clock: events at host-times 1.0, 3.0. The actor's clock
    # runs 10s AHEAD (offset +10): its local stamps 12.0, 14.0 are
    # host-times 2.0, 4.0 — so the true order is h1, a1, h2, a2.
    self._write_trace(tmp_path / "trace_host.jsonl", "host", 100, 0.0,
                      [("h1", 1.0, 0.1), ("h2", 3.0, 0.1)])
    self._write_trace(tmp_path / "trace_actor-0.jsonl", "actor-0",
                      200, 10.0,
                      [("a1", 12.0, 0.1), ("a2", 14.0, 0.1)])
    trace = merge_lib.merge_traces(str(tmp_path))
    assert sorted(merge_lib.roles_in(trace)) == ["actor-0", "host"]
    timed = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in timed] == ["h1", "a1", "h2", "a2"]
    # ts are µs relative to the earliest corrected span and sorted.
    ts = [e["ts"] for e in timed]
    assert ts[0] == 0.0 and ts == sorted(ts)
    assert ts[1] == pytest.approx(1e6)
    # Roles render as process names for Perfetto.
    names = {e["pid"]: e["args"]["name"]
             for e in trace["traceEvents"] if e["name"] == "process_name"}
    assert names == {100: "host", 200: "actor-0"}

  def test_restart_keeps_per_incarnation_offsets(self, tmp_path):
    """Two meta lines in ONE file (a restarted role appending): each
    span uses the offset most recently stamped above it."""
    path = tmp_path / "trace_actor-0.jsonl"
    with open(path, "w") as f:
      f.write(json.dumps({"ph": "M", "role": "actor-0", "pid": 1,
                          "clock_offset": 5.0}) + "\n")
      f.write(json.dumps({"ph": "X", "name": "old", "ts": 10.0,
                          "dur": 0.1, "pid": 1, "tid": 1,
                          "role": "actor-0"}) + "\n")
      f.write(json.dumps({"ph": "M", "role": "actor-0", "pid": 2,
                          "clock_offset": 7.0}) + "\n")
      f.write(json.dumps({"ph": "X", "name": "new", "ts": 13.0,
                          "dur": 0.1, "pid": 2, "tid": 1,
                          "role": "actor-0"}) + "\n")
    trace = merge_lib.merge_traces(str(tmp_path))
    timed = {e["name"]: e["ts"]
             for e in trace["traceEvents"] if e["ph"] == "X"}
    # old: 10-5=5, new: 13-7=6 → old is t0, new lands 1s later.
    assert timed["old"] == 0.0
    assert timed["new"] == pytest.approx(1e6)

  def test_merge_cli_writes_summary_and_file(self, tmp_path):
    self._write_trace(tmp_path / "trace_learner.jsonl", "learner", 9,
                      0.0, [("step", 0.5, 0.2)])
    out = tmp_path / "merged.json"
    result = subprocess.run(
        [sys.executable, "-m", "tensor2robot_tpu.telemetry.merge",
         "--trace-dir", str(tmp_path), "--out", str(out)],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert result.returncode == 0, result.stderr
    summary = json.loads(result.stdout.strip())
    assert summary["roles"] == ["learner"]
    assert summary["span_count"] == 1
    merged = json.load(open(out))
    assert merged["metadata"]["span_count"] == 1


class TestJaxFreeImport:

  def test_telemetry_package_imports_without_jax(self):
    # The worker-safe property (IMP401): actors and data-plane workers
    # import the WHOLE telemetry package at spawn.
    code = (
        "import sys; "
        "import tensor2robot_tpu.telemetry; "
        "import tensor2robot_tpu.telemetry.core, "
        "tensor2robot_tpu.telemetry.metrics, "
        "tensor2robot_tpu.telemetry.records, "
        "tensor2robot_tpu.telemetry.flightrec, "
        "tensor2robot_tpu.telemetry.merge; "
        "assert 'jax' not in sys.modules, 'jax leaked'; "
        "print('JAXFREE')")
    result = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO)
    assert result.returncode == 0, result.stderr
    assert "JAXFREE" in result.stdout

  def test_telemetry_is_in_t2rcheck_scopes(self):
    from tensor2robot_tpu.analysis import cli
    from tensor2robot_tpu.analysis import import_rules

    assert "tensor2robot_tpu/telemetry" in cli._CONCURRENCY_PATHS
    assert "tensor2robot_tpu.telemetry" in \
        import_rules.WORKER_SAFE_MODULES


class TestFlightRecorder:

  def test_dump_and_read(self, tmp_path):
    tcore.configure("host")
    with tcore.span("last_op", key=1):
      pass
    tmetrics.counter("replay.adds").inc(5)
    path = flightrec.dump(str(tmp_path), "test latch",
                          extra={"who": "me"})
    assert path
    dumps = flightrec.read_dumps(str(tmp_path))
    assert len(dumps) == 1
    dump = dumps[0]
    assert dump["reason"] == "test latch"
    assert dump["role"] == "host"
    assert dump["extra"] == {"who": "me"}
    assert any(s["name"] == "last_op" for s in dump["spans"])
    assert dump["metrics"]["counters"]["replay.adds"] == 5.0

  @pytest.mark.slow
  def test_flight_record_on_latched_fleet_error(self, tmp_path):
    """The crash-policy harness (tests/test_fleet.py): an injected
    learner crash latches a FleetError — and now every reachable
    process leaves a flight record: the dying learner (its own except
    path), the still-live host (the orchestrator's flight_record RPC),
    and the orchestrator itself (heartbeat ages + restart counts)."""
    from tensor2robot_tpu.fleet import Fleet, FleetConfig, FleetError

    config = FleetConfig(
        num_actors=2, env="toy_grasp", image_size=16, action_dim=2,
        torso_filters=(8,), head_filters=(8,), dense_sizes=(16,),
        cem_population=8, cem_iterations=1, cem_elites=2,
        batch_size=16, max_train_steps=16, min_replay_size=32,
        publish_every_steps=8, log_every_steps=8,
        batch_episodes=8, serve_max_batch=4,
        replay_capacity=512, replay_shards=1,
        heartbeat_timeout_secs=0.0, launch_timeout_secs=240.0,
        # Short leash: the learner crashes at step 4, so the normal
        # path is ~20s — a wedged run must fail fast instead of
        # eating the tier-1 budget.
        run_timeout_secs=180.0, seed=0,
        learner_crash_after_steps=4)
    model_dir = str(tmp_path / "fleet")
    fleet = Fleet(config, model_dir)
    with pytest.raises(FleetError, match="learner died"):
      fleet.run()
    dumps = flightrec.read_dumps(
        flightrec.flightrec_dir(model_dir))
    by_role = {d["role"]: d for d in dumps}
    assert "learner" in by_role, f"roles: {sorted(by_role)}"
    assert "injected learner crash" in by_role["learner"]["reason"]
    # The learner's last spans survived (the train loop records one
    # per dispatch).
    assert any(s["name"] == "qtopt.dispatch"
               for s in by_role["learner"]["spans"])
    assert "orchestrator" in by_role
    orch = by_role["orchestrator"]
    assert "learner died" in orch["reason"]
    assert "t2r-fleet-learner" in orch["extra"]["heartbeat_ages_secs"]
    assert "host" in by_role
    assert by_role["host"]["metrics"]["counters"].get(
        "replay.adds", 0.0) > 0.0
    # The run's traces survived too — the post-mortem timeline merges.
    trace = merge_lib.merge_traces(
        os.path.join(model_dir, "telemetry"))
    assert "learner" in merge_lib.roles_in(trace)


@pytest.mark.slow
class TestEnvelopeFromTrainers:
  """Schema validation over records the REAL trainers produce (the
  tier-1 smoke configs): trainer + qtopt-learner loops both emit the
  unified envelope. (The anakin producer is covered at tier-1 by
  TestRecordEnvelope.test_metric_logger_emits_envelope — its logger is
  MetricLogger(role='anakin') — and at tier-2 by the full run here.)"""

  def _validate_file(self, path, expected_role):
    raw = [json.loads(line) for line in open(path)]
    assert raw
    for record in raw:
      assert trecords.validate_record(record) == [], record
      assert record["role"] == expected_role
      assert record["wall"] > 0

  def test_train_eval_and_qtopt_records_are_enveloped(self, tmp_path):
    from tensor2robot_tpu import train_eval
    from tensor2robot_tpu.data import RandomInputGenerator
    from tensor2robot_tpu.research.qtopt import (
        GraspingQModel,
        QTOptLearner,
    )
    from tensor2robot_tpu.research.qtopt.train_qtopt import train_qtopt
    from tensor2robot_tpu.utils.mocks import MockT2RModel

    supervised = str(tmp_path / "supervised")
    train_eval.train_eval_model(
        model=MockT2RModel(),
        model_dir=supervised,
        input_generator_train=RandomInputGenerator(batch_size=8),
        input_generator_eval=RandomInputGenerator(batch_size=8),
        max_train_steps=4, eval_steps=1, save_checkpoints_steps=4,
        log_every_steps=2)
    self._validate_file(
        os.path.join(supervised, "metrics_train.jsonl"), "trainer")
    self._validate_file(
        os.path.join(supervised, "metrics_eval.jsonl"), "trainer")

    qtopt_dir = str(tmp_path / "qtopt")
    learner = QTOptLearner(
        GraspingQModel(image_size=16, torso_filters=(8,),
                       head_filters=(8,), dense_sizes=(16,),
                       action_dim=2),
        cem_population=8, cem_iterations=1, cem_elites=2)
    train_qtopt(learner=learner, model_dir=qtopt_dir,
                prefill_random=True, max_train_steps=4, batch_size=8,
                log_every_steps=2, save_checkpoints_steps=4, seed=0)
    self._validate_file(
        os.path.join(qtopt_dir, "metrics_train.jsonl"), "trainer")
    # The compile-cache tap surfaced in the ordinary train log (the
    # CompileWatch gap, closed): the first interval records the
    # trace-time compile requests.
    records = trecords.read_records(
        os.path.join(qtopt_dir, "metrics_train.jsonl"))
    assert "compile_cache.requests" in records[-1]


class TestPrometheusAdapter:
  """The Prometheus text-format endpoint (ISSUE 12 satellite): a
  ~50-line adapter over `MetricsRegistry.snapshot()` — counters as
  `_total`, gauges verbatim, histograms as CUMULATIVE `le` buckets
  closed by `+Inf`, names sanitized to the exposition charset."""

  def _publish(self):
    tmetrics.counter("replay.add_rows").inc(7)
    tmetrics.gauge("serving.queue_depth").set(3.0)
    hist = tmetrics.histogram("serving.bucket_8_ms",
                              bounds=(1.0, 10.0))
    hist.observe(0.5)
    hist.observe(5.0)
    hist.observe(50.0)

  def test_render_scrape_format(self):
    from tensor2robot_tpu.telemetry import prometheus

    self._publish()
    body = prometheus.render_text()
    lines = body.splitlines()
    # Counters: sanitized (dots → underscores), `_total`-suffixed.
    assert "# TYPE t2r_replay_add_rows_total counter" in lines
    assert "t2r_replay_add_rows_total 7.0" in lines
    assert "# TYPE t2r_serving_queue_depth gauge" in lines
    assert "t2r_serving_queue_depth 3.0" in lines
    # Histogram: cumulative buckets, +Inf closes at total count.
    assert "# TYPE t2r_serving_bucket_8_ms histogram" in lines
    assert 't2r_serving_bucket_8_ms_bucket{le="1.0"} 1' in lines
    assert 't2r_serving_bucket_8_ms_bucket{le="10.0"} 2' in lines
    assert 't2r_serving_bucket_8_ms_bucket{le="+Inf"} 3' in lines
    assert "t2r_serving_bucket_8_ms_sum 55.5" in lines
    assert "t2r_serving_bucket_8_ms_count 3" in lines
    assert body.endswith("\n")

  def test_metric_names_sanitize_to_exposition_charset(self):
    import re

    from tensor2robot_tpu.telemetry import prometheus

    tmetrics.counter("fleet.actor-0.steps").inc()
    body = prometheus.render_text()
    name_re = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{|\s)")
    for line in body.splitlines():
      if line.startswith("#"):
        continue
      assert name_re.match(line), line
    assert "t2r_fleet_actor_0_steps_total 1.0" in body

  def _parse_exposition(self, body):
    """Minimal text-format (0.0.4) parser: returns
    ({family: type}, [(name, labels_dict, value)]). The unit tests run
    the rendered body through THIS instead of grepping lines, so label
    syntax and family grouping are checked structurally."""
    import re as _re

    line_re = _re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{([^}]*)\})?\s+(\S+)$")
    label_re = _re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|'
                           r'\\.)*)"')
    types = {}
    samples = []
    for line in body.splitlines():
      if not line:
        continue
      if line.startswith("# TYPE "):
        _, _, family, kind = line.split(" ")
        assert family not in types, f"duplicate TYPE for {family}"
        types[family] = kind
        continue
      if line.startswith("#"):
        continue
      match = line_re.match(line)
      assert match, f"unparseable sample line: {line!r}"
      name, raw_labels, value = match.groups()
      labels = dict(label_re.findall(raw_labels or ""))
      samples.append((name, labels, float(value)))
    return types, samples

  def test_tenant_prefixes_render_as_labels(self):
    """ISSUE 13 satellite: `serving.<tenant>.*` metrics become ONE
    family per metric with a `tenant=` label; reserved serving
    namespaces (arena/front/admission) stay label-free."""
    from tensor2robot_tpu.telemetry import prometheus

    tmetrics.counter("serving.robotA.dispatches").inc(4)
    tmetrics.counter("serving.robotB.dispatches").inc(9)
    tmetrics.counter("serving.robotA.admission.dropped").inc(2)
    tmetrics.counter("serving.arena.loads").inc(3)
    tmetrics.counter("serving.dispatches").inc(13)  # front-wide total
    hist_bounds = (1.0, 10.0)
    tmetrics.histogram("serving.robotA.bucket_8_ms",
                       bounds=hist_bounds).observe(0.5)
    tmetrics.histogram("serving.robotB.bucket_8_ms",
                       bounds=hist_bounds).observe(5.0)
    tmetrics.gauge("serving.robotA.queue_depth").set(2.0)

    body = prometheus.render_text()
    types, samples = self._parse_exposition(body)

    def sample(name, **labels):
      rows = [value for n, l, value in samples
              if n == name and l == labels]
      assert len(rows) == 1, (name, labels, rows)
      return rows[0]

    # One family, two tenant series + the unlabeled front-wide total.
    assert types["t2r_serving_dispatches_total"] == "counter"
    assert sample("t2r_serving_dispatches_total", tenant="robotA") == 4
    assert sample("t2r_serving_dispatches_total", tenant="robotB") == 9
    assert sample("t2r_serving_dispatches_total") == 13
    # Nested tenant namespaces keep their tail.
    assert sample("t2r_serving_admission_dropped_total",
                  tenant="robotA") == 2
    # Reserved namespace: a POOL metric, not a tenant called "arena".
    assert sample("t2r_serving_arena_loads_total") == 3
    assert not [l for n, l, _ in samples
                if n == "t2r_serving_arena_loads_total" and l]
    # Gauges carry the label too.
    assert sample("t2r_serving_queue_depth", tenant="robotA") == 2.0
    # Histograms: per-tenant bucket series under one family/TYPE.
    assert types["t2r_serving_bucket_8_ms"] == "histogram"
    assert sample("t2r_serving_bucket_8_ms_bucket",
                  tenant="robotA", le="1.0") == 1
    assert sample("t2r_serving_bucket_8_ms_bucket",
                  tenant="robotB", le="1.0") == 0
    assert sample("t2r_serving_bucket_8_ms_bucket",
                  tenant="robotB", le="+Inf") == 1
    assert sample("t2r_serving_bucket_8_ms_count",
                  tenant="robotA") == 1
    assert sample("t2r_serving_bucket_8_ms_sum",
                  tenant="robotB") == 5.0

  def test_two_segment_serving_names_stay_unlabeled(self):
    # `serving.bucket_8_ms` / `serving.microbatch_rows` (the
    # single-model engine's names) have no tenant segment and must
    # render exactly as before the label feature.
    from tensor2robot_tpu.telemetry import prometheus

    tmetrics.histogram("serving.bucket_8_ms",
                       bounds=(1.0, 10.0)).observe(0.5)
    tmetrics.gauge("serving.microbatch_queue_depth").set(1.0)
    body = prometheus.render_text()
    assert 't2r_serving_bucket_8_ms_bucket{le="1.0"} 1' in body
    assert "t2r_serving_microbatch_queue_depth 1.0" in body

  def test_http_endpoint_scrapes_live_registry(self):
    import urllib.request

    from tensor2robot_tpu.telemetry import prometheus

    self._publish()
    endpoint = prometheus.serve(port=0)
    try:
      url = f"http://127.0.0.1:{endpoint.port}/metrics"
      with urllib.request.urlopen(url, timeout=5) as resp:
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        body = resp.read().decode("utf-8")
      assert "t2r_replay_add_rows_total 7.0" in body
      # Scrape-time snapshot: a later publish shows on the NEXT pull.
      tmetrics.counter("replay.add_rows").inc(1)
      with urllib.request.urlopen(url, timeout=5) as resp:
        assert "t2r_replay_add_rows_total 8.0" in resp.read().decode(
            "utf-8")
      with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(
            f"http://127.0.0.1:{endpoint.port}/other", timeout=5)
    finally:
      endpoint.close()
