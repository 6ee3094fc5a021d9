"""Always-on performance plane tests (ISSUE 15): live MFU attribution,
resource watermarks, the alert sentinel, trace-flow correlation, and
the run report — pinned contracts:

  * live `perf.mfu` equals `telemetry.perf.mfu_value` over
    `utils.profiling.analytic_flops` for the same config within 1e-6
    relative, published by all three trainers incl. the pod modes
    (device-count aware); that model count stands near XLA's cost
    analysis of the unlevered step;
  * sentinel semantics: EWMA warmup never fires, a sustained breach
    fires exactly once (hysteresis) and re-arms on recovery, a
    page-severity breach in a REAL 2-actor fleet (slow_host stimulus
    through the ISSUE-14 fault seams) produces flight records;
  * the resource sampler publishes rsrc.* gauges with monotone peak
    watermarks and never raises out of a broken source;
  * fleet RPC spans correlate client↔server by `req` id as Perfetto
    flow events in the merged timeline;
  * the report CLI folds a run dir into one markdown page (smoke
    against a synthetic run; tier1.sh runs it against the committed
    artifacts/telemetry merged trace).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tensor2robot_tpu.telemetry import core as tcore
from tensor2robot_tpu.telemetry import merge as merge_lib
from tensor2robot_tpu.telemetry import metrics as tmetrics
from tensor2robot_tpu.telemetry import perf as perf_lib
from tensor2robot_tpu.telemetry import sentinel as sentinel_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAK = 1.0e12  # the test roofline (CPU has no table entry)


@pytest.fixture(autouse=True)
def _isolated_telemetry():
  tcore.reset_for_tests()
  tmetrics.reset_for_tests()
  perf_lib.stop_resource_sampler()
  perf_lib.set_plane_enabled(None)
  yield
  perf_lib.stop_resource_sampler()
  perf_lib.set_plane_enabled(None)
  tcore.reset_for_tests()
  tmetrics.reset_for_tests()


def _expected_mfu(record, flops, devices):
  return perf_lib.mfu_value(record["grad_steps_per_sec"], flops,
                            PEAK, devices=devices)


class TestSharedDenominator:
  """One MFU code path for every caller and the live gauges (the
  ISSUE-15 shared-path pin), over a model count that XLA bears out."""

  def test_analytic_flops_near_xla_cost_analysis(self):
    """A dropped or doubled term of the model count would skew every
    live `perf.mfu`: on the unlevered step (bf16 tower, lax select, no
    remat) it must stand near XLA's own count. The band is wide
    because a tiny model is elementwise-heavy (0.81 here)."""
    import jax
    import jax.numpy as jnp

    from tensor2robot_tpu.research.qtopt import (
        GraspingQModel,
        QTOptLearner,
    )
    from tensor2robot_tpu.specs import make_random_tensors
    from tensor2robot_tpu.utils import profiling

    learner = QTOptLearner(
        GraspingQModel(image_size=16, torso_filters=(8,),
                       head_filters=(8, 8), dense_sizes=(16,),
                       action_dim=2),
        cem_population=8, cem_iterations=1, cem_elites=2,
        cem_inference="bf16", cem_select="lax")
    state = learner.create_state(jax.random.PRNGKey(0))
    transitions = jax.tree_util.tree_map(jnp.asarray, make_random_tensors(
        learner.transition_specification(), batch_size=8, seed=0))
    xla = profiling.compiled_flops_per_call(
        jax.jit(learner.train_step).lower(
            state, transitions, jax.random.PRNGKey(2)).compile())
    analytic = profiling.analytic_flops(
        "qtopt_step", learner=learner, batch_size=8,
        params=state.train_state.params)
    assert xla, "XLA:CPU gave no cost analysis"
    assert 0.7 <= analytic / xla <= 1.3, (analytic, xla)

  def test_profiling_mfu_delegates_to_perf_formula(self, monkeypatch):
    from tensor2robot_tpu.utils import profiling
    monkeypatch.setenv("T2R_PEAK_FLOPS_OVERRIDE", str(PEAK))
    for rate, flops in ((12.5, 3.1e9), (700.0, 1.0e8)):
      assert profiling.mfu(rate, flops) == perf_lib.mfu_value(
          rate, flops, PEAK)

  def test_mfu_value_devices_and_unknowables(self):
    assert perf_lib.mfu_value(10.0, 1e9, 1e12) == pytest.approx(0.01)
    # Device-count aware: peak scales, MFU stays per-chip.
    assert perf_lib.mfu_value(10.0, 4e9, 1e12, devices=4) == (
        pytest.approx(0.01))
    assert perf_lib.mfu_value(10.0, None, 1e12) is None
    assert perf_lib.mfu_value(10.0, 1e9, None) is None


class TestPerfMeter:

  def test_publish_sets_gauges_and_dispatch_is_the_plain_span(self):
    tcore.configure("trainer")
    meter = perf_lib.PerfMeter(flops_per_step=100.0, peak_flops=1e3,
                               devices=2, enabled=True)
    with meter.dispatch("x.dispatch", step=3):
      pass
    (span,) = tcore.get_tracer().snapshot_spans()
    assert span["name"] == "x.dispatch" and span["args"] == {"step": 3}
    out = meter.publish(steps_per_sec=5.0)
    assert out["perf.flops_per_sec"] == pytest.approx(500.0)
    assert out["perf.mfu"] == pytest.approx(5.0 * 100.0 / (1e3 * 2))
    gauges = tmetrics.registry().snapshot()["gauges"]
    assert gauges["perf.mfu"] == pytest.approx(out["perf.mfu"])
    # The dispatch span times the enqueue: nothing is published under
    # a device's name from it (PERF.md, PR 24).
    assert "perf.device_time_fraction" not in out
    assert "perf.device_time_fraction" not in gauges

  def test_unknown_peak_publishes_no_mfu(self):
    meter = perf_lib.PerfMeter(flops_per_step=100.0, peak_flops=None,
                               enabled=True)
    out = meter.publish(5.0)
    assert set(out) == {"perf.flops_per_sec"}

  def test_disabled_plane_publishes_nothing(self):
    meter = perf_lib.PerfMeter(flops_per_step=100.0, peak_flops=1e3,
                               enabled=False)
    assert meter.publish(5.0) == {}
    assert tmetrics.registry().snapshot()["gauges"] == {}


class TestResourceSampler:

  def test_rss_and_peak_watermarks(self):
    sampler = perf_lib.ResourceSampler(watched_gauges=())
    sampler.sample_once()
    gauges = tmetrics.registry().snapshot()["gauges"]
    assert gauges["rsrc.host_rss_bytes"] > 0
    assert gauges["rsrc.host_rss_bytes_peak"] >= (
        gauges["rsrc.host_rss_bytes"] * 0.99)

  def test_watched_gauge_peak_is_monotone(self):
    fill = tmetrics.gauge("replay.fill")
    sampler = perf_lib.ResourceSampler(
        sources=[lambda: {}], watched_gauges=("replay.fill",))
    for value in (0.2, 0.9, 0.4):
      fill.set(value)
      sampler.sample_once()
    gauges = tmetrics.registry().snapshot()["gauges"]
    assert gauges["rsrc.replay.fill_peak"] == pytest.approx(0.9)

  def test_broken_source_is_skipped_not_raised(self):
    def broken():
      raise RuntimeError("boom")

    sampler = perf_lib.ResourceSampler(
        sources=[broken, lambda: {"ok": 1.0}], watched_gauges=())
    sampler.sample_once()  # must not raise
    assert tmetrics.registry().snapshot()["gauges"]["rsrc.ok"] == 1.0

  def test_process_singleton_respects_plane_switch(self):
    perf_lib.set_plane_enabled(False)
    assert perf_lib.start_resource_sampler() is None
    perf_lib.set_plane_enabled(True)
    sampler = perf_lib.start_resource_sampler()
    assert sampler is not None
    assert perf_lib.start_resource_sampler() is sampler  # idempotent
    perf_lib.stop_resource_sampler()


class TestSentinelSemantics:

  def test_ewma_warmup_never_fires(self):
    watch = sentinel_lib.Watch(name="w", metric="m", kind="ewma_drop",
                               threshold=0.2, warmup=5, sustain=1)
    sentinel = sentinel_lib.Sentinel([watch])
    # Five warmup evaluations on a COLLAPSING value: still no fire.
    for value in (1.0, 0.5, 0.1, 0.01, 0.001):
      assert sentinel.evaluate({"m": value}) == []

  def test_sustained_breach_fires_once_with_hysteresis(self):
    watch = sentinel_lib.Watch(name="w", metric="m", kind="ewma_drop",
                               threshold=0.2, warmup=2, sustain=2)
    sentinel = sentinel_lib.Sentinel([watch])
    fired = [len(sentinel.evaluate({"m": value}))
             for value in (1.0, 1.0,          # warmup
                           0.5, 0.5, 0.5, 0.5,  # breach sustained
                           1.0,                # recovery re-arms
                           0.5, 0.5)]          # second event train
    # One alert per sustained event train, at the sustain threshold.
    assert fired == [0, 0, 0, 1, 0, 0, 0, 0, 1]
    counters = tmetrics.registry().snapshot()["counters"]
    assert counters["alert.fired"] == 2.0
    assert counters["alert.w"] == 2.0

  def test_baseline_absorbs_only_healthy_values(self):
    watch = sentinel_lib.Watch(name="w", metric="m", kind="ewma_drop",
                               threshold=0.2, warmup=1, sustain=10 ** 6)
    sentinel = sentinel_lib.Sentinel([watch])
    sentinel.evaluate({"m": 1.0})
    for _ in range(50):  # a sustained breach never reaching sustain
      sentinel.evaluate({"m": 0.5})
    state = sentinel._states[("w", "m")]
    assert state.ewma == pytest.approx(1.0)  # not dragged down

  def test_increase_kind_counts_warm_increments(self):
    watch = sentinel_lib.Watch(name="recompile",
                               metric="compile_cache.misses",
                               kind="increase", warmup=1, sustain=1)
    sentinel = sentinel_lib.Sentinel([watch])
    fired = [len(sentinel.evaluate({"compile_cache.misses": value}))
             for value in (3.0, 3.0, 4.0, 4.0, 6.0)]
    # First evaluation is the cold-compile baseline; each later
    # increment is one warm-path recompile alert.
    assert fired == [0, 0, 1, 0, 1]

  def test_role_prefixed_metric_names_the_role(self, tmp_path):
    watch = sentinel_lib.Watch(name="timeouts",
                               metric="fleet.rpc.timeouts",
                               kind="above", threshold=0.0, warmup=0)
    alerts_path = str(tmp_path / "alerts.jsonl")
    sentinel = sentinel_lib.Sentinel([watch], alerts_path=alerts_path)
    fired = sentinel.evaluate({"actor-1/fleet.rpc.timeouts": 2.0})
    assert [a["role"] for a in fired] == ["actor-1"]
    sentinel.close()
    read = sentinel_lib.read_alerts(alerts_path)
    assert len(read) == 1 and read[0]["metric"] == (
        "actor-1/fleet.rpc.timeouts")

  def test_page_severity_invokes_hook_once(self):
    pages = []
    watch = sentinel_lib.Watch(name="p", metric="m", kind="above",
                               threshold=1.0, warmup=0,
                               severity="page")
    sentinel = sentinel_lib.Sentinel([watch], on_page=pages.append)
    for value in (2.0, 2.0, 2.0):
      sentinel.evaluate({"m": value})
    assert len(pages) == 1 and pages[0]["rule"] == "p"

  def test_watch_validation(self):
    with pytest.raises(ValueError):
      sentinel_lib.Watch(name="x", metric="m", kind="sideways")
    with pytest.raises(ValueError):
      sentinel_lib.Watch(name="x", metric="m", severity="shrug")


def _read_perf_record(model_dir):
  from tensor2robot_tpu.telemetry.records import read_records
  records = read_records(os.path.join(model_dir, "metrics_train.jsonl"))
  assert records
  record = records[-1]
  assert "perf.device_time_fraction" not in record
  assert "perf.flops_per_sec" in record
  return record


class TestTrainerLiveMfu:
  """The acceptance pin: live perf.mfu == `mfu_value` over the one
  analytic denominator (same config) within 1e-6 relative, all three
  trainers, pod modes device-count aware."""

  def test_train_qtopt_live_mfu_matches_bench_formula(
      self, tmp_path, monkeypatch):
    import jax

    from tensor2robot_tpu.research.qtopt import (
        GraspingQModel,
        QTOptLearner,
    )
    from tensor2robot_tpu.research.qtopt.train_qtopt import train_qtopt
    from tensor2robot_tpu.utils import profiling

    monkeypatch.setenv("T2R_PEAK_FLOPS_OVERRIDE", str(PEAK))
    learner = QTOptLearner(
        GraspingQModel(image_size=16, torso_filters=(8,),
                       head_filters=(8,), dense_sizes=(16,),
                       action_dim=2),
        cem_population=8, cem_iterations=1, cem_elites=2)
    batch = 16
    state = train_qtopt(
        learner=learner, model_dir=str(tmp_path), prefill_random=True,
        max_train_steps=32, batch_size=batch, log_every_steps=16,
        save_checkpoints_steps=32, seed=0)
    record = _read_perf_record(str(tmp_path))
    # The one formula over the one denominator — the same
    # analytic_flops call the trainer makes, devices = the mesh.
    flops = profiling.analytic_flops(
        "qtopt_step", learner=learner, batch_size=batch,
        params=state.train_state.params)
    expected = _expected_mfu(record, flops, jax.device_count())
    assert record["perf.mfu"] == pytest.approx(expected, rel=1e-6)
    assert record["perf.flops_per_sec"] == pytest.approx(
        record["grad_steps_per_sec"] * flops, rel=1e-6)

  # pmap at num_devices=0 = the FULL 8-virtual-device conftest mesh
  # (the acceptance criterion's pod mode); shard_map at 2 bounds the
  # compile bill while pinning the second pod substrate.
  @pytest.mark.parametrize("pod_program,num_devices",
                           [("pmap", 0), ("shard_map", 2)])
  def test_train_anakin_pod_live_mfu_device_count_aware(
      self, tmp_path, monkeypatch, pod_program, num_devices):
    import jax

    from tensor2robot_tpu.envs import train_anakin
    from tensor2robot_tpu.research.qtopt import (
        GraspingQModel,
        QTOptLearner,
    )
    from tensor2robot_tpu.utils import profiling

    monkeypatch.setenv("T2R_PEAK_FLOPS_OVERRIDE", str(PEAK))
    learner = QTOptLearner(
        GraspingQModel(image_size=16, torso_filters=(8,),
                       head_filters=(8,), dense_sizes=(16,),
                       action_dim=2),
        cem_population=8, cem_iterations=1, cem_elites=2)
    batch = 16
    d = num_devices or jax.local_device_count()
    kwargs = dict(env_family="pose", num_envs=16, rollout_length=2,
                  train_batches_per_iter=4, batch_size=batch,
                  replay_capacity=256, max_train_steps=16,
                  log_every_steps=8, save_checkpoints_steps=16,
                  seed=0, num_devices=num_devices,
                  pod_program=pod_program)
    if pod_program == "shard_map":
      kwargs["sharding_rules"] = "qtopt"
    state = train_anakin(learner=learner,
                         model_dir=str(tmp_path / pod_program),
                         **kwargs)
    record = _read_perf_record(str(tmp_path / pod_program))
    # Per-device analytic count × D over peak × D: MFU stays the
    # per-chip fraction at any pod size.
    flops = profiling.analytic_flops(
        "qtopt_step", learner=learner, batch_size=batch,
        params=state.train_state.params) * d
    expected = _expected_mfu(record, flops, d)
    assert record["perf.mfu"] == pytest.approx(expected, rel=1e-6)

  def test_train_eval_publishes_utilization(self, tmp_path,
                                            monkeypatch):
    import jax

    from tensor2robot_tpu.data import Mode, RandomInputGenerator
    from tensor2robot_tpu.train_eval import train_eval_model
    from tensor2robot_tpu.utils.mocks import MockT2RModel

    monkeypatch.setenv("T2R_PEAK_FLOPS_OVERRIDE", str(PEAK))
    train_eval_model(
        model=MockT2RModel(),
        model_dir=str(tmp_path),
        input_generator_train=RandomInputGenerator(batch_size=16),
        max_train_steps=20, log_every_steps=10,
        save_checkpoints_steps=20, eval_steps=0)
    record = _read_perf_record(str(tmp_path))
    if "perf.mfu" in record:
      # The generic trainer's denominator is XLA's count of the AOT
      # program; the FORMULA is still the one shared path —
      # mfu ≡ flops_per_sec / (peak × devices) by construction.
      assert record["perf.mfu"] == pytest.approx(
          record["perf.flops_per_sec"] / (PEAK * jax.device_count()),
          rel=1e-6)

  def test_quiet_tiny_run_fires_no_alerts(self, tmp_path):
    """Sentinel rides every trainer at log cadence; a healthy tiny
    run must write no alerts.jsonl."""
    from tensor2robot_tpu.research.qtopt import (
        GraspingQModel,
        QTOptLearner,
    )
    from tensor2robot_tpu.research.qtopt.train_qtopt import train_qtopt

    learner = QTOptLearner(
        GraspingQModel(image_size=16, torso_filters=(8,),
                       head_filters=(8,), dense_sizes=(16,),
                       action_dim=2),
        cem_population=8, cem_iterations=1, cem_elites=2)
    train_qtopt(learner=learner, model_dir=str(tmp_path),
                prefill_random=True, max_train_steps=32,
                batch_size=16, log_every_steps=8,
                save_checkpoints_steps=32, seed=0)
    assert sentinel_lib.read_alerts(
        str(tmp_path / "telemetry" / "alerts.jsonl")) == []


class TestRpcFlowCorrelation:

  def test_req_ids_link_client_and_server_spans(self, tmp_path):
    from tensor2robot_tpu.fleet.rpc import RpcClient, RpcServer

    tcore.configure("host", trace_dir=str(tmp_path))
    with RpcServer(lambda m, p, ctx: p, authkey=b"t") as server:
      with RpcClient(server.address, authkey=b"t") as client:
        for value in range(4):
          assert client.call("echo", value) == value
    tcore.get_tracer().close()
    trace = merge_lib.merge_traces(str(tmp_path))
    assert trace["metadata"]["rpc_flows"] == 4
    flows = [e for e in trace["traceEvents"]
             if e.get("cat") == "rpc_flow"]
    assert len(flows) == 8  # one s/f pair per call
    by_id = {}
    for event in flows:
      by_id.setdefault(event["id"], []).append(event["ph"])
    assert all(sorted(phs) == ["f", "s"] for phs in by_id.values())
    # The span args carry matching req ids on both sides.
    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    client_reqs = {e["args"]["req"] for e in spans
                   if e["name"] == "rpc_call.echo"}
    server_reqs = {e["args"]["req"] for e in spans
                   if e["name"] == "rpc.echo"}
    assert client_reqs == server_reqs and len(client_reqs) == 4

  def test_unpaired_req_emits_no_flow(self, tmp_path):
    tracer = tcore.Tracer().configure("solo", trace_dir=str(tmp_path))
    with tracer.span("rpc_call.lost", req="1-2-3"):
      pass
    tracer.close()
    trace = merge_lib.merge_traces(str(tmp_path))
    assert trace["metadata"]["rpc_flows"] == 0


class TestSentinelFleetE2E:
  """The page path against a REAL 2-actor fleet: one injected
  slow_host stall (ISSUE-14 fault seams) → the stalled client times
  out and recovers → the orchestrator's page-severity watch fires
  exactly one alert train → flight records land, role-named, exactly
  like the hang path's."""

  @pytest.mark.slow
  def test_slow_host_pages_with_flight_record(self, tmp_path):
    from tensor2robot_tpu import config as gin
    from tensor2robot_tpu.fleet import Fleet, FleetConfig
    from tensor2robot_tpu.fleet import faults as faults_lib
    from tensor2robot_tpu.telemetry import flightrec

    plan = faults_lib.FaultPlan(seed=3, events=(
        faults_lib.FaultEvent(
            fault=faults_lib.SLOW_HOST, target="host", at=4,
            duration_secs=3.0, method="sample"),))
    config = FleetConfig(
        num_actors=2, env="pose", image_size=16, action_dim=2,
        torso_filters=(8,), head_filters=(8,), dense_sizes=(16,),
        cem_population=8, cem_iterations=1, cem_elites=2,
        batch_size=16, max_train_steps=16, min_replay_size=32,
        publish_every_steps=8, log_every_steps=8, batch_episodes=8,
        serve_max_batch=4, replay_capacity=512, replay_shards=2,
        heartbeat_timeout_secs=0.0, launch_timeout_secs=240.0,
        run_timeout_secs=600.0, telemetry_poll_secs=0.5,
        rpc_call_timeout_secs=1.0, rpc_max_retries=2,
        fault_plan=plan, seed=0)
    gin.bind_parameter("fleet_watches.rpc_timeout_severity", "page")
    try:
      Fleet(config, str(tmp_path)).run()
    finally:
      gin.clear_config()
    alerts = sentinel_lib.read_alerts(
        str(tmp_path / "telemetry" / "alerts.jsonl"))
    timeout_alerts = [a for a in alerts
                      if a["rule"] == "rpc_timeouts"]
    assert len(timeout_alerts) == 1, alerts
    alert = timeout_alerts[0]
    assert alert["severity"] == "page"
    assert alert["role"] in ("learner", "actor-0", "actor-1")
    dumps = flightrec.read_dumps(flightrec.flightrec_dir(
        str(tmp_path)))
    page_dumps = [d for d in dumps
                  if "sentinel page" in str(d.get("reason", ""))]
    # The orchestrator's own view (heartbeat ages, restart counts —
    # the hang path's exact artifact shape) plus the host's ring.
    roles = {d["role"] for d in page_dumps}
    assert "orchestrator" in roles, dumps
    assert "host" in roles, dumps
    orch = next(d for d in page_dumps if d["role"] == "orchestrator")
    assert alert["role"] in orch["reason"]  # names the offender
    assert "heartbeat_ages_secs" in orch.get("extra", {})


class TestReportCli:

  def _synthetic_run(self, tmp_path):
    from tensor2robot_tpu.telemetry import records as trecords
    run = tmp_path / "run"
    run.mkdir()
    with open(run / "metrics_train.jsonl", "w") as f:
      for step in (10, 20, 30):
        record = trecords.make_record(step, {
            "grad_steps_per_sec": 100.0 + step,
            "perf.mfu": 0.2 + step / 1000.0,
            "rsrc.host_rss_bytes_peak": 1.0e9,
        }, role="trainer", wall=1000.0 + step)
        f.write(json.dumps(record) + "\n")
    with open(run / "alerts.jsonl", "w") as f:
      f.write(json.dumps({
          "rule": "mfu_drop", "metric": "perf.mfu",
          "role": "trainer", "value": 0.1, "baseline": 0.22,
          "threshold": 0.25, "kind": "ewma_drop",
          "severity": "warn", "wall": 1020.0}) + "\n")
    tracer = tcore.Tracer().configure("trainer",
                                      trace_dir=str(run))
    with tracer.span("qtopt.dispatch", step=1):
      pass
    tracer.close()
    return run

  def test_report_builds_and_renders_all_sections(self, tmp_path):
    from tensor2robot_tpu.telemetry import report as report_lib

    run = self._synthetic_run(tmp_path)
    report = report_lib.build_report(str(run))
    assert report["metrics"]["train"]["mfu"]["last"] == (
        pytest.approx(0.23))
    assert report["watermarks"]["rsrc.host_rss_bytes_peak"] == 1.0e9
    assert [a["rule"] for a in report["alerts"]] == ["mfu_drop"]
    assert report["span_summary"][0]["span"] == "qtopt.dispatch"
    markdown = report_lib.render_markdown(report)
    for heading in ("## Rates", "## MFU timeline (train)",
                    "## Resource watermarks", "## Alerts",
                    "## Span summary"):
      assert heading in markdown, heading
    assert "alert.mfu_drop" in markdown

  def test_report_cli_smoke(self, tmp_path):
    run = self._synthetic_run(tmp_path)
    out_md = tmp_path / "report.md"
    out_json = tmp_path / "report.json"
    result = subprocess.run(
        [sys.executable, "-m", "tensor2robot_tpu.telemetry.report",
         "--run-dir", str(run), "--out", str(out_md),
         "--json", str(out_json)],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert result.returncode == 0, result.stderr
    markdown = out_md.read_text()
    assert "# Run report" in markdown and "## Alerts" in markdown
    loaded = json.loads(out_json.read_text())
    assert loaded["alerts"] and loaded["metrics"]["train"]["records"] == 3

  def test_report_cli_empty_dir_exits_nonzero(self, tmp_path):
    empty = tmp_path / "empty"
    empty.mkdir()
    result = subprocess.run(
        [sys.executable, "-m", "tensor2robot_tpu.telemetry.report",
         "--run-dir", str(empty)],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert result.returncode == 1

  def test_report_reads_premerged_gz_trace(self, tmp_path):
    """The committed artifacts/telemetry layout: only a merged .gz
    timeline — the report must still render a span summary (the
    tier1.sh smoke's in-process twin)."""
    import gzip

    from tensor2robot_tpu.telemetry import report as report_lib

    run = tmp_path / "artifacts"
    run.mkdir()
    trace = {"traceEvents": [
        {"ph": "X", "name": "rpc.act", "cat": "host", "ts": 0.0,
         "dur": 1500.0, "pid": 1, "tid": 1}]}
    with gzip.open(run / "fleet_trace.json.gz", "wt") as f:
      json.dump(trace, f)
    report = report_lib.build_report(str(run))
    assert report["span_summary"] == [
        {"role": "host", "span": "rpc.act", "count": 1,
         "total_ms": 1.5, "mean_ms": 1.5}]
    assert report_lib.has_content(report)


class TestGoodputGauge:

  def test_front_publishes_per_tenant_goodput(self):
    """The serving front's completion loop feeds the goodput window;
    pin the gauge arithmetic through the internal seam (the full
    open-loop path has no measurement yet: ROADMAP W1)."""
    from tensor2robot_tpu.serving import front as front_lib

    entry = front_lib._Tenant("tenA", max_queue=4, seed=0,
                              takes_rng=False)
    front = front_lib.ServingFront.__new__(front_lib.ServingFront)
    front._tenants = {"tenA": entry}
    front._goodput_rows = 30.0
    front._goodput_t0 = -1.0  # window long since open
    entry.goodput_rows = 10.0
    entry.goodput_t0 = -1.0
    front._roll_goodput_windows(now=1.0)
    gauges = tmetrics.registry().snapshot()["gauges"]
    assert gauges["serving.tenA.goodput_rows_per_sec"] == (
        pytest.approx(5.0))
    assert gauges["perf.goodput_rows_per_sec"] == pytest.approx(15.0)
    # Idle windows keep rolling: a later zero-row close decays the
    # gauge to 0 instead of freezing the burst value (review finding).
    front._roll_goodput_windows(now=3.0)
    gauges = tmetrics.registry().snapshot()["gauges"]
    assert gauges["serving.tenA.goodput_rows_per_sec"] == 0.0
    assert gauges["perf.goodput_rows_per_sec"] == 0.0
