"""Fault-injection & recovery tests (ISSUE 14).

The chaos contract of docs/FLEET.md §"Failure & recovery", pinned:

  * the fault plan is DETERMINISTIC — same seed, same schedule, any
    host (digest-pinned), and it ships picklable inside `FleetConfig`;
  * the injector fires count-based triggers exactly once per
    incarnation (respawns replay a fault-free schedule; `recurring`
    events re-arm — the crash-loop fixture);
  * `RpcClient` calls carry a PER-CALL DEADLINE: a dead or half-dead
    host raises `TimeoutError`/`ConnectionError` instead of stranding
    the caller until the heartbeat timer (the pinned ISSUE-14 hang),
    and `call()` recovers through reconnect-and-retry with the outage
    stamped into `fleet.recovery_ms`;
  * the restart budget is RATE-based: a sliding window absorbs
    occasional churn forever and trips on a crash-loop;
  * elastic membership (`Fleet.scale_to`) grows and shrinks the actor
    fleet mid-run with zero partial episode rows;
  * a fleet under a seeded multi-class fault schedule RECOVERS —
    every injected class lands in `Fleet.recoveries`/the retry
    counters, and `committed % batch_episodes == 0` holds after every
    recovery (slow lane, with learner crash-resume restoring from the
    latest checkpoint).
"""

from __future__ import annotations

import os
import pickle
import threading
import time

import pytest

from tensor2robot_tpu.fleet import (
    Fleet,
    FleetConfig,
    FleetError,
    RpcClient,
    RpcError,
    RpcServer,
)
from tensor2robot_tpu.fleet import faults
from tensor2robot_tpu.fleet import rpc as rpc_lib
from tensor2robot_tpu.telemetry import metrics as tmetrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The seed-7 / 2-actor plan, frozen: regenerating it on ANY host must
# reproduce this digest bit-for-bit (the replay pin — a drifted
# generator would silently change every committed chaos run).
_SEED7_DIGEST = (
    "1a0cb555a8f2197709fba02331449752b8796fd59df907901bae45a3388a3d8d")


@pytest.fixture(autouse=True)
def _fresh_registry():
  tmetrics.reset_for_tests()
  rpc_lib.set_fault_injector(None)
  yield
  rpc_lib.set_fault_injector(None)
  tmetrics.reset_for_tests()


def _tiny_config(**overrides) -> FleetConfig:
  base = dict(
      num_actors=2, env="toy_grasp", image_size=16, action_dim=2,
      torso_filters=(8,), head_filters=(8,), dense_sizes=(16,),
      cem_population=8, cem_iterations=1, cem_elites=2,
      batch_size=16, max_train_steps=16, min_replay_size=32,
      publish_every_steps=8, log_every_steps=8,
      batch_episodes=8, serve_max_batch=4,
      replay_capacity=512, replay_shards=1,
      heartbeat_timeout_secs=0.0, launch_timeout_secs=240.0,
      run_timeout_secs=420.0, seed=0,
      rpc_call_timeout_secs=20.0, rpc_max_retries=2)
  base.update(overrides)
  return FleetConfig(**base)


class TestFaultPlan:

  def test_same_seed_same_plan_digest_pinned(self):
    plan_a = faults.FaultPlan.generate(seed=7, num_actors=2)
    plan_b = faults.FaultPlan.generate(seed=7, num_actors=2)
    assert plan_a.events == plan_b.events
    assert plan_a.digest() == plan_b.digest() == _SEED7_DIGEST
    # One event per class, each on a valid target.
    assert plan_a.classes() == tuple(sorted(faults.FAULT_CLASSES))
    assert faults.FaultPlan.generate(
        seed=8, num_actors=2).digest() != _SEED7_DIGEST

  def test_plan_ships_picklable_inside_fleet_config(self):
    plan = faults.FaultPlan.generate(seed=3, num_actors=2)
    config = _tiny_config(fault_plan=plan)
    clone = pickle.loads(pickle.dumps(config))
    assert clone.fault_plan.digest() == plan.digest()
    with pytest.raises(ValueError, match="fault_plan"):
      _tiny_config(fault_plan={"not": "a plan"})

  def test_unknown_class_rejected(self):
    with pytest.raises(ValueError, match="unknown fault class"):
      faults.FaultPlan.generate(seed=0, num_actors=1,
                                classes=("actor_crash", "bogus"))

  def test_for_target_filters(self):
    plan = faults.FaultPlan.generate(seed=7, num_actors=2)
    targets = {e.target for e in plan.events}
    for target in targets:
      events = plan.for_target(target)
      assert events and all(e.target == target for e in events)
    assert plan.for_target("actor-99") == ()


class TestFaultInjector:

  def _plan(self, *events):
    return faults.FaultPlan(seed=0, events=tuple(events))

  def test_on_batch_fires_once_and_respawn_is_fault_free(self):
    plan = self._plan(faults.FaultEvent(
        fault=faults.ACTOR_CRASH, target="actor-0", at=3, mode="hard"))
    injector = faults.FaultInjector(plan, "actor-0", incarnation=0)
    assert injector.active
    assert injector.on_batch(1) is None
    assert injector.on_batch(2) is None
    event = injector.on_batch(3)
    assert event is not None and event.fault == faults.ACTOR_CRASH
    assert injector.on_batch(4) is None  # fired, disarmed
    # The respawned incarnation replays a fault-free schedule.
    respawn = faults.FaultInjector(plan, "actor-0", incarnation=1)
    assert not respawn.active
    assert respawn.on_batch(3) is None
    # Other roles never see the event.
    other = faults.FaultInjector(plan, "actor-1", incarnation=0)
    assert not other.active

  def test_recurring_event_rearms_in_every_incarnation(self):
    plan = self._plan(faults.FaultEvent(
        fault=faults.ACTOR_CRASH, target="actor-0", at=1,
        mode="hard", recurring=True))
    for incarnation in (0, 1, 2):
      injector = faults.FaultInjector(plan, "actor-0",
                                      incarnation=incarnation)
      assert injector.on_batch(1) is not None, incarnation

  def test_rpc_action_counts_per_side_method_and_duration(self):
    plan = self._plan(
        faults.FaultEvent(fault=faults.RPC_DELAY, target="learner",
                          at=2, duration_secs=0.01, count=2),
        faults.FaultEvent(fault=faults.RPC_DROP, target="learner",
                          at=4, method="sample"))
    injector = faults.FaultInjector(plan, "learner")
    # Call 1: below every trigger. Calls 2-3: the delay (count=2).
    assert injector.rpc_action("client", "sample") is None
    assert injector.rpc_action("client", "sample") == ("delay", 0.01)
    assert injector.rpc_action("client", "sample") == ("delay", 0.01)
    # Call 4: the drop (method-filtered).
    assert injector.rpc_action("client", "sample") == ("drop", 0.0)
    assert injector.rpc_action("client", "sample") is None
    # A different method never matched the method-filtered drop, and
    # the server side never sees client-side classes.
    assert injector.rpc_action("client", "publish") is None
    fresh = faults.FaultInjector(plan, "learner")
    assert fresh.rpc_action("server", "sample") is None

  def test_injections_recorded_in_registry_and_log(self):
    plan = self._plan(faults.FaultEvent(
        fault=faults.LEARNER_CRASH, target="learner", at=1))
    injector = faults.FaultInjector(plan, "learner")
    assert injector.on_step(1) is not None
    snap = tmetrics.registry().snapshot()
    assert snap["counters"][
        "fleet.faults.injected.learner_crash"] == 1.0
    assert injector.injected[0]["fault"] == faults.LEARNER_CRASH


class TestRpcDeadlineRetry:
  """The ISSUE-14 satellite regression: `recv()` with no deadline
  stranded callers on a half-dead host until the 300s heartbeat
  timer. Every shape of that hang now raises within the deadline."""

  def test_unresponsive_handler_raises_timeout_not_strand(self):
    release = threading.Event()

    def handler(method, payload, ctx):
      if method == "stall":
        release.wait(timeout=30.0)
      return payload

    server = RpcServer(handler)
    try:
      client = RpcClient(server.address)
      t0 = time.monotonic()
      with pytest.raises(TimeoutError, match="no reply"):
        client.call_once("stall", timeout_secs=0.4)
      waited = time.monotonic() - t0
      assert waited < 5.0, f"caller stranded {waited:.1f}s"
      assert tmetrics.registry().snapshot()["counters"][
          "fleet.rpc.timeouts"] >= 1.0
      client.close()
    finally:
      release.set()
      server.close()

  def test_dead_server_raises_connection_error_mid_call(self):
    outcome = {}
    started = threading.Event()

    def handler(method, payload, ctx):
      started.set()
      time.sleep(30.0)
      return payload

    server = RpcServer(handler)
    client = RpcClient(server.address)

    def caller():
      try:
        client.call_once("x", timeout_secs=25.0)
      except (ConnectionError, TimeoutError) as e:
        outcome["error"] = e

    thread = threading.Thread(target=caller)
    thread.start()
    assert started.wait(timeout=10.0)
    server.close(timeout_secs=0.2)  # the host dies mid-call
    thread.join(timeout=10.0)
    assert not thread.is_alive(), "caller stranded by host death"
    assert "error" in outcome
    client.close()

  def test_retry_reconnects_and_stamps_recovery(self):
    calls = []
    release = threading.Event()

    def handler(method, payload, ctx):
      if method == "flaky":
        calls.append(1)
        if len(calls) == 1:
          release.wait(timeout=30.0)  # first call blows the deadline
      return payload

    server = RpcServer(handler)
    try:
      client = RpcClient(server.address, call_timeout_secs=0.3,
                         max_retries=2)
      assert client.call("flaky", 42) == 42
      assert client.reconnects == 1
      snap = tmetrics.registry().snapshot()["counters"]
      assert snap["fleet.rpc.retries"] >= 1.0
      assert snap["fleet.rpc.recovered"] >= 1.0
      hist = tmetrics.registry().snapshot()["histograms"][
          "fleet.recovery_ms"]
      assert hist["count"] >= 1
      client.close()
    finally:
      release.set()
      server.close()

  def test_injected_drop_recovers_through_real_machinery(self):
    # The no-mocks property: a planned rpc_drop loses the SEND, the
    # real deadline fires, the real reconnect-and-retry resends.
    plan = faults.FaultPlan(seed=0, events=(faults.FaultEvent(
        fault=faults.RPC_DROP, target="learner", at=1,
        method="ping"),))
    rpc_lib.set_fault_injector(
        faults.FaultInjector(plan, "learner"))
    server = RpcServer(lambda method, payload, ctx: payload)
    try:
      client = RpcClient(server.address, call_timeout_secs=0.3,
                         max_retries=2)
      assert client.call("ping", 5) == 5  # dropped once, recovered
      assert client.reconnects == 1
      snap = tmetrics.registry().snapshot()["counters"]
      assert snap["fleet.faults.injected.rpc_drop"] == 1.0
      assert snap["fleet.rpc.recovered"] >= 1.0
      client.close()
    finally:
      server.close()

  def test_injected_disconnect_runs_real_disconnect_path(self):
    # Server-side disconnect: the handler thread breaks out, the
    # synthetic __disconnect__ runs (the session-abort path), and the
    # client recovers on a fresh connection.
    disconnects = []

    def handler(method, payload, ctx):
      if method == rpc_lib.DISCONNECT_METHOD:
        disconnects.append(1)
        return None
      return payload

    plan = faults.FaultPlan(seed=0, events=(faults.FaultEvent(
        fault=faults.RPC_DISCONNECT, target="host", at=2),))
    rpc_lib.set_fault_injector(faults.FaultInjector(plan, "host"))
    server = RpcServer(handler)
    try:
      client = RpcClient(server.address, call_timeout_secs=5.0,
                         max_retries=2)
      assert client.call("ping", 1) == 1
      # Call 2 of "ping" (counts are per-method): the server drops the
      # connection BEFORE handling — the request is discarded, the
      # disconnect path runs, the client resends on a fresh socket.
      assert client.call("ping", 2) == 2
      assert client.reconnects == 1
      assert disconnects, "__disconnect__ never ran"
      client.close()
    finally:
      server.close()

  def test_server_side_handler_error_never_retries(self):
    attempts = []

    def handler(method, payload, ctx):
      attempts.append(method)
      raise ValueError("application error")

    server = RpcServer(handler)
    try:
      client = RpcClient(server.address, call_timeout_secs=5.0,
                         max_retries=3)
      with pytest.raises(RpcError, match="application error"):
        client.call("op")
      # The request ARRIVED; the transport must not re-send it.
      assert attempts == ["op"]
    finally:
      server.close()


class TestRateBudget:
  """The sliding-window restart budget, unit-level (no processes)."""

  def _fleet(self, tmp_path, **overrides):
    return Fleet(_tiny_config(**overrides), str(tmp_path / "m"))

  def test_window_absorbs_churn_and_trips_on_crash_loop(self, tmp_path):
    fleet = self._fleet(tmp_path, max_actor_restarts=2,
                        restart_window_secs=0.2)
    assert fleet._budget_ok("actor-0")
    fleet._charge_restart("actor-0")
    assert fleet._budget_ok("actor-0")
    fleet._charge_restart("actor-0")
    assert not fleet._budget_ok("actor-0")  # crash-loop: tripped
    time.sleep(0.25)
    # The window slid: occasional churn is absorbed forever.
    assert fleet._budget_ok("actor-0")
    # Budgets are per-target.
    assert fleet._budget_ok("actor-1")

  def test_window_zero_restores_lifetime_cap(self, tmp_path):
    fleet = self._fleet(tmp_path, max_actor_restarts=1,
                        restart_window_secs=0.0)
    fleet._charge_restart("actor-0")
    time.sleep(0.05)
    assert not fleet._budget_ok("actor-0")  # never expires

  def test_learner_budget_uses_its_own_cap(self, tmp_path):
    fleet = self._fleet(tmp_path, max_actor_restarts=5,
                        max_learner_restarts=1,
                        restart_window_secs=600.0)
    fleet._charge_restart("learner")
    assert not fleet._budget_ok("learner")
    assert fleet._budget_ok("actor-0")


def _committed(metrics):
  return int(metrics.get("service", {}).get(
      "replay_committed_transitions", -1))


class TestFleetFaultsE2E:
  """Real multi-process recoveries through the real seams."""

  @pytest.mark.slow
  def test_restart_budget_trips_on_crash_looping_actor(self, tmp_path):
    # A recurring crash re-fires in EVERY incarnation: the rate budget
    # must trip instead of respawning forever.
    plan = faults.FaultPlan(seed=0, events=(faults.FaultEvent(
        fault=faults.ACTOR_CRASH, target="actor-0", at=1,
        mode="hard", recurring=True),))
    config = _tiny_config(fault_plan=plan, max_actor_restarts=2,
                          restart_window_secs=600.0,
                          max_train_steps=64)
    fleet = Fleet(config, str(tmp_path / "m"))
    with pytest.raises(FleetError, match="budget"):
      fleet.run()
    assert fleet._restarts[0] == 2  # two respawns, then the trip

  @pytest.mark.slow
  def test_elastic_scale_up_down_lands_no_partial_rows(self, tmp_path):
    config = _tiny_config(max_train_steps=24)
    fleet = Fleet(config, str(tmp_path / "m"))
    fleet.launch()
    try:
      time.sleep(3.0)
      fleet.scale_to(3)
      assert sorted(fleet._actors) == [0, 1, 2]
      time.sleep(2.0)
      fleet.scale_to(1)
      assert sorted(fleet._actors) == [0]
      fleet.wait()
    finally:
      metrics = fleet.shutdown()
    assert metrics is not None
    committed = _committed(metrics)
    assert committed > 0
    # Scale-down drained actors mid-run; every landed episode batch is
    # whole (atomic commits + drain-after-batch).
    assert committed % config.batch_episodes == 0
    actions = [e["action"] for e in fleet.scale_events]
    assert actions == ["add", "remove", "remove"]
    assert fleet._restarts.get(0, 0) == 0  # drains never read as crashes

  @pytest.mark.slow
  def test_actor_crash_recovers_with_mttr_and_no_partial_rows(
      self, tmp_path):
    # One planned mid-episode crash: the disconnect abort discards the
    # staged half-episode, the restart policy respawns, MTTR lands in
    # `recoveries`, and the commit ledger stays whole.
    plan = faults.FaultPlan(seed=0, events=(faults.FaultEvent(
        fault=faults.ACTOR_CRASH, target="actor-0", at=2,
        mode="mid_episode"),))
    config = _tiny_config(fault_plan=plan, max_train_steps=16,
                          max_actor_restarts=3,
                          restart_window_secs=600.0)
    fleet = Fleet(config, str(tmp_path / "m"))
    result = fleet.run()
    assert result.actor_restarts == 1
    assert [r["fault"] for r in result.recoveries] == ["actor_crash"]
    assert result.recoveries[0]["target"] == "actor-0"
    assert result.recoveries[0]["mttr_ms"] > 0
    committed = _committed(result.metrics)
    assert committed > 0 and committed % config.batch_episodes == 0
    service = result.metrics["service"]
    assert service.get("replay_aborted_episodes", 0) >= 1

  @pytest.mark.slow
  def test_learner_crash_resume_restores_step_and_finishes(
      self, tmp_path):
    # The resume policy: the learner dies at step 10, the host keeps
    # the store + engine alive, the respawn restores from the step-8
    # checkpoint (publish cadence 8) and finishes the run — at most
    # one cadence of progress re-trained, zero experience lost.
    plan = faults.FaultPlan(seed=0, events=(faults.FaultEvent(
        fault=faults.LEARNER_CRASH, target="learner", at=10),))
    config = _tiny_config(fault_plan=plan,
                          learner_crash_policy="resume",
                          max_learner_restarts=2,
                          restart_window_secs=600.0,
                          max_train_steps=16)
    fleet = Fleet(config, str(tmp_path / "m"))
    result = fleet.run()
    assert result.learner_restarts == 1
    assert [r["fault"] for r in result.recoveries] == ["learner_crash"]
    assert result.recoveries[0]["mttr_ms"] > 0
    # The run FINISHED: the resumed learner reached the exact final
    # step and published its params (the host stamps them).
    window = result.metrics["learner_window"]
    assert window["last_step"] == config.max_train_steps
    assert result.metrics["params_learner_step"] == (
        config.max_train_steps)
    # The host WITNESSED the restore (a backward set_learner_step):
    # the measured restore point is the last checkpoint before the
    # crash, so the measured loss is bounded by the publish cadence —
    # the host's own record of the restore.
    (resume,) = result.metrics["learner_resumes"]
    assert resume["to_step"] <= resume["from_step"] <= 10
    assert resume["from_step"] - resume["to_step"] <= (
        config.publish_every_steps)
    assert resume["to_step"] >= 10 - config.publish_every_steps
    committed = _committed(result.metrics)
    assert committed > 0 and committed % config.batch_episodes == 0

  @pytest.mark.slow
  def test_multi_class_chaos_plan_recovers_every_class(self, tmp_path):
    # A whole fault schedule in miniature: hang + crash + client/
    # server RPC faults in ONE run, every class recovering through its
    # real path.
    plan = faults.FaultPlan(seed=0, events=(
        faults.FaultEvent(fault=faults.ACTOR_CRASH, target="actor-0",
                          at=2, mode="hard"),
        faults.FaultEvent(fault=faults.ACTOR_HANG, target="actor-1",
                          at=2, mode="hard", duration_secs=45.0),
        faults.FaultEvent(fault=faults.RPC_DROP, target="actor-1",
                          at=3, method="act"),
        faults.FaultEvent(fault=faults.RPC_DELAY, target="learner",
                          at=4, duration_secs=0.05, count=3),
        faults.FaultEvent(fault=faults.SLOW_HOST, target="host",
                          at=6, method="act", duration_secs=0.2,
                          count=4),
        faults.FaultEvent(fault=faults.RPC_DISCONNECT, target="host",
                          at=10, method="commit"),
    ))
    # The hang (45s) must outlive its detection window (5s) by far,
    # and the RUN must outlive the detection: 48 learner steps keeps
    # the learner busy well past the stale-heartbeat kill + respawn.
    config = _tiny_config(
        fault_plan=plan, max_train_steps=48,
        max_actor_restarts=3, restart_window_secs=600.0,
        actor_heartbeat_timeout_secs=5.0,
        rpc_call_timeout_secs=3.0, rpc_max_retries=3,
        telemetry_dir="off")
    fleet = Fleet(config, str(tmp_path / "m"))
    result = fleet.run()
    recovered = {r["fault"] for r in result.recoveries}
    assert recovered == {faults.ACTOR_CRASH, faults.ACTOR_HANG}
    assert all(r["mttr_ms"] > 0 for r in result.recoveries)
    assert result.actor_restarts == 2
    # MTTR is detection → recovered; the stale window the hang sat
    # undetected is reported separately and must cover the timeout.
    hang = next(r for r in result.recoveries
                if r["fault"] == faults.ACTOR_HANG)
    assert hang["stale_secs"] >= config.actor_heartbeat_timeout_secs
    committed = _committed(result.metrics)
    assert committed > 0 and committed % config.batch_episodes == 0
    window = result.metrics["learner_window"]
    assert window["last_step"] == config.max_train_steps
