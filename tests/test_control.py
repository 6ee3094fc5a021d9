"""Closed-loop control plane tests (ISSUE 18, docs/CONTROL.md).

Pins the policy plane's contracts:

  * the rule grammar — window means, hysteresis re-arm bands,
    sustained-breach streaks, EWMA baselines that absorb only healthy
    values, per-second rate kinds, per-role `aggregate="each"`;
  * the controller — per-rule cooldowns, the GLOBAL rate-based
    actuation budget, deterministic rule-order precedence under that
    budget, dry-run (charges cooldown + budget, never touches an
    actuator, never silences a page), decision records that validate
    under the telemetry envelope schema;
  * escalation tiers — the sentinel's act tier routes through
    `Controller.handle_alert`, a successful remediation DEMOTES a
    page, and flight records stay the terminal tier;
  * the package is jax-free (subprocess pin) and inside the t2rcheck
    CON3xx / IMP401 scopes;
  * (slow) the e2e remediation smoke: a killed front replica is
    detected, respawned at its index under the front restart budget,
    and rejoins a live `ServingRouter` via the observer seam with no
    manual step.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

import pytest

from tensor2robot_tpu.control import actuators as actuators_lib
from tensor2robot_tpu.control import controller as controller_lib
from tensor2robot_tpu.control import policies as policies_lib
from tensor2robot_tpu.control import rules as rules_lib
from tensor2robot_tpu.control.actuators import (
    ActuationError,
    Actuator,
    DegradationLadder,
    fleet_actuators,
)
from tensor2robot_tpu.control.controller import (
    Controller,
    OUTCOMES,
    read_decisions,
)
from tensor2robot_tpu.control.rules import ControlRule, RuleState
from tensor2robot_tpu.telemetry import metrics as tmetrics
from tensor2robot_tpu.telemetry import records as trecords
from tensor2robot_tpu.telemetry import sentinel as sentinel_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rule(**kw):
  base = dict(name="r", metric="m", action="act", kind="above",
              threshold=10.0)
  base.update(kw)
  return ControlRule(**base)


def _evaluate_series(rule, values, t0=1000.0, dt=1.0):
  """Feeds `values` one second apart; returns the trigger bitmap."""
  state = RuleState(rule.window)
  out = []
  for i, value in enumerate(values):
    result = rules_lib.evaluate(rule, state, value, now=t0 + i * dt)
    out.append(result["triggered"])
  return out


class _Lever:
  """One recording actuator; optionally always-raises."""

  def __init__(self, fail=False):
    self.calls = []
    self._fail = fail

  def __call__(self, params, decision):
    if self._fail:
      raise ActuationError("broken lever")
    self.calls.append((dict(params), decision["rule"]))
    return {"ok": True}


def _controller(rules, lever=None, **kw):
  lever = lever if lever is not None else _Lever()
  kw.setdefault("registry", tmetrics.MetricsRegistry())
  ctrl = Controller(
      rules, {"act": Actuator("act", lever)}, **kw)
  return ctrl, lever


class TestRuleGrammar:

  def test_window_mean_and_sustain(self):
    rule = _rule(window=2, sustain=2)
    # Window means: [20]=20, [20,0]=10 (not >10), [0,30]=15, [30,30]=30
    # — the sustain streak only completes on the 4th observation.
    assert _evaluate_series(rule, [20.0, 0.0, 30.0, 30.0]) == [
        False, False, False, True]

  def test_hysteresis_rearm_band(self):
    rule = _rule(threshold=10.0, clear=5.0, cooldown_secs=0.0)
    # Fires at 12; stays DISARMED through 12 and 7 (inside the band);
    # re-arms only at 4 (<= clear); fires again at 12.
    assert _evaluate_series(rule, [12.0, 12.0, 7.0, 4.0, 12.0]) == [
        True, False, False, False, True]

  def test_clear_must_sit_on_healthy_side(self):
    with pytest.raises(ValueError):
      _rule(kind="above", threshold=10.0, clear=11.0)
    with pytest.raises(ValueError):
      _rule(kind="below", threshold=10.0, clear=9.0)

  def test_ewma_drop_baseline_ignores_breaches(self):
    rule = _rule(kind="ewma_drop", threshold=0.5, warmup=2, alpha=0.5,
                 cooldown_secs=0.0, clear=None)
    state = RuleState(rule.window)
    for i, value in enumerate([1.0, 1.0]):  # warmup: never fires
      result = rules_lib.evaluate(rule, state, value, now=1000.0 + i)
      assert not result["triggered"]
    # A 70% drop against the ~1.0 baseline fires...
    result = rules_lib.evaluate(rule, state, 0.3, now=1002.0)
    assert result["triggered"] and result["baseline"] == pytest.approx(
        1.0)
    # ...and the breach value did NOT drag the baseline down (only
    # healthy observations feed the EWMA).
    assert state.ewma == pytest.approx(1.0)

  def test_rate_above_per_second(self):
    rule = _rule(kind="rate_above", threshold=5.0, warmup=1,
                 cooldown_secs=0.0)
    state = RuleState(rule.window)
    # First observation only establishes the counter baseline.
    assert not rules_lib.evaluate(rule, state, 100.0,
                                  now=1000.0)["triggered"]
    # +20 over 2s = 10/s > 5/s; the computed rate rides in the
    # result's baseline (value stays the raw counter reading).
    result = rules_lib.evaluate(rule, state, 120.0, now=1002.0)
    assert result["triggered"]
    assert result["baseline"] == pytest.approx(10.0)

  def test_each_aggregate_resolves_roles(self):
    scalars = {"front0/perf.mfu": 0.4, "front1/perf.mfu": 0.1,
               "learner/perf.mfu": 0.5, "perf.mfux": 9.9}
    targets = rules_lib.resolve_metric("perf.mfu", "each", scalars)
    assert targets == [("front0/perf.mfu", 0.4),
                       ("front1/perf.mfu", 0.1),
                       ("learner/perf.mfu", 0.5)]
    # Folding aggregates collapse to the bare metric name.
    assert rules_lib.resolve_metric("perf.mfu", "max", scalars) == [
        ("perf.mfu", 0.5)]

  def test_bad_kind_and_aggregate_rejected(self):
    with pytest.raises(ValueError):
      _rule(kind="sideways")
    with pytest.raises(ValueError):
      _rule(aggregate="median")


class TestController:

  def test_cooldown_pin(self):
    ctrl, lever = _controller(
        [_rule(cooldown_secs=60.0)], max_actions=10)
    ctrl.step({"m": 20.0}, now=1000.0)
    ctrl.step({"m": 20.0}, now=1001.0)  # hysteresis: still disarmed
    outcomes = [d["outcome"] for d in ctrl.decisions]
    assert outcomes == ["actuated"]
    # Re-arm (no clear → re-arms on any non-breach), breach again
    # INSIDE the cooldown: triggered but skipped, and the skip is
    # recorded with the remaining cooldown.
    ctrl.step({"m": 1.0}, now=1002.0)
    ctrl.step({"m": 20.0}, now=1003.0)
    assert [d["outcome"] for d in ctrl.decisions] == [
        "actuated", "cooldown"]
    assert ctrl.decisions[-1]["cooldown_remaining_secs"] > 0
    assert len(lever.calls) == 1
    # Past the cooldown the same breach actuates again.
    ctrl.step({"m": 1.0}, now=1070.0)
    ctrl.step({"m": 20.0}, now=1071.0)
    assert len(lever.calls) == 2

  def test_global_budget_and_rule_order_determinism(self):
    # Two rules breach in the same pass with ONE action of budget:
    # table order decides, deterministically, who gets it.
    rules = [_rule(name="first", cooldown_secs=0.0),
             _rule(name="second", cooldown_secs=0.0)]
    for _ in range(3):  # determinism: same outcome every time
      ctrl, lever = _controller(
          [r for r in rules], max_actions=1, budget_window_secs=0.0)
      ctrl.step({"m": 20.0}, now=1000.0)
      by_rule = {d["rule"]: d["outcome"] for d in ctrl.decisions}
      assert by_rule == {"first": "actuated", "second": "budget"}
      assert [r for _, r in lever.calls] == ["first"]
      assert ctrl.budget_remaining(1000.0) == 0

  def test_budget_window_slides(self):
    ctrl, lever = _controller(
        [_rule(cooldown_secs=0.0)], max_actions=1,
        budget_window_secs=30.0)
    ctrl.step({"m": 20.0}, now=1000.0)
    ctrl.step({"m": 1.0}, now=1001.0)
    ctrl.step({"m": 20.0}, now=1002.0)  # budget spent
    assert [d["outcome"] for d in ctrl.decisions] == [
        "actuated", "budget"]
    ctrl.step({"m": 1.0}, now=1030.0)
    ctrl.step({"m": 20.0}, now=1040.0)  # window slid: budget back
    assert [d["outcome"] for d in ctrl.decisions][-1] == "actuated"
    assert len(lever.calls) == 2

  def test_dry_run_never_actuates_but_charges(self):
    lever = _Lever(fail=True)  # would raise if ever applied
    ctrl, _ = _controller(
        [_rule(name="a", cooldown_secs=0.0),
         _rule(name="b", cooldown_secs=0.0)],
        lever=lever, dry_run=True, max_actions=1,
        budget_window_secs=0.0)
    ctrl.step({"m": 20.0}, now=1000.0)
    by_rule = {d["rule"]: d["outcome"] for d in ctrl.decisions}
    # Dry-run charges the budget exactly like live mode — the
    # would-act log IS the live actuation schedule.
    assert by_rule == {"a": "would_act", "b": "budget"}
    assert ctrl.stats()["actuated"] == 0

  def test_actuator_error_is_contained(self):
    ctrl, _ = _controller([_rule()], lever=_Lever(fail=True))
    ctrl.step({"m": 20.0}, now=1000.0)
    decision = ctrl.decisions[-1]
    assert decision["outcome"] == "error"
    assert "broken lever" in decision["error"]
    assert ctrl.stats()["error"] == 1

  def test_unknown_action_rejected_at_construction(self):
    with pytest.raises(ValueError, match="unknown actuator"):
      Controller([_rule(action="warp_core")],
                 {"act": Actuator("act", _Lever())},
                 registry=tmetrics.MetricsRegistry())
    with pytest.raises(ValueError, match="duplicate"):
      Controller([_rule(), _rule()],
                 {"act": Actuator("act", _Lever())},
                 registry=tmetrics.MetricsRegistry())

  def test_decision_records_validate(self, tmp_path):
    path = str(tmp_path / "control_decisions.jsonl")
    ctrl, _ = _controller(
        [_rule(cooldown_secs=60.0, aggregate="each")], max_actions=10,
        decisions_path=path)
    ctrl.step({"front0/m": 20.0, "front1/m": 1.0}, step=7,
              now=1000.0)
    ctrl.step({"front0/m": 1.0}, now=1001.0)
    ctrl.step({"front0/m": 20.0}, now=1002.0)  # cooldown skip
    ctrl.close()
    records = read_decisions(path)
    assert len(records) == 2
    for record in records:
      trecords.validate_record(record)  # envelope schema holds
    first = records[0]
    assert first["step"] == 7
    assert first["role"] == "front0"  # per-role targeting recorded
    assert first["payload"]["control.r.outcome"] == float(
        OUTCOMES.index("actuated"))
    assert first["payload"]["control.r.actuated"] == 1.0
    assert records[1]["payload"]["control.r.outcome"] == float(
        OUTCOMES.index("cooldown"))

  def test_handle_alert_remediation_and_fallthrough(self):
    ctrl, lever = _controller(
        [_rule(alert="mfu_drop", cooldown_secs=0.0)], max_actions=10)
    alert = {"rule": "mfu_drop", "metric": "front0/perf.mfu",
             "value": 0.1, "role": "front0"}
    assert ctrl.handle_alert(alert) is True
    assert lever.calls and lever.calls[-1][1] == "r"
    # An alert no rule is bound to falls through (the page proceeds;
    # `alert_unhandled` only counts BOUND alerts whose remediation
    # did not actuate, so it stays zero here).
    assert ctrl.handle_alert({"rule": "who", "value": 0.0}) is False
    assert ctrl.stats()["alert_handled"] == 1
    assert ctrl.stats()["alert_unhandled"] == 0

  def test_dry_run_alert_never_silences_pages(self):
    ctrl, _ = _controller(
        [_rule(alert="mfu_drop", cooldown_secs=0.0)], dry_run=True)
    assert ctrl.handle_alert(
        {"rule": "mfu_drop", "value": 0.1}) is False


class TestEscalationTiers:
  """Sentinel severities map to tiers: log → act → page, with the
  controller's act hook demoting remediated pages (ISSUE 18)."""

  def _watch(self, severity):
    return sentinel_lib.Watch(name="w", metric="m", kind="above",
                              threshold=10.0, warmup=0,
                              severity=severity)

  def test_act_severity_routes_through_hook_and_never_pages(self):
    acted, paged = [], []
    sentinel = sentinel_lib.Sentinel(
        [self._watch("act")], on_act=lambda a: acted.append(a) or True,
        on_page=lambda a: paged.append(a),
        registry=tmetrics.MetricsRegistry())
    [record] = sentinel.evaluate({"m": 20.0})
    assert record["escalation"] == "act" and record["handled"]
    assert acted and not paged

  def test_remediated_page_demotes(self):
    paged = []
    registry = tmetrics.MetricsRegistry()
    sentinel = sentinel_lib.Sentinel(
        [self._watch("page")], on_act=lambda a: True,
        on_page=lambda a: paged.append(a), registry=registry)
    [record] = sentinel.evaluate({"m": 20.0})
    assert record["escalation"] == "act"  # demoted: no flight record
    assert not paged
    assert registry.scalars()["alert.remediated"] == 1.0

  def test_unremediated_page_escalates(self):
    paged = []
    registry = tmetrics.MetricsRegistry()
    sentinel = sentinel_lib.Sentinel(
        [self._watch("page")], on_act=lambda a: False,
        on_page=lambda a: paged.append(a), registry=registry)
    [record] = sentinel.evaluate({"m": 20.0})
    assert record["escalation"] == "page" and not record["handled"]
    assert paged
    assert registry.scalars()["alert.paged"] == 1.0

  def test_page_without_hooks_still_pages(self):
    registry = tmetrics.MetricsRegistry()
    sentinel = sentinel_lib.Sentinel([self._watch("page")],
                                     registry=registry)
    [record] = sentinel.evaluate({"m": 20.0})
    assert record["escalation"] == "page"
    assert registry.scalars()["alert.paged"] == 1.0


class TestDegradationLadder:

  def test_shed_order_exhaustion_and_restore(self):
    retunes = []
    ladder = DegradationLadder(
        ("bulk", "batch"),
        retune=lambda t, rate_rps=None: retunes.append((t, rate_rps)),
        shed_rate_rps=2.0)
    assert ladder.shed_next() == "bulk"
    assert ladder.shed_next() == "batch"
    assert ladder.shed_next() is None  # exhausted → next rule pages
    assert retunes == [("bulk", 2.0), ("batch", 2.0)]
    assert ladder.restore() == ("bulk", "batch")
    assert retunes[-2:] == [("bulk", None), ("batch", None)]


class TestStandardPolicyTable:

  def test_fleet_rules_resolve_against_fleet_actuators(self):
    class _FakeFleet:
      num_actors, num_fronts = 2, 1
      def scale_to(self, n): pass
      def scale_fronts_to(self, n): pass
      def kick(self, role): pass
      def retune_admission(self, tenant, **kw): return {}
    rules = policies_lib.fleet_rules(env_steps_per_sec_min=10.0,
                                     env_steps_per_sec_max=100.0)
    # Construction validates: unique names, every action resolves.
    ctrl = Controller(
        rules, fleet_actuators(_FakeFleet()),
        registry=tmetrics.MetricsRegistry())
    assert [r.name for r in ctrl.rules][0] == "slow_host_respawn"
    # The slow-host rule is the sentinel's mfu_drop remediation and
    # evaluates per role (it must name WHO to kick).
    slow = ctrl.rules[0]
    assert slow.alert == "mfu_drop" and slow.aggregate == "each"
    # Degradation precedes restore; page never appears (paging is the
    # sentinel's fallback, not a standing rule).
    names = [r.name for r in ctrl.rules]
    assert names.index("overload_shed") < names.index(
        "recovered_restore")
    assert all(r.action != "page" for r in ctrl.rules)

  def test_offered_load_prescale_rule(self):
    # Predictive pre-scale (ISSUE 19): default OFF; when a slope bound
    # is set, a rate_above rule on the admitted-rows counter scales
    # the front tier BEFORE the reactive p95 rule can breach — so it
    # must sit ahead of front_p95_scale_up in actuation priority.
    base = [r.name for r in policies_lib.fleet_rules()]
    assert "front_offered_prescale" not in base
    rules = policies_lib.fleet_rules(offered_load_slope_max=200.0,
                                     tenant="policy", max_fronts=3)
    names = [r.name for r in rules]
    assert names.index("front_offered_prescale") < names.index(
        "front_p95_scale_up")
    rule = next(r for r in rules
                if r.name == "front_offered_prescale")
    assert rule.kind == "rate_above"
    assert rule.metric == "serving.policy.admission.admitted"
    assert rule.threshold == 200.0
    assert rule.action == "scale_fronts"
    assert rule.action_params == {"delta": 1, "min": 1, "max": 3}
    # Worst replica's offered load, not the average: one hot front
    # must be enough to pre-scale.
    assert rule.aggregate == "max"

  def test_p95_breach_scales_the_front_tier_within_its_bounds(self):
    """The closed loop's ramp: the shipped table over the shipped
    actuators on a fleet surface. A sustained p95 breach on the worst
    replica adds one front; past the table's `max_fronts` the rule
    still triggers and the actuator holds the bound; nothing pages,
    since the breach has a remediation."""
    class _Tier:
      num_actors, num_fronts = 1, 1
      def __init__(self): self.calls = []
      def scale_to(self, n): raise AssertionError("actors untouched")
      def kick(self, role): raise AssertionError("nobody kicked")
      def retune_admission(self, tenant, **kw): return {}
      def scale_fronts_to(self, n):
        self.calls.append(n)
        self.num_fronts = n
    tier, pages = _Tier(), []
    ctrl = Controller(
        policies_lib.fleet_rules(tenant="policy", slo_ms=100.0,
                                 max_fronts=2, cooldown_secs=0.0),
        fleet_actuators(tier, on_page=pages.append),
        registry=tmetrics.MetricsRegistry())
    p95 = "serving.policy.request_ms_p95"
    breach = {f"front0/{p95}": 40.0, f"front1/{p95}": 250.0}
    ctrl.step(breach, now=1000.0)   # sustain=2: one reading is noise
    assert tier.calls == []
    ctrl.step(breach, now=1001.0)
    assert tier.calls == [2]
    scale_up = [d for d in ctrl.decisions
                if d["rule"] == "front_p95_scale_up"]
    assert [d["outcome"] for d in scale_up] == ["actuated"]
    assert scale_up[0]["detail"] == {"fronts_before": 1,
                                     "fronts_after": 2}
    # Re-arm under the clear band, breach again: at the bound.
    ctrl.step({f"front0/{p95}": 10.0}, now=1002.0)
    ctrl.step({f"front0/{p95}": 10.0}, now=1003.0)
    ctrl.step(breach, now=1004.0)
    ctrl.step(breach, now=1005.0)
    assert tier.calls == [2] and tier.num_fronts == 2
    assert ctrl.decisions[-1]["detail"] == {"noop": "at_bound",
                                            "fronts": 2}
    assert pages == [] and ctrl.stats()["alert_unhandled"] == 0

  def test_respawn_role_requires_concrete_role(self):
    acts = fleet_actuators(object())
    with pytest.raises(ActuationError):
      acts["respawn_role"].apply({}, {"role": "fleet"})


class TestPackageScope:

  def test_control_package_is_jax_free(self):
    code = (
        "import sys; "
        "import tensor2robot_tpu.control; "
        "import tensor2robot_tpu.control.rules, "
        "tensor2robot_tpu.control.controller, "
        "tensor2robot_tpu.control.actuators, "
        "tensor2robot_tpu.control.policies; "
        "assert 'jax' not in sys.modules, 'jax leaked'; "
        "print('JAXFREE')")
    result = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO)
    assert result.returncode == 0, result.stderr
    assert "JAXFREE" in result.stdout

  def test_control_is_in_t2rcheck_scopes(self):
    from tensor2robot_tpu.analysis import cli
    from tensor2robot_tpu.analysis import import_rules

    assert "tensor2robot_tpu/control" in cli._CONCURRENCY_PATHS
    assert "tensor2robot_tpu.control" in \
        import_rules.WORKER_SAFE_MODULES


@pytest.mark.slow
class TestFleetRemediationEndToEnd:
  """The seeded e2e smoke: kill a front replica under a live fleet —
  supervision detects it, respawns it at its index under the front
  restart budget, and the observer seam rejoins it to a real
  `ServingRouter` via `mark_alive` with NO manual step."""

  def test_killed_front_respawns_and_rejoins_router(self, tmp_path):
    import numpy as np

    from tensor2robot_tpu.fleet.orchestrator import Fleet, FleetConfig
    from tensor2robot_tpu.serving.router import ServingRouter

    config = FleetConfig(
        num_actors=1, env="mujoco_pose", image_size=16, action_dim=2,
        torso_filters=(8,), head_filters=(8,), dense_sizes=(16,),
        cem_population=8, cem_iterations=1, cem_elites=2,
        batch_size=8, batch_episodes=2, max_train_steps=2000,
        publish_every_steps=1000, serve_max_batch=4,
        transport="tcp", front_hosts=2, front_tenants=("a", "b"),
        front_respawn=True, max_front_restarts=2,
        telemetry_poll_secs=0.0, launch_timeout_secs=240.0,
        run_timeout_secs=900.0, seed=0)
    fleet = Fleet(config, str(tmp_path))
    events = []
    fleet.launch()
    try:
      router = ServingRouter(dict(fleet._addresses["fronts"]),
                             authkey=config.authkey, transport="tcp")
      try:
        def observer(event, index, address):
          events.append((event, index))
          if event in ("respawned", "added"):
            router.mark_alive(index, address)
          else:
            router.mark_dead(index)
        fleet.add_front_observer(observer)

        from tensor2robot_tpu.specs import make_random_tensors
        import jax  # noqa: F401 — spec sampling only
        from tensor2robot_tpu.fleet.host import _build_learner
        learner = _build_learner(config)
        obs = make_random_tensors(
            learner.observation_specification(), batch_size=1, seed=0)
        for tenant in ("a", "b"):
          assert np.asarray(router.predict(tenant, obs)).size > 0

        victim = router.placement("a")[0]
        fleet._fronts[victim].kill()
        deadline = time.monotonic() + 300.0
        while time.monotonic() < deadline:
          fleet._supervise_once()
          if any(r["target"] == f"front-{victim}"
                 for r in fleet.recoveries):
            break
          time.sleep(0.2)
        else:
          pytest.fail(f"front {victim} never recovered; "
                      f"events={events}")

        # Recovery accounting: a real MTTR, NO membership shrink.
        [recovery] = [r for r in fleet.recoveries
                      if r["target"] == f"front-{victim}"]
        assert recovery["mttr_ms"] > 0
        assert fleet.front_failures == []
        assert ("respawned", victim) in events
        # The respawned replica is live placement again — predicts
        # for its tenants answer without any manual rejoin.
        assert victim in router.alive()
        for tenant in ("a", "b"):
          assert np.asarray(router.predict(tenant, obs)).size > 0
        # ...and it SURVIVES that traffic: mark_alive flushed the
        # stale pre-kill sockets, so the respawned replica is not
        # demoted straight back to dead by its first checkout (a
        # failure mode failover masks whenever another replica
        # exists).
        assert victim in router.alive()
      finally:
        router.close()
    finally:
      fleet.shutdown(collect_metrics=False)
