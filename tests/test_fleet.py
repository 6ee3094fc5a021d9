"""Fleet orchestrator tests: the failure paths ARE the product.

The lifecycle contract of docs/FLEET.md, pinned:

  * an actor crash mid-episode never lands partial rows (the staged
    half-episode is aborted on disconnect, across the process
    boundary);
  * the restart policy respawns a crashed actor whose session reopen
    discards stale staged state; the abort policy takes the fleet
    down;
  * learner death is detected and the actors exit;
  * the shutdown barrier (normal AND after an injected crash) leaks
    zero child processes and zero shm segments;
  * a two-actor fleet runs end-to-end on CPU with the param
    publication channel live (`param_refresh_lag` measured, policy
    versions monotonic);
  * fleet actor processes import WITHOUT jax (the Podracer actors-
    are-cheap property).
"""

from __future__ import annotations

import json
import multiprocessing as mp
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from tensor2robot_tpu.fleet import (
    Fleet,
    FleetConfig,
    FleetError,
    RpcClient,
    RpcError,
    RpcServer,
)
from tensor2robot_tpu.fleet import host as host_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
      run_timeout_secs=420.0, seed=0)
  base.update(overrides)
  return FleetConfig(**base)


def _shm_entries():
  try:
    return set(os.listdir("/dev/shm"))
  except FileNotFoundError:  # non-Linux: nothing to pin
    return set()


def _assert_no_new_shm(before):
  """Zero-shm-leak pin: once the fleet handle is released (callers
  `del` their Fleet first — while it lives, its own stop Events /
  heartbeat Values legitimately hold `sem.mp-*` entries), /dev/shm is
  back to baseline. The contract is about what SURVIVES the fleet."""
  import gc

  gc.collect()
  deadline = time.monotonic() + 10.0
  while time.monotonic() < deadline:
    if not _shm_entries() - before:
      return
    time.sleep(0.1)
  assert _shm_entries() - before == set()


def _fleet_children():
  return [p for p in mp.active_children()
          if p.name.startswith("t2r-fleet")]


def _transitions(n=4, size=16):
  return {
      "image": np.zeros((n, size, size, 3), np.uint8),
      "action": np.zeros((n, 2), np.float32),
      "reward": np.ones((n, 1), np.float32),
      "done": np.ones((n, 1), np.float32),
      "next_image": np.zeros((n, size, size, 3), np.uint8),
  }


class TestRpc:
  """Transport-level contract: errors travel, disconnects fire."""

  def test_roundtrip_error_and_disconnect_callback(self):
    seen = {"disconnects": 0}

    def handler(method, payload, ctx):
      if method == "echo":
        ctx["n"] = ctx.get("n", 0) + 1
        return {"payload": payload, "call": ctx["n"]}
      if method == "boom":
        raise ValueError("intentional")
      if method == "__disconnect__":
        seen["disconnects"] += 1
        seen["calls_at_disconnect"] = ctx.get("n", 0)
        return None
      raise KeyError(method)

    with RpcServer(handler, authkey=b"test") as server:
      client = RpcClient(server.address, authkey=b"test")
      assert client.call("echo", 1) == {"payload": 1, "call": 1}
      assert client.call("echo", "x")["call"] == 2
      with pytest.raises(RpcError, match="intentional"):
        client.call("boom")
      # The connection survives a handler error.
      assert client.call("echo", None)["call"] == 3
      client.close()
      deadline = time.monotonic() + 5
      while seen["disconnects"] == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert seen["disconnects"] == 1
    assert seen["calls_at_disconnect"] == 3

  def test_ephemeral_coordinator_addresses_are_distinct(self):
    from tensor2robot_tpu.parallel.distributed import (
        ephemeral_coordinator_address,
    )

    first = ephemeral_coordinator_address()
    second = ephemeral_coordinator_address()
    assert first.startswith("127.0.0.1:")
    # Two concurrent launches (two fleets, two test runs) must never
    # be handed the same port.
    assert first != second


class TestParamsVersion:
  """The hot-swap publication counter (the param_refresh_lag seam)."""

  def test_engine_version_monotonic_and_learner_step_stamped(self):
    import jax

    from tensor2robot_tpu import specs
    from tensor2robot_tpu.data.abstract_input_generator import Mode
    from tensor2robot_tpu.serving import BucketedServingEngine
    from tensor2robot_tpu.utils.mocks import MockT2RModel

    model = MockT2RModel()
    state = model.create_inference_state(jax.random.PRNGKey(0))
    wire = specs.flatten_spec_structure(
        model.preprocessor.get_in_feature_specification(Mode.PREDICT))
    example = specs.make_random_tensors(wire, batch_size=1, seed=0)
    engine = BucketedServingEngine(model.predict_step, state, example,
                                   max_batch=2)
    assert engine.params_version == 0
    assert engine.params_learner_step == 0
    engine.swap_state(state, learner_step=40)
    assert engine.params_version == 1
    assert engine.params_learner_step == 40
    # A swap without a stamp keeps the previous learner step (a
    # non-learner swapper must not reset the lag clock).
    engine.swap_state(state)
    assert engine.params_version == 2
    assert engine.params_learner_step == 40
    engine.swap_state(state, learner_step=80)
    assert engine.params_version == 3
    assert engine.params_learner_step == 80


class TestPoseGraspBandit:
  """The adapter that lets GraspActor drive the pose envs."""

  def test_reset_grade_shapes_and_threshold(self):
    from tensor2robot_tpu.research.pose_env.grasp_bandit import (
        PoseGraspBandit,
    )
    from tensor2robot_tpu.research.pose_env.pose_env import (
        WORKSPACE_HIGH,
    )

    bandit = PoseGraspBandit(image_size=16, physics=False, seed=3,
                             success_threshold=0.1)
    observations, poses = bandit.reset_batch(5)
    assert observations["image"].shape == (5, 16, 16, 3)
    assert observations["image"].dtype == np.uint8
    assert poses.shape == (5, 2)
    # A perfect grasp (the pose mapped back to [-1, 1]) succeeds; the
    # far corner fails.
    perfect = poses / WORKSPACE_HIGH
    assert bandit.grade(perfect, poses).all()
    miss = -np.sign(perfect) * np.ones_like(perfect)
    assert bandit.grade(miss, poses).sum() == 0

  def test_physics_variant_settles_poses(self):
    from tensor2robot_tpu.research.pose_env.grasp_bandit import (
        PoseGraspBandit,
    )

    bandit = PoseGraspBandit(image_size=16, physics=True, seed=5)
    _, poses = bandit.reset_batch(2)
    # Settled poses differ from the commanded drop (contact dynamics
    # moved the block) — the physics is real, not a relabeled RNG.
    assert not np.allclose(poses[-1], bandit.env.last_drop_pose)


class TestActorImportClosure:

  def test_actor_modules_import_without_jax(self):
    # The Podracer actors-are-cheap property: everything a fleet actor
    # process imports must stay jax-free (no XLA runtime per actor).
    code = (
        "import sys; "
        "import tensor2robot_tpu.fleet.actor, "
        "tensor2robot_tpu.fleet.pod, "
        "tensor2robot_tpu.fleet.rpc, tensor2robot_tpu.fleet.proc, "
        "tensor2robot_tpu.research.qtopt.actor, "
        "tensor2robot_tpu.research.qtopt.grasping_env, "
        "tensor2robot_tpu.research.pose_env.grasp_bandit; "
        "assert 'jax' not in sys.modules, 'jax leaked'; "
        "print('JAXFREE')")
    result = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO)
    assert result.returncode == 0, result.stderr
    assert "JAXFREE" in result.stdout

  def test_fleet_is_in_t2rcheck_concurrency_scope(self):
    from tensor2robot_tpu.analysis import cli

    assert "tensor2robot_tpu/fleet" in cli._CONCURRENCY_PATHS

  def test_entry_binary_import_initializes_no_backend(self):
    # multiprocessing's spawn re-imports `__main__` in every fleet
    # child BEFORE its target runs, and the shipped binary is that
    # __main__ — so its import closure must not execute any jax
    # computation: an initialized XLA backend makes the learner
    # group's `jax.distributed.initialize` raise (found by driving
    # qtopt_fleet_hybrid.gin through the real run_t2r_trainer; a
    # module-level `jnp.array` constant was enough to trip it).
    # This subprocess run is the e2e WITNESS; the static guarantee is
    # JAX205 (analysis/spmd_rules.py), which scans the COMPUTED entry
    # import closure so new modules are covered without editing any
    # list here (tests/test_analysis.py::TestSpmdRules).
    code = (
        "import tensor2robot_tpu.bin.run_t2r_trainer; "
        "from jax._src import xla_bridge; "
        "assert not xla_bridge.backends_are_initialized(), "
        "'entry import ran a jax computation'; "
        "print('BACKEND_FREE')")
    result = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=180, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert result.returncode == 0, result.stderr
    assert "BACKEND_FREE" in result.stdout


class TestClaimDevice:
  """A fleet child that cannot get its device ends at once, saying
  why — not silently at the orchestrator's heartbeat timeout."""

  def test_returns_when_the_backend_comes_up(self):
    from tensor2robot_tpu.fleet import proc

    assert proc.claim_device("host") is None

  def test_init_failure_exits_with_the_reason(self, monkeypatch):
    import jax

    from tensor2robot_tpu.fleet import proc

    def no_chip():
      raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_chip)
    with pytest.raises(SystemExit) as failure:
      proc.claim_device("front-1")
    message = str(failure.value)
    assert "fleet front-1" in message
    assert "Unable to initialize backend 'tpu'" in message
    assert "one process at a time" in message
    assert "JAX_PLATFORMS=cpu" in message

  def test_blocked_init_kills_the_process(self):
    code = (
        "import time, jax\n"
        "from tensor2robot_tpu.fleet import proc\n"
        "jax.devices = lambda: time.sleep(600)\n"
        "proc.claim_device('learner', timeout_secs=0.2)\n"
        "print('SURVIVED')\n")
    result = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert result.returncode == 1
    assert "SURVIVED" not in result.stdout
    assert "still blocked after" in result.stderr


class TestHostSessionAbort:
  """The mid-episode crash contract across the process boundary."""

  @pytest.fixture(scope="class")
  def host(self):
    ctx = mp.get_context("spawn")
    config = _tiny_config()
    parent_conn, child_conn = ctx.Pipe()
    stop = ctx.Event()
    heartbeat = ctx.Value("d", 0.0)
    process = ctx.Process(
        target=host_lib.host_main,
        args=(config, child_conn, stop, heartbeat),
        name="t2r-fleet-host", daemon=True)
    process.start()
    child_conn.close()
    assert parent_conn.poll(240.0), "host never reported ready"
    address = tuple(parent_conn.recv()["address"])
    parent_conn.close()
    yield config, address
    stop.set()
    process.join(timeout=30.0)
    if process.is_alive():
      process.terminate()
      process.join(5.0)
    assert process.exitcode == 0

  def test_dropped_connection_aborts_staged_episode(self, host):
    config, address = host
    actor = RpcClient(address, authkey=config.authkey)
    actor.call("begin_episode", "actor-crashy")
    actor.call("append", {"actor_id": "actor-crashy",
                          "transitions": _transitions()})
    # The actor process "dies" mid-episode: connection drops with the
    # episode staged but never ended.
    actor.close()

    observer = RpcClient(address, authkey=config.authkey)
    deadline = time.monotonic() + 10
    aborted = 0.0
    while time.monotonic() < deadline:
      metrics = observer.call("metrics")
      aborted = metrics["service"]["replay_aborted_episodes"]
      if aborted >= 1.0:
        break
      time.sleep(0.05)
    assert aborted >= 1.0
    # Not one staged row landed.
    assert observer.call("size") == 0
    assert metrics["store"]["adds_total"] == 0.0

    # A committed episode DOES land (the abort above was surgical) and
    # carries the refresh-lag stamp.
    committer = RpcClient(address, authkey=config.authkey)
    payload = {"actor_id": "actor-ok", "transitions": _transitions(),
               "policy_version": 0, "policy_learner_step": 0}
    assert committer.call("commit", payload) is True
    deadline = time.monotonic() + 10
    while observer.call("size") < 4 and time.monotonic() < deadline:
      time.sleep(0.05)
    assert observer.call("size") == 4
    assert observer.call("metrics")["param_refresh_lag"]["rows"] == 4
    committer.close()
    observer.close()

  def test_acting_state_serves_params_once_per_version(self, host):
    # The pod param seam (ISSUE 19): `acting_state` returns the full
    # publication on a version move and a stamp-only reply otherwise,
    # so a polling pod pays the state transfer once per publication.
    config, address = host
    pod = RpcClient(address, authkey=config.authkey)
    first = pod.call("acting_state", {"have_version": -1})
    # Version 0 exists from engine construction — a pod's first
    # refresh always lands acting params.
    assert first["params_version"] >= 0
    assert first["state"] is not None
    assert "params_learner_step" in first
    assert "params_hop" in first
    second = pod.call(
        "acting_state", {"have_version": first["params_version"]})
    assert second["state"] is None
    assert second["params_version"] == first["params_version"]
    assert second["params_learner_step"] == first["params_learner_step"]
    pod.close()


class TestLearnerGroup:
  """The multi-process learner-group contract (ISSUE 19)."""

  def test_plan_roles_shards_and_publication(self):
    from tensor2robot_tpu.fleet.learner import learner_group_plan

    config = _tiny_config()  # batch_size=16
    solo = learner_group_plan(config, world_size=1, rank=0)
    assert solo == {"role": "learner", "local_batch_size": 16,
                    "publishes": True}
    chief = learner_group_plan(config, world_size=2, rank=0)
    assert chief["role"] == "learner"
    assert chief["local_batch_size"] == 8
    assert chief["publishes"] is True
    peer = learner_group_plan(config, world_size=2, rank=1)
    assert peer["role"] == "learner-r1"
    assert peer["local_batch_size"] == 8
    assert peer["publishes"] is False

  def test_plan_rejects_bad_geometry(self):
    from tensor2robot_tpu.fleet.learner import learner_group_plan

    config = _tiny_config()
    with pytest.raises(ValueError, match="divide"):
      learner_group_plan(config, world_size=3, rank=0)
    with pytest.raises(ValueError, match="rank"):
      learner_group_plan(config, world_size=2, rank=2)

  def test_config_rejects_unsound_group_geometry(self):
    with pytest.raises(ValueError, match="divide"):
      _tiny_config(learner_hosts=2, batch_size=15)
    with pytest.raises(ValueError, match="fatal"):
      _tiny_config(learner_hosts=2, learner_crash_policy="resume")
    with pytest.raises(ValueError, match="collector"):
      _tiny_config(num_actors=0, pod_hosts=0)

  def test_non_chief_rank_owns_no_host_side_surface(
      self, tmp_path, monkeypatch):
    # The rank-0-only side-effect pin: a rank-1 process runs the same
    # loop (its batch shard feeds the shared GSPMD program) and makes
    # the COLLECTIVE checkpoint-save calls (orbax barriers pair across
    # ranks; primary-host ownership keeps process 0 the data writer),
    # but owns none of the chief's host-side surfaces — no train
    # metrics, no sentinel pages.
    import jax

    from tensor2robot_tpu.models import optimizers as opt_lib
    from tensor2robot_tpu.research.qtopt import (
        GraspingQModel,
        QTOptLearner,
        train_qtopt,
    )

    model = GraspingQModel(
        image_size=16, action_dim=2, torso_filters=(8,),
        head_filters=(8,), dense_sizes=(16,),
        create_optimizer_fn=lambda: opt_lib.create_optimizer(
            learning_rate=1e-3))
    learner = QTOptLearner(model, cem_population=8, cem_iterations=1,
                           cem_elites=2)
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    model_dir = str(tmp_path / "rank1")
    state = train_qtopt(
        learner=learner, model_dir=model_dir, max_train_steps=4,
        batch_size=8, save_checkpoints_steps=4, log_every_steps=2,
        prefill_random=True)
    assert int(np.asarray(state.step)) == 4  # it DID train
    # ckpt/ is the collective surface (here process_count is 1, so
    # this mocked rank doubles as orbax's primary host); every
    # chief-only file — metrics_train.jsonl and friends — is absent.
    assert os.listdir(model_dir) == ["ckpt"]

  def test_single_member_group_is_bitwise_single_learner(
      self, tmp_path):
    # The N=1 acceptance pin: the learner-group path (coordinator
    # adoption → jax.distributed init → plan-sized batch) produces
    # BITWISE the single-learner params — the group machinery is the
    # existing path at world_size=1, not an approximation of it.
    import subprocess

    worker = os.path.join(REPO, "tests", "learner_group_worker.py")
    outputs = {}
    for mode in ("plain", "group"):
      outfile = str(tmp_path / f"{mode}.npz")
      env = {k: v for k, v in os.environ.items()
             if not k.startswith(("JAX_", "XLA_", "TPU"))}
      env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
      env["TF_CPP_MIN_LOG_LEVEL"] = "2"
      result = subprocess.run(
          [sys.executable, worker, mode, outfile,
           str(tmp_path / mode)],
          env=env, capture_output=True, text=True, timeout=300)
      assert result.returncode == 0, (
          f"{mode} worker failed:\n{result.stdout}\n{result.stderr}")
      assert "BITWISE_OK" in result.stdout
      outputs[mode] = dict(np.load(outfile))
    assert set(outputs["plain"]) == set(outputs["group"])
    for key, plain in outputs["plain"].items():
      grouped = outputs["group"][key]
      assert plain.dtype == grouped.dtype, key
      assert np.array_equal(plain, grouped), key


class TestPodUnits:
  """The pod module's pure seams (jax-free, like the module import)."""

  def test_env_family_maps_onto_functional_envs(self):
    from tensor2robot_tpu.fleet.pod import pod_env_family

    assert pod_env_family("pose") == "pose"
    assert pod_env_family("mujoco_pose") == "pose"
    assert pod_env_family("procgen") == "procgen"
    with pytest.raises(ValueError, match="functional"):
      pod_env_family("toy_grasp")

  def test_trim_devices_largest_dividing_prefix(self):
    from tensor2robot_tpu.fleet.pod import trim_devices

    devices = [f"d{i}" for i in range(8)]
    assert trim_devices(devices, 32) == devices  # 8 | 32
    assert trim_devices(devices, 12) == devices[:6]
    assert trim_devices(devices, 7) == devices[:7]
    assert trim_devices(devices[:3], 16) == devices[:2]
    assert trim_devices(devices[:1], 5) == devices[:1]  # always valid

  def test_pod_home_shard_remap_is_minimal(self):
    # Rendezvous placement over the `pod-N` id namespace: shrinking
    # the shard set remaps ONLY pods homed on the removed shard, and
    # growing it moves pods ONLY onto the new shard — everyone else's
    # segments keep landing where they always did.
    from tensor2robot_tpu.fleet.actor import home_shard

    pods = [f"pod-{k}" for k in range(32)]
    with_three = {p: home_shard(p, 3) for p in pods}
    with_two = {p: home_shard(p, 2) for p in pods}
    displaced = [p for p in pods if with_three[p] == 2]
    assert displaced  # the pin is vacuous if nobody homed on shard 2
    for p in pods:
      if with_three[p] != 2:
        assert with_two[p] == with_three[p], p
      if with_two[p] != with_three[p]:
        assert with_three[p] == 2, p


class TestFleetLifecycle:
  """Whole-topology runs: the expensive, load-bearing pins."""

  @pytest.mark.slow
  def test_two_actor_smoke_end_to_end(self, tmp_path):
    shm_before = _shm_entries()
    # distributed_learner=True also exercises the collision-safe
    # ephemeral-coordinator handoff end to end (a 1-process gloo
    # cluster in the learner child).
    config = _tiny_config(env="mujoco_pose", distributed_learner=True,
                          telemetry_poll_secs=2.0)
    fleet = Fleet(config, str(tmp_path / "fleet"))
    result = fleet.run()

    assert result.clean_shutdown
    assert result.metrics["store"]["adds_total"] > 0
    assert result.env_steps_per_sec > 0
    # The learner ran to max_train_steps and its rate was measured
    # over the learner-step window.
    assert result.metrics["learner_window"]["last_step"] == 16
    assert result.learner_steps_per_sec > 0
    # The publication channel was live: the final checkpoint publishes
    # too, so >= 2 refreshes reached the serving engine, versions are
    # monotonic, and committed rows carry lag attribution.
    assert result.publishes >= 2
    assert result.params_version == result.publishes
    assert result.param_refresh_lag["rows"] > 0
    assert result.param_refresh_lag["max"] >= 0
    # The learner's training batches have a measured staleness
    # distribution (ages in learner steps).
    staleness = [s for s in result.replay_staleness.values() if s]
    assert staleness and staleness[0]["rows"] > 0
    # The telemetry plane of a healthy fleet: every role's trace
    # merges into one timeline WITH spans (a process that configured
    # tracing and wedged would leave a meta line only), the
    # orchestrator's aggregated records keep the envelope schema and
    # carry the samplers' watermarks, and nothing alerted.
    from tensor2robot_tpu.telemetry import merge, records, sentinel
    trace_dir = tmp_path / "fleet" / "telemetry"
    roles = set(merge.roles_with_spans(merge.merge_traces(
        str(trace_dir))))
    assert roles >= {"host", "learner", "actor-0", "actor-1"}, roles
    with open(trace_dir / "fleet_metrics.jsonl") as f:
      aggregated = [json.loads(line) for line in f if line.strip()]
    assert aggregated
    assert [records.validate_record(r) for r in aggregated] == (
        [[]] * len(aggregated))
    assert any("rsrc." in key for record in aggregated
               for key in record["payload"])
    assert sentinel.read_alerts(
        str(trace_dir / sentinel.ALERTS_FILENAME)) == []
    # The shutdown barrier: no child processes, no shm segments.
    assert _fleet_children() == []
    del fleet
    _assert_no_new_shm(shm_before)

  @pytest.mark.slow
  def test_actor_crash_restart_lands_no_partial_rows(self, tmp_path):
    shm_before = _shm_entries()
    config = _tiny_config(
        actor_crash_after_episodes=2, actor_crash_mode="mid_episode",
        crash_actor_index=0, max_actor_restarts=2)
    fleet = Fleet(config, str(tmp_path / "fleet"))
    result = fleet.run()

    service = result.metrics["service"]
    # The crash was real (the orchestrator restarted the actor), the
    # reopen aborted the staged half-episode, and every row that DID
    # land arrived in whole batch_episodes-sized commits — a partial
    # episode would break the divisibility.
    assert result.actor_restarts >= 1
    assert service["replay_actor_restarts"] >= 1.0
    assert service["replay_aborted_episodes"] >= 1.0
    assert result.metrics["store"]["adds_total"] % config.batch_episodes == 0
    assert result.clean_shutdown
    assert _fleet_children() == []
    del fleet
    _assert_no_new_shm(shm_before)

  @pytest.mark.slow
  def test_learner_death_detected_and_actors_exit(self, tmp_path):
    shm_before = _shm_entries()
    config = _tiny_config(learner_crash_after_steps=4)
    fleet = Fleet(config, str(tmp_path / "fleet"))
    with pytest.raises(FleetError, match="learner died"):
      fleet.run()
    # The abort teardown stopped every actor and the host — crash
    # shutdown leaks nothing either.
    assert _fleet_children() == []
    del fleet
    _assert_no_new_shm(shm_before)

  @pytest.mark.slow
  def test_actor_abort_policy_takes_fleet_down(self, tmp_path):
    config = _tiny_config(
        actor_crash_after_episodes=1, actor_crash_mode="hard",
        actor_crash_policy="abort")
    fleet = Fleet(config, str(tmp_path / "fleet"))
    with pytest.raises(FleetError, match="actor 0 died"):
      fleet.run()
    assert _fleet_children() == []


class TestHybridPodracer:
  """ISSUE 19 end-to-end: Anakin pods and the learner group live in
  the supervised fleet, under the same atomic-commit and rank-0-only
  publication contracts the unit pins promise."""

  @pytest.mark.slow
  def test_pod_commits_land_whole_across_pod_kill(self, tmp_path):
    from tensor2robot_tpu.fleet import faults

    shm_before = _shm_entries()
    # A pods-only fleet (num_actors=0) with one planned mid-segment
    # kill: the staged wire batch is aborted on disconnect, the
    # restart policy respawns pod-0, and every landed row arrived in
    # whole segment-sized commits.
    plan = faults.FaultPlan(seed=0, events=(faults.FaultEvent(
        fault=faults.ACTOR_CRASH, target="pod-0", at=2,
        mode="mid_episode"),))
    # The run must outlast the respawn for the recovery to be observed.
    # With the persistent compile cache warm the learner's 16 default
    # steps take no time at all, so it gets enough steps to still be
    # training when the new pod stamps its first heartbeat.
    config = _tiny_config(
        num_actors=0, pod_hosts=1, envs_per_pod=8,
        pod_rollout_length=2, env="mujoco_pose", fault_plan=plan,
        max_actor_restarts=2, restart_window_secs=600.0,
        max_train_steps=160)
    fleet = Fleet(config, str(tmp_path / "fleet"))
    result = fleet.run()

    assert result.clean_shutdown
    assert result.actor_restarts >= 1  # the pod respawn is counted
    assert [r["target"] for r in result.recoveries] == ["pod-0"]
    assert result.recoveries[0]["fault"] == "actor_crash"
    assert result.recoveries[0]["mttr_ms"] > 0
    service = result.metrics["service"]
    assert service["replay_aborted_episodes"] >= 1.0
    segment_rows = config.envs_per_pod * config.pod_rollout_length
    committed = int(service["replay_committed_transitions"])
    assert committed > 0
    assert committed % segment_rows == 0
    assert _fleet_children() == []
    del fleet
    _assert_no_new_shm(shm_before)

  @pytest.mark.slow
  def test_hybrid_fleet_end_to_end(self, tmp_path):
    shm_before = _shm_entries()
    # The full hybrid topology, tiny: one process actor and one Anakin
    # pod feed the same replay plane while a 2-process learner group
    # trains over the shared mesh — and only rank 0 publishes (the
    # publication counter and the engine version counter must agree,
    # which a double-publishing rank 1 would break).
    config = _tiny_config(
        env="mujoco_pose", num_actors=1, pod_hosts=1,
        envs_per_pod=8, pod_rollout_length=2, learner_hosts=2)
    fleet = Fleet(config, str(tmp_path / "fleet"))
    result = fleet.run()

    assert result.clean_shutdown
    assert result.metrics["store"]["adds_total"] > 0
    assert result.metrics["learner_window"]["last_step"] == 16
    assert result.publishes >= 2
    assert result.params_version == result.publishes
    assert result.param_refresh_lag["rows"] > 0
    assert _fleet_children() == []
    del fleet
    _assert_no_new_shm(shm_before)
