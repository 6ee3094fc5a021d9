"""Tests for the on-device vectorized env subsystem (ISSUE 9).

Pins the functional-env contract (docs/ENVS.md): host-vs-device pose
parity on matched geometry, auto-reset semantics at episode
boundaries, same-key scenario determinism (the JaxARC property), the
rollout engine's replay-wire-spec output, the jit-once guarantee (no
retrace across iterations), and the --trainer=anakin e2e loop.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.telemetry.records import read_records
from tensor2robot_tpu.envs import (
    AutoResetEnv,
    BatchedEnv,
    JaxEnvBandit,
    PoseBanditEnv,
    ProcGenGraspEnv,
    evaluate_scenarios,
    host_parity_env,
    make_anakin_collect_fn,
    make_batched,
    make_collect_fn,
    train_anakin,
)
from tensor2robot_tpu.envs.rollout import flatten_time, rollout
from tensor2robot_tpu.research.qtopt import (
    GraspingQModel,
    QTOptLearner,
)

RNG = jax.random.PRNGKey(0)


def _tiny_learner(image_size=16, **learner_kwargs):
  model = GraspingQModel(image_size=image_size, torso_filters=(8,),
                         head_filters=(8,), dense_sizes=(16,),
                         action_dim=2)
  learner_kwargs.setdefault("cem_population", 8)
  learner_kwargs.setdefault("cem_iterations", 1)
  learner_kwargs.setdefault("cem_elites", 2)
  return QTOptLearner(model, **learner_kwargs)


class TestHostDeviceParity:
  """The pose env mirrors `PoseGraspBandit` on matched geometry."""

  def test_reward_parity_on_matched_geometry(self):
    from tensor2robot_tpu.research.pose_env.grasp_bandit import (
        PoseGraspBandit,
    )

    host = PoseGraspBandit(image_size=16, physics=False, seed=3)
    device = host_parity_env(host)
    _, poses = host.reset_batch(64)
    actions = np.random.default_rng(0).uniform(
        -1, 1, (64, 2)).astype(np.float32)
    host_rewards = host.grade(actions, poses)
    device_rewards = np.asarray(jax.device_get(jax.vmap(
        device.grasp_reward)(jnp.asarray(actions),
                             jnp.asarray(poses))))
    # Same float32 math on both sides; a mixed batch (some successes)
    # proves the comparison isn't vacuous.
    np.testing.assert_array_equal(host_rewards, device_rewards)
    assert 0.0 < host_rewards.mean() < 1.0 or host_rewards.mean() == 0.0

  def test_step_reward_equals_host_grade(self):
    from tensor2robot_tpu.research.pose_env.grasp_bandit import (
        grade_grasp,
    )

    env = PoseBanditEnv(image_size=16)
    state = env.reset(RNG)
    action = jnp.asarray([0.3, -0.2])
    _, _, reward, done = env.step(state, action, RNG)
    expected = grade_grasp(np.asarray(action)[None],
                           np.asarray(state.pose)[None],
                           threshold=0.1)[0]
    assert float(reward) == float(expected)
    assert bool(done)  # single-step bandit

  def test_noiseless_frames_bitwise_equal(self):
    from tensor2robot_tpu.research.pose_env.pose_env import PoseEnv

    host = PoseEnv(image_size=16, seed=5, noise=0.0)
    host_obs = host.reset()
    device = PoseBanditEnv(image_size=16, noise=0.0)
    device_obs = device.observe(
        device.state_at(host.pose, jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(np.asarray(device_obs["image"]),
                                  host_obs["image"])


class TestAutoReset:

  def test_resets_at_step_limit(self):
    env = PoseBanditEnv(image_size=8, max_episode_steps=3)
    wrapped = AutoResetEnv(env)
    state = wrapped.reset(RNG)
    pose0 = np.asarray(state.pose)
    miss = jnp.asarray([1.0, 1.0])  # corner: never within threshold
    key = jax.random.PRNGKey(1)
    for t in range(2):
      state, _, reward, done = wrapped.step(
          state, miss, jax.random.fold_in(key, t))
      assert not bool(done) and float(reward) == 0.0
      # Mid-episode: same block, advancing clock.
      np.testing.assert_array_equal(np.asarray(state.pose), pose0)
      assert int(state.t) == t + 1
    state, obs, reward, done = wrapped.step(
        state, miss, jax.random.fold_in(key, 2))
    assert bool(done)
    # The returned state is a FRESH episode: clock zeroed, new block.
    assert int(state.t) == 0
    assert not np.array_equal(np.asarray(state.pose), pose0)

  def test_terminal_obs_is_old_episode(self):
    env = PoseBanditEnv(image_size=8, noise=0.0, max_episode_steps=1)
    wrapped = AutoResetEnv(env)
    state = wrapped.reset(RNG)
    pose0 = np.asarray(state.pose)
    new_state, obs, _, done = wrapped.step(
        state, jnp.asarray([1.0, 1.0]), jax.random.PRNGKey(1))
    assert bool(done)
    old_frame = env.observe(
        env.state_at(pose0, jax.random.PRNGKey(9)))["image"]
    np.testing.assert_array_equal(np.asarray(obs["image"]),
                                  np.asarray(old_frame))
    fresh_frame = wrapped.observe(new_state)["image"]
    assert not np.array_equal(np.asarray(fresh_frame),
                              np.asarray(old_frame))

  def test_success_ends_episode(self):
    env = PoseBanditEnv(image_size=8, max_episode_steps=5)
    state = env.reset(RNG)
    hit = state.pose / jnp.asarray(0.4)  # exact grasp, normalized
    _, _, reward, done = env.step(state, hit, RNG)
    assert float(reward) == 1.0 and bool(done)


class TestScenarioDeterminism:
  """JaxARC property: the key IS the scenario."""

  def test_same_key_same_scenario(self):
    env = ProcGenGraspEnv(image_size=16)
    a = env.reset(jax.random.PRNGKey(7))
    b = env.reset(jax.random.PRNGKey(7))
    for leaf_a, leaf_b in zip(jax.tree_util.tree_leaves(a),
                              jax.tree_util.tree_leaves(b)):
      np.testing.assert_array_equal(np.asarray(leaf_a),
                                    np.asarray(leaf_b))
    np.testing.assert_array_equal(
        np.asarray(env.observe(a)["image"]),
        np.asarray(env.observe(b)["image"]))

  def test_different_keys_differ(self):
    env = ProcGenGraspEnv(image_size=16)
    a = env.reset(jax.random.PRNGKey(7))
    b = env.reset(jax.random.PRNGKey(8))
    assert not np.array_equal(np.asarray(a.pose), np.asarray(b.pose))

  def test_scenario_diversity_and_buckets(self):
    env = ProcGenGraspEnv(image_size=16, max_distractors=3)
    states = jax.vmap(env.reset)(jax.random.split(RNG, 128))
    buckets = np.asarray(jax.vmap(env.scenario_bucket)(states))
    # All four buckets appear and geometry actually varies.
    assert set(buckets.tolist()) == {0, 1, 2, 3}
    assert np.asarray(states.half_extent).std() > 0
    assert np.asarray(states.workspace).std() > 0

  def test_sweep_digests_reproduce(self):
    learner = _tiny_learner()
    state = learner.create_state(RNG)
    env = ProcGenGraspEnv(image_size=16, action_dim=2)
    a = evaluate_scenarios(learner, state, env=env,
                           num_scenarios=32, seed=3)
    b = evaluate_scenarios(learner, state, env=env,
                           num_scenarios=32, seed=3)
    c = evaluate_scenarios(learner, state, env=env,
                           num_scenarios=32, seed=4)
    assert a["action_digest"] == b["action_digest"]
    assert a["scenario_digest"] == b["scenario_digest"]
    assert a["scenario_digest"] != c["scenario_digest"]
    assert sum(row["count"] for row in a["per_bucket"].values()) == 32


class TestRolloutEngine:

  def test_batch_matches_replay_wire_spec(self):
    learner = _tiny_learner()
    env = PoseBanditEnv(image_size=16, action_dim=2)
    init_fn, collect_fn = make_collect_fn(
        learner, env, num_envs=4, rollout_length=3, epsilon=0.5)
    states = jax.jit(init_fn)(RNG)
    state = learner.create_state(RNG)
    _, batch = jax.jit(collect_fn)(state, states,
                                   jax.random.PRNGKey(2))
    spec = learner.transition_specification().to_flat_dict()
    assert set(batch) == set(spec)
    for key, sp in spec.items():
      assert batch[key].shape == (12,) + tuple(sp.shape), key
      assert batch[key].dtype == sp.dtype, key
    # Wire batches feed the replay plane unchanged.
    from tensor2robot_tpu.research.qtopt import ReplayBuffer
    replay = ReplayBuffer(learner.transition_specification(),
                          capacity=64)
    replay.add({k: np.asarray(v) for k, v in batch.items()})
    assert len(replay) == 12

  def test_per_env_keys_are_independent(self):
    env = PoseBanditEnv(image_size=8)
    batched = BatchedEnv(env, 16)
    states = batched.reset(RNG)
    poses = np.asarray(states.pose)
    assert np.unique(poses, axis=0).shape[0] == 16

  def test_jit_once_across_iterations(self):
    learner = _tiny_learner()
    env = PoseBanditEnv(image_size=16, action_dim=2)
    init_fn, collect_fn = make_collect_fn(
        learner, env, num_envs=4, rollout_length=2)
    traces = {"count": 0}

    def counted(learner_state, env_states, key):
      traces["count"] += 1
      return collect_fn(learner_state, env_states, key)

    collect = jax.jit(counted)
    state = learner.create_state(RNG)
    env_states = jax.jit(init_fn)(RNG)
    for t in range(4):
      env_states, batch = collect(state, env_states,
                                  jax.random.fold_in(RNG, t))
    float(batch["reward"].sum())
    assert traces["count"] == 1  # one trace, many dispatches

  def test_done_rows_present_and_rewards_graded(self):
    env = PoseBanditEnv(image_size=8)  # single-step: every row done
    batched = make_batched(env, 8)

    def random_policy(obs, key):
      del obs
      return jax.random.uniform(key, (8, 2), minval=-1.0, maxval=1.0)

    states = batched.reset(RNG)
    _, traj = jax.jit(
        lambda st, key: rollout(batched, random_policy, st, key, 4))(
            states, jax.random.PRNGKey(3))
    flat = flatten_time(traj)
    np.testing.assert_array_equal(np.asarray(flat["done"]),
                                  np.ones((32, 1), np.float32))
    rewards = np.asarray(flat["reward"])
    assert set(np.unique(rewards)).issubset({0.0, 1.0})

  def test_anakin_scaleout_matches_wire(self):
    learner = _tiny_learner()
    env = PoseBanditEnv(image_size=16, action_dim=2)
    devices = jax.local_devices()[:2]
    init_fn, collect_fn = make_anakin_collect_fn(
        learner, env, num_envs=4, rollout_length=2, devices=devices)
    state = learner.create_state(RNG)
    env_states = init_fn(RNG)
    _, batch = collect_fn(state, env_states, jax.random.PRNGKey(2))
    from tensor2robot_tpu.envs import flatten_devices
    flat = flatten_devices(batch)
    assert flat["image"].shape == (8, 16, 16, 3)
    assert flat["action"].shape == (8, 2)


class TestJaxEnvBandit:
  """The host adapter: functional envs as GraspActor scenario sources."""

  def test_bandit_interface(self):
    bandit = JaxEnvBandit(env=ProcGenGraspEnv(image_size=16), seed=0)
    obs, poses = bandit.reset_batch(8)
    assert obs["image"].shape == (8, 16, 16, 3)
    assert obs["image"].dtype == np.uint8
    assert poses.shape == (8, 2)
    assert bandit.last_buckets is not None
    rewards = bandit.grade(
        np.zeros((8, 2), np.float32), poses)
    assert rewards.shape == (8,)
    transitions = bandit.sample_transitions(8)
    assert set(transitions) == {"image", "action", "reward", "done",
                                "next_image"}

  def test_grasp_actor_collects_through_bandit(self):
    from tensor2robot_tpu.research.qtopt import (
        GraspActor,
        ReplayBuffer,
    )

    learner = _tiny_learner()
    replay = ReplayBuffer(learner.transition_specification(),
                          capacity=128)
    actor = GraspActor(
        learner, replay,
        env=JaxEnvBandit(env=ProcGenGraspEnv(image_size=16), seed=1),
        batch_episodes=8, epsilon=0.5, seed=2)
    actor.collect_once()  # bootstrap (random policy)
    actor.update_state(learner.create_state(RNG))
    actor.collect_once()  # CEM policy through the adapter
    assert len(replay) == 16
    assert actor.episodes_collected == 16


class TestTrainAnakin:

  def test_e2e_smoke(self, tmp_path):
    learner = _tiny_learner()
    state = train_anakin(
        learner=learner,
        model_dir=str(tmp_path),
        env_family="pose",
        num_envs=16,
        rollout_length=2,
        train_batches_per_iter=4,
        batch_size=16,
        replay_capacity=128,
        max_train_steps=16,
        log_every_steps=8,
        save_checkpoints_steps=16,
        seed=0)
    assert int(state.step) == 16
    rows = read_records(str(tmp_path / "metrics_train.jsonl"))
    assert rows, "no train metrics written"
    for row in rows:
      # Zero by construction: acting and training params are the same
      # arrays inside one program.
      assert row["param_refresh_lag_steps"] == 0.0
      assert 0.0 <= row["replay_fill"] <= 1.0
      assert row["env_steps_per_sec"] > 0
    from tensor2robot_tpu.utils import checkpoints as ckpt_lib
    assert ckpt_lib.latest_step(str(tmp_path)) == 16

  def test_cadence_must_divide(self, tmp_path):
    learner = _tiny_learner()
    with pytest.raises(ValueError):
      train_anakin(learner=learner, model_dir=str(tmp_path),
                   num_envs=4, rollout_length=1,
                   train_batches_per_iter=4, batch_size=4,
                   max_train_steps=10,  # not a multiple of 4
                   log_every_steps=4, save_checkpoints_steps=4)

  def test_rejects_extra_state_features(self, tmp_path):
    model = GraspingQModel(image_size=16, torso_filters=(8,),
                           head_filters=(8,), dense_sizes=(16,),
                           action_dim=2,
                           extra_state_features={"gripper": (1,)})
    learner = QTOptLearner(model, cem_population=4,
                           cem_iterations=1, cem_elites=2)
    with pytest.raises(ValueError, match="extra keys"):
      train_anakin(learner=learner, model_dir=str(tmp_path),
                   num_envs=4, rollout_length=1,
                   train_batches_per_iter=1, batch_size=4,
                   max_train_steps=1, log_every_steps=1,
                   save_checkpoints_steps=1)

class TestPodAnakin:
  """Pod mode (ISSUE 10): the ENTIRE collect-and-learn iteration as
  one pmap'd SPMD program — per-device env shards and replay rings,
  per-device Bellman batches, gradients pmean'd over the device axis
  before the replicated Adam+Polyak update."""

  POD_KWARGS = dict(
      env_family="pose", num_envs=16, rollout_length=2,
      train_batches_per_iter=4, batch_size=16, replay_capacity=128,
      max_train_steps=16, log_every_steps=8,
      save_checkpoints_steps=16, seed=0)

  def test_pod_smoke_metrics_and_exact_resume(self, tmp_path):
    learner = _tiny_learner()
    state = train_anakin(learner=learner, model_dir=str(tmp_path),
                         num_devices=2, **self.POD_KWARGS)
    # Returned state is the unreplicated device-0 replica.
    assert int(state.step) == 16
    rows = read_records(str(tmp_path / "metrics_train.jsonl"))
    assert rows
    for row in rows:
      # Zero by construction at ANY device count: acting params ARE
      # the training params inside the one pmap'd program.
      assert row["param_refresh_lag_steps"] == 0.0
      assert row["devices"] == 2
      assert row["global_batch_size"] == 32
      # Bellman throughput counts one per-device batch per step.
      assert row["bellman_batches_per_sec"] == pytest.approx(
          2 * row["grad_steps_per_sec"])
      assert 0.0 <= row["replay_fill"] <= 1.0
    # (The cross-device param-checksum agreement asserted at every log
    # boundary inside the loop did not fire — replicas stayed equal.)
    from tensor2robot_tpu.utils import checkpoints as ckpt_lib
    assert ckpt_lib.latest_step(str(tmp_path)) == 16
    # Resume restores the learner exactly: a second call at the same
    # max step trains zero iterations and returns the checkpoint.
    resumed = train_anakin(learner=learner, model_dir=str(tmp_path),
                           num_devices=2, **self.POD_KWARGS)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)),
            np.asarray(jax.device_get(b))),
        state.train_state.params, resumed.train_state.params)

  def test_pmean_parity_and_replication_invariant(self):
    """Statistical pin of the pmean'd update: a 2-device pmap step
    over two half batches equals the explicitly-averaged per-half
    gradients applied once (the DEFINITION of the pmean'd update —
    per-device batch-norm and loss semantics included), and the
    per-device results are bitwise IDENTICAL across the axis (the
    replication invariant pmean exists to preserve)."""
    import optax
    from tensor2robot_tpu.data.abstract_input_generator import Mode
    from tensor2robot_tpu.models import optimizers as opt_lib
    from tensor2robot_tpu.specs import (
        TensorSpecStruct,
        make_random_tensors,
    )

    # SGD, not Adam: the parity bound must survive the optimizer.
    # Adam's first step is ~sign(g)·lr, which flips on near-zero
    # gradients under any last-ulp noise; SGD keeps the update linear
    # in the pmean'd gradient so the tolerance is meaningful.
    model = GraspingQModel(
        image_size=16, torso_filters=(8,), head_filters=(8,),
        dense_sizes=(16,), action_dim=2,
        create_optimizer_fn=lambda: opt_lib.create_optimizer(
            optimizer_name="sgd", learning_rate=0.1))
    state = model.create_train_state(jax.random.PRNGKey(0),
                                     batch_size=2)
    feats = make_random_tensors(
        model.get_feature_specification(Mode.TRAIN), batch_size=32,
        seed=1)
    feats = {k: jnp.asarray(v) for k, v in feats.items()}
    labels = {"target_q": jax.random.uniform(jax.random.PRNGKey(2),
                                             (32, 1))}
    rng = jax.random.PRNGKey(3)
    struct = TensorSpecStruct.from_flat_dict

    devices = jax.local_devices()[:2]
    split = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: x.reshape((2, 16) + x.shape[1:]), t)
    pod_step = jax.pmap(
        lambda s, f, l, r: model.train_step(
            s, struct(f), struct(l), r, axis_name="pod"),
        axis_name="pod", devices=devices, in_axes=(0, 0, 0, None))
    replicated = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (2,) + x.shape), state)
    got, got_metrics = pod_step(replicated, split(feats),
                                split(labels), rng)

    # Replication invariant: both replicas hold bitwise-equal params.
    for leaf in jax.tree_util.tree_leaves(
        jax.device_get(got.params)):
      np.testing.assert_array_equal(np.asarray(leaf)[0],
                                    np.asarray(leaf)[1])

    # Reference: per-half gradients (same per-device BN/loss
    # semantics), explicitly averaged, applied once.
    def half(f, l):
      grad_fn = jax.value_and_grad(model.loss_fn, has_aux=True)
      (loss, (_, stats)), grads = grad_fn(
          state.params, state.batch_stats, struct(f), struct(l),
          rng, Mode.TRAIN)
      return loss, stats, grads
    half = jax.jit(half)
    halves = [jax.tree_util.tree_map(lambda x, i=i: x[i * 16:
                                                      (i + 1) * 16],
                                     t)
              for t in (feats, labels) for i in (0, 1)]
    l0, s0, g0 = half(halves[0], halves[2])
    l1, s1, g1 = half(halves[1], halves[3])
    mean2 = lambda a, b: jax.tree_util.tree_map(  # noqa: E731
        lambda x, y: (x + y) / 2, a, b)

    @jax.jit
    def apply(grads):
      updates, _ = model.tx.update(grads, state.opt_state,
                                   state.params)
      return optax.apply_updates(state.params, updates)

    ref_params = apply(mean2(g0, g1))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(jax.device_get(a)),
            np.asarray(jax.device_get(b))[0], rtol=1e-4, atol=1e-5),
        ref_params, got.params)
    # Cross-replica batch stats: pmean of the per-half BN statistics.
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(jax.device_get(a)),
            np.asarray(jax.device_get(b))[0], rtol=1e-4, atol=1e-5),
        mean2(s0, s1), got.batch_stats)
    # Metrics are pmean'd: device-0 reports the global mean loss.
    np.testing.assert_allclose(float(got_metrics["loss"][0]),
                               (float(l0) + float(l1)) / 2,
                               rtol=1e-4, atol=1e-5)

  def test_pod_validates_devices_and_divisibility(self, tmp_path):
    learner = _tiny_learner()
    with pytest.raises(ValueError, match="divide"):
      train_anakin(learner=learner, model_dir=str(tmp_path),
                   num_envs=6, rollout_length=1, num_devices=4,
                   train_batches_per_iter=1, batch_size=4,
                   max_train_steps=1, log_every_steps=1,
                   save_checkpoints_steps=1)
    with pytest.raises(ValueError, match="devices are visible"):
      train_anakin(learner=learner, model_dir=str(tmp_path),
                   num_envs=64, rollout_length=1, num_devices=64,
                   train_batches_per_iter=1, batch_size=4,
                   max_train_steps=1, log_every_steps=1,
                   save_checkpoints_steps=1)

  def test_pod_ignores_shard_weight_update_with_warning(
      self, tmp_path, caplog):
    """pmap replicas are single-device programs: the GSPMD constraint
    has no mesh to act on, so pod mode warns and proceeds."""
    import logging

    learner = _tiny_learner()
    with caplog.at_level(logging.WARNING,
                         logger="tensor2robot_tpu.envs.rollout"):
      state = train_anakin(
          learner=learner, model_dir=str(tmp_path), env_family="pose",
          num_envs=4, rollout_length=1, train_batches_per_iter=1,
          batch_size=4, replay_capacity=16, max_train_steps=2,
          log_every_steps=2, save_checkpoints_steps=2, num_devices=2,
          shard_weight_update=True, seed=0)
    assert int(state.step) == 2
    assert any("shard_weight_update" in r.message
               for r in caplog.records)

  def test_single_program_shard_weight_update_smoke(self, tmp_path):
    """The PR-6 composition on the jit+mesh path: a short single-
    program run with the flag on completes and checkpoints on the
    8-virtual-device mesh (moments constrained by the update
    sharding; 1-device meshes are the pinned bitwise no-op)."""
    learner = _tiny_learner()
    state = train_anakin(
        learner=learner, model_dir=str(tmp_path), env_family="pose",
        num_envs=8, rollout_length=1, train_batches_per_iter=2,
        batch_size=8, replay_capacity=32, max_train_steps=4,
        log_every_steps=2, save_checkpoints_steps=4,
        shard_weight_update=True, seed=0)
    assert int(np.asarray(jax.device_get(state.step))) == 4

  @pytest.mark.slow
  def test_pod_one_device_bitwise_vs_single_program(self):
    """THE equivalence pin: at D=1 the pmap'd pod program reproduces
    the PR-9 single-device jitted program BITWISE — same PRNG
    streams, same ring schedule, same updates. XLA:CPU's LLVM
    backend makes per-module FMA-contraction choices (jit- and
    pmap-compiled modules of the same jaxpr drift by 1 ulp/step in
    the conv/dense backward), so the pin runs in a subprocess under
    an FMA-less ISA cap — program equivalence is exactly what
    remains once the compiler's contraction freedom is removed."""
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import tempfile
        import numpy as np, jax
        from tensor2robot_tpu.envs import train_anakin
        from tensor2robot_tpu.research.qtopt import (
            GraspingQModel, QTOptLearner)

        def tiny():
          model = GraspingQModel(image_size=16, torso_filters=(8,),
                                 head_filters=(8,), dense_sizes=(16,),
                                 action_dim=2)
          return QTOptLearner(model, cem_population=8,
                              cem_iterations=1, cem_elites=2)

        kwargs = dict(env_family="pose", num_envs=16,
                      rollout_length=2, train_batches_per_iter=4,
                      batch_size=16, replay_capacity=128,
                      max_train_steps=16, log_every_steps=8,
                      save_checkpoints_steps=16, seed=0)
        with tempfile.TemporaryDirectory() as t1:
          single = train_anakin(learner=tiny(), model_dir=t1, **kwargs)
        with tempfile.TemporaryDirectory() as t2:
          pod = train_anakin(learner=tiny(), model_dir=t2,
                             num_devices=1, **kwargs)
        for tag, a, b in (
            ("params", single.train_state.params,
             pod.train_state.params),
            ("batch_stats", single.train_state.batch_stats,
             pod.train_state.batch_stats),
            ("opt_state", single.train_state.opt_state,
             pod.train_state.opt_state),
            ("target_params", single.target_params,
             pod.target_params)):
          la = jax.tree_util.tree_leaves(jax.device_get(a))
          lb = jax.tree_util.tree_leaves(jax.device_get(b))
          for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y)), tag
        print("BITWISE_OK")
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                        "--xla_cpu_max_isa=SSE4_2")
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-4000:]
    assert "BITWISE_OK" in out.stdout

  @pytest.mark.slow
  def test_pod_two_devices_close_to_single_program(self, tmp_path):
    """Device-count invariance, statistically pinned end to end: a
    2-device pod run (same total envs, same per-device batch) stays
    a working learner — finite losses, full replay ring, and a final
    collect reward in the same regime as the single-program run."""
    learner = _tiny_learner()
    single = train_anakin(
        learner=learner, model_dir=str(tmp_path / "single"),
        **self.POD_KWARGS)
    pod = train_anakin(
        learner=learner, model_dir=str(tmp_path / "pod"),
        num_devices=2, **self.POD_KWARGS)
    rows_s = read_records(str(tmp_path / "single" / "metrics_train.jsonl"))
    rows_p = read_records(str(tmp_path / "pod" / "metrics_train.jsonl"))
    assert int(single.step) == int(pod.step) == 16
    assert np.isfinite(rows_p[-1]["loss"])
    # Same collection volume per iteration: both fill the ring at the
    # same rate even though the pod splits it across two shards.
    assert rows_p[-1]["replay_fill"] == rows_s[-1]["replay_fill"]
    # Both learners' Bellman targets live on the same sigmoid scale.
    assert abs(rows_p[-1]["target_mean"]
               - rows_s[-1]["target_mean"]) < 0.25


class TestScenarioSuccessEvalHook:
  """Per-checkpoint procgen robustness sweeps land in the metrics log
  AND the success-protocol artifact family (ISSUE 10 satellite)."""

  def test_checkpoint_sweep_logs_and_appends(self, tmp_path):
    from tensor2robot_tpu.hooks import ScenarioSuccessEvalHook

    learner = _tiny_learner()
    state = learner.create_state(RNG)
    env = ProcGenGraspEnv(image_size=16, action_dim=2)
    hook = ScenarioSuccessEvalHook(learner=learner, env=env,
                                   num_scenarios=32, seed=3)
    hook.begin(learner.model, str(tmp_path))
    # train_anakin hands hooks the device-0 critic TrainState.
    hook.after_checkpoint(500, state.train_state, str(tmp_path))
    hook.after_checkpoint(1000, state.train_state, str(tmp_path))

    rows = read_records(str(tmp_path / "metrics_scenario_eval.jsonl"))
    assert [r["step"] for r in rows] == [500, 1000]
    assert 0.0 <= rows[0]["success_rate"] <= 1.0
    assert "random_baseline_success_rate" in rows[0]
    assert any(k.startswith("bucket_") for k in rows[0])

    art = tmp_path / "success_protocol" / "scenarios_by_checkpoint.jsonl"
    records = [json.loads(line) for line in open(art)]
    assert [r["step"] for r in records] == [500, 1000]
    assert records[0]["phase"] == "checkpoint_sweep"
    assert records[0]["per_bucket"]
    # Seeded sweep: every checkpoint scored on the SAME scenario set.
    assert (records[0]["scenario_digest"]
            == records[1]["scenario_digest"])

  def test_every_n_checkpoints_thins(self, tmp_path):
    from tensor2robot_tpu.hooks import ScenarioSuccessEvalHook

    learner = _tiny_learner()
    state = learner.create_state(RNG)
    hook = ScenarioSuccessEvalHook(
        learner=learner, env=ProcGenGraspEnv(image_size=16,
                                             action_dim=2),
        num_scenarios=16, seed=1, every_n_checkpoints=2)
    hook.begin(learner.model, str(tmp_path))
    for step in (100, 200, 300):
      hook.after_checkpoint(step, state.train_state, str(tmp_path))
    rows = read_records(str(tmp_path / "metrics_scenario_eval.jsonl"))
    assert [r["step"] for r in rows] == [100, 300]


class TestTrainAnakinLearning:

  @pytest.mark.slow
  def test_anakin_learns_pose_bandit(self, tmp_path):
    # Training-quality check (slow lane): on-device online QT-Opt
    # should beat the random baseline on the pose bandit. Recipe
    # mirrors test_qtopt's proven toy-grasp clone (lr 1e-3, the
    # (16,32)/(32,)/(32,32) tower); measured on this host:
    # success 1.0 vs random ~0.09 at 600 steps in ~23s.
    from tensor2robot_tpu.models import optimizers as opt_lib

    model = GraspingQModel(
        image_size=16, action_dim=2, torso_filters=(16, 32),
        head_filters=(32,), dense_sizes=(32, 32),
        create_optimizer_fn=lambda: opt_lib.create_optimizer(
            learning_rate=1e-3))
    learner = QTOptLearner(model, cem_population=16,
                           cem_iterations=2, cem_elites=4)
    env = PoseBanditEnv(image_size=16, action_dim=2,
                        success_threshold=0.15)
    state = train_anakin(
        learner=learner, model_dir=str(tmp_path), env=env,
        num_envs=128, rollout_length=2, train_batches_per_iter=4,
        batch_size=128, replay_capacity=4096, max_train_steps=600,
        log_every_steps=200, save_checkpoints_steps=600, epsilon=0.3,
        seed=0)
    sweep = evaluate_scenarios(learner, state, env=env,
                               num_scenarios=256, seed=9,
                               cem_population=64, cem_iterations=3)
    assert sweep["success_rate"] > max(
        3 * sweep["random_baseline_success_rate"], 0.5), sweep


class TestShardMapPodProgram:
  """The jit+shard_map pod program over the named `pod` mesh axis
  (ISSUE 12): env shards / rings / Bellman batches ride
  PartitionSpec("pod"), training runs as GSPMD jit — so ZeRO
  (`shard_weight_update`) composes with the pod axis instead of being
  warn-ignored, and D=1 is bitwise the pmap pod program."""

  POD_KWARGS = dict(
      env_family="pose", num_envs=16, rollout_length=2,
      train_batches_per_iter=4, batch_size=16, replay_capacity=128,
      max_train_steps=16, log_every_steps=8,
      save_checkpoints_steps=16, seed=0)

  def test_smoke_metrics_and_exact_resume(self, tmp_path):
    learner = _tiny_learner()
    state = train_anakin(learner=learner, model_dir=str(tmp_path),
                         num_devices=2, pod_program="shard_map",
                         **self.POD_KWARGS)
    assert int(np.asarray(jax.device_get(state.step))) == 16
    rows = read_records(str(tmp_path / "metrics_train.jsonl"))
    assert rows
    for row in rows:
      # Same contract as the pmap pod program: acting params ARE the
      # training params inside the one jitted program.
      assert row["param_refresh_lag_steps"] == 0.0
      assert row["devices"] == 2
      assert row["global_batch_size"] == 32
      assert row["bellman_batches_per_sec"] == pytest.approx(
          2 * row["grad_steps_per_sec"])
      assert 0.0 <= row["replay_fill"] <= 1.0
      assert np.isfinite(row["loss"])
    resumed = train_anakin(learner=learner, model_dir=str(tmp_path),
                           num_devices=2, pod_program="shard_map",
                           **self.POD_KWARGS)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)),
            np.asarray(jax.device_get(b))),
        state.train_state.params, resumed.train_state.params)

  def test_zero_shards_moments_across_pod_axis(self, tmp_path,
                                               caplog):
    """THE composition pin: shard_weight_update in shard_map pod mode
    leaves optimizer moments sharded P over the `pod` axis — no
    warn-ignore path — while params stay replicated."""
    import logging

    from tensor2robot_tpu.envs.rollout import POD_AXIS

    learner = _tiny_learner(image_size=16)
    with caplog.at_level(logging.WARNING,
                         logger="tensor2robot_tpu.envs.rollout"):
      state = train_anakin(
          learner=learner, model_dir=str(tmp_path),
          num_devices=2, pod_program="shard_map",
          shard_weight_update=True, update_shard_min_size=64,
          sharding_rules="qtopt", **self.POD_KWARGS)
    # No warn-ignore: the flag composes instead of being dropped.
    assert not any("shard_weight_update" in r.message
                   for r in caplog.records)
    pod_sharded = [
        leaf for leaf in jax.tree_util.tree_leaves(
            state.train_state.opt_state)
        if hasattr(leaf, "sharding")
        and POD_AXIS in [ax for ax in leaf.sharding.spec if ax]]
    assert pod_sharded, "no optimizer moment rides the pod axis"
    for leaf in jax.tree_util.tree_leaves(state.train_state.params):
      assert leaf.sharding.spec == jax.sharding.PartitionSpec()

  def test_rejects_unknown_pod_program_and_family(self, tmp_path):
    learner = _tiny_learner()
    with pytest.raises(ValueError, match="pod_program"):
      train_anakin(learner=learner, model_dir=str(tmp_path),
                   num_devices=2, pod_program="spmd",
                   **self.POD_KWARGS)
    with pytest.raises(ValueError, match="unknown model family"):
      train_anakin(learner=learner, model_dir=str(tmp_path),
                   num_devices=2, pod_program="shard_map",
                   sharding_rules="nope", **self.POD_KWARGS)

  def test_two_devices_close_to_pmap_pod(self, tmp_path):
    """Program-substrate invariance, statistically pinned: the
    shard_map program at D=2 matches the pmap program's collection
    volume exactly and lands its Bellman targets in the same regime
    (global-batch GSPMD training vs per-device pmean'd training are
    numerically different schedules, not different learners)."""
    learner = _tiny_learner()
    pmap_state = train_anakin(
        learner=learner, model_dir=str(tmp_path / "pmap"),
        num_devices=2, **self.POD_KWARGS)
    sm_state = train_anakin(
        learner=learner, model_dir=str(tmp_path / "sm"),
        num_devices=2, pod_program="shard_map", **self.POD_KWARGS)
    rows_p = read_records(str(tmp_path / "pmap" /
                              "metrics_train.jsonl"))
    rows_s = read_records(str(tmp_path / "sm" /
                              "metrics_train.jsonl"))
    assert int(pmap_state.step) == int(
        np.asarray(jax.device_get(sm_state.step))) == 16
    assert rows_s[-1]["replay_fill"] == rows_p[-1]["replay_fill"]
    assert np.isfinite(rows_s[-1]["loss"])
    assert abs(rows_s[-1]["target_mean"]
               - rows_p[-1]["target_mean"]) < 0.25

  @pytest.mark.slow
  def test_shardmap_one_device_bitwise_vs_pmap_pod(self):
    """THE equivalence pin (acceptance, ISSUE 12): at D=1 the
    jit+shard_map pod program reproduces the pmap pod program BITWISE
    on params/opt_state/batch_stats/target_params — same PRNG
    schedule, same ring schedule, same updates. Runs in a subprocess
    under an FMA-less ISA cap (`--xla_cpu_max_isa=SSE4_2`), the PR-10
    methodology: jit- and pmap-compiled modules of the same jaxpr may
    differ by per-module FMA-contraction choices, and program
    equivalence is what remains once that freedom is removed."""
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import tempfile
        import numpy as np, jax
        from tensor2robot_tpu.envs import train_anakin
        from tensor2robot_tpu.research.qtopt import (
            GraspingQModel, QTOptLearner)

        def tiny():
          model = GraspingQModel(image_size=16, torso_filters=(8,),
                                 head_filters=(8,), dense_sizes=(16,),
                                 action_dim=2)
          return QTOptLearner(model, cem_population=8,
                              cem_iterations=1, cem_elites=2)

        kwargs = dict(env_family="pose", num_envs=16,
                      rollout_length=2, train_batches_per_iter=4,
                      batch_size=16, replay_capacity=128,
                      max_train_steps=16, log_every_steps=8,
                      save_checkpoints_steps=16, seed=0)
        with tempfile.TemporaryDirectory() as t1:
          pmap_pod = train_anakin(learner=tiny(), model_dir=t1,
                                  num_devices=1, **kwargs)
        with tempfile.TemporaryDirectory() as t2:
          sm_pod = train_anakin(learner=tiny(), model_dir=t2,
                                num_devices=1,
                                pod_program="shard_map", **kwargs)
        for tag, a, b in (
            ("params", pmap_pod.train_state.params,
             sm_pod.train_state.params),
            ("batch_stats", pmap_pod.train_state.batch_stats,
             sm_pod.train_state.batch_stats),
            ("opt_state", pmap_pod.train_state.opt_state,
             sm_pod.train_state.opt_state),
            ("target_params", pmap_pod.target_params,
             sm_pod.target_params)):
          la = jax.tree_util.tree_leaves(jax.device_get(a))
          lb = jax.tree_util.tree_leaves(jax.device_get(b))
          for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y)), tag
        print("BITWISE_OK")
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                        "--xla_cpu_max_isa=SSE4_2")
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-4000:]
    assert "BITWISE_OK" in out.stdout

  @pytest.mark.slow
  def test_zero_rewrap_across_device_counts_does_not_stack(
      self, tmp_path):
    """A caller may reuse ONE learner across device counts: the keyed
    `wrap_optimizer(key="shard_weight_update")` must REPLACE the
    previous pod-mesh wrap, not stack a constraint pinned to a dead
    mesh's devices (the failure this regression-pins)."""
    learner = _tiny_learner()
    kwargs = {**self.POD_KWARGS, "max_train_steps": 8,
              "log_every_steps": 4, "save_checkpoints_steps": 8}
    for run, dcount in enumerate((2, 4)):
      state = train_anakin(
          learner=learner, model_dir=str(tmp_path / str(run)),
          num_devices=dcount, pod_program="shard_map",
          shard_weight_update=True, update_shard_min_size=64,
          **kwargs)
      assert int(np.asarray(jax.device_get(state.step))) == 8
    # And the flag-OFF leak direction: a later run WITHOUT the flag on
    # the same learner must get the identity re-wrap, not the previous
    # run's pod-mesh-pinned ZeRO constraint — its moments replicate.
    state = train_anakin(
        learner=learner, model_dir=str(tmp_path / "off"),
        num_devices=2, pod_program="shard_map",
        shard_weight_update=False, **kwargs)
    assert int(np.asarray(jax.device_get(state.step))) == 8
    for leaf in jax.tree_util.tree_leaves(state.train_state.opt_state):
      if hasattr(leaf, "sharding"):
        assert leaf.sharding.spec == jax.sharding.PartitionSpec(), leaf
