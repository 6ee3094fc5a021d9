"""Full-scale success-protocol runs → committed artifacts.

BASELINE.md protocol step 3: score each policy checkpoint by
closed-loop success on ≥500 held-out episodes, per checkpoint, via the
per-checkpoint hooks — not a hand-rolled eval. This script trains the
flagship QT-Opt config and the gripper BC configs to their test-proven
levels and runs the SAME hooks the trainer runs, at protocol scale
(512 / 500 episodes), writing `metrics_success_eval.jsonl` next to the
train metrics and copying the results into
`artifacts/success_protocol/` (committed so a reader can see
protocol-scale numbers without running anything).

Usage:
  python scripts/run_success_protocol.py qtopt
  python scripts/run_success_protocol.py gripper
  python scripts/run_success_protocol.py online   # offline→online
  python scripts/run_success_protocol.py envs     # on-device anakin
                                                  # train + procedural
                                                  # scenario sweep
  python scripts/run_success_protocol.py seedcheck  # reproducibility
                                                  # dry run (CPU-ok)

Each mode prints one JSON line per artifact it wrote.

Seeding: every stochastic input of the online protocol is pinned by
`PROTOCOL_SEED` — replay sampling (the store's seeded Generator), actor
exploration (env + ε draws + CEM keys), trainer PRNG. `seedcheck` runs
the online plane twice under a synchronous collect→flush→sample
schedule and asserts the two sample schedules (SHA-256 over the exact
rows drawn) and action streams are identical; a threaded run's residual
variation is then attributable to thread interleaving alone, which the
staleness histogram measures rather than hides.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# After the path bootstrap: the script must run standalone
# (`python scripts/run_success_protocol.py ...`).
from tensor2robot_tpu.telemetry.records import read_records  # noqa: E402
ARTIFACTS = os.path.join(REPO, "artifacts", "success_protocol")

# The one seed every stochastic input of the protocol derives from.
PROTOCOL_SEED = 0


def _emit(name: str, payload: dict) -> None:
  os.makedirs(ARTIFACTS, exist_ok=True)
  print(json.dumps({"artifact": name, **payload}))


def _copy_jsonl(model_dir: str, tag: str, out_name: str) -> dict:
  src = os.path.join(model_dir, f"metrics_{tag}.jsonl")
  dst = os.path.join(ARTIFACTS, out_name)
  os.makedirs(ARTIFACTS, exist_ok=True)
  shutil.copyfile(src, dst)
  records = read_records(src)
  return {"records": len(records), "last": records[-1]}


def run_qtopt(tmp: str) -> None:
  """Flagship 64×64 QT-Opt: replay → fused Bellman → 512-episode CEM
  success eval per checkpoint (QTOptSuccessEvalHook)."""
  import jax.numpy as jnp  # noqa: F401  (device init)

  from tensor2robot_tpu.hooks import QTOptSuccessEvalHook
  from tensor2robot_tpu.models import optimizers as opt_lib
  from tensor2robot_tpu.research.qtopt import (
      GraspingQModel,
      QTOptLearner,
      ReplayBuffer,
      ToyGraspEnv,
      train_qtopt,
  )

  model = GraspingQModel(
      create_optimizer_fn=lambda: opt_lib.create_optimizer(
          learning_rate=1e-3))
  learner = QTOptLearner(model, cem_population=64, cem_iterations=2,
                         cem_elites=6)
  env = ToyGraspEnv(image_size=model.image_size,
                    action_dim=model.action_dim, seed=0)
  replay = ReplayBuffer(learner.transition_specification(),
                        capacity=16384)
  replay.add(env.sample_transitions(16384))

  model_dir = os.path.join(tmp, "qtopt")
  hook = QTOptSuccessEvalHook(
      learner,
      eval_kwargs={"num_episodes": 512,
                   "image_size": model.image_size, "seed": 5,
                   "cem_population": 64, "cem_iterations": 3})
  train_qtopt(
      learner=learner,
      model_dir=model_dir,
      replay_buffer=replay,
      max_train_steps=2000,
      batch_size=256,
      save_checkpoints_steps=500,
      log_every_steps=250,
      hooks=[hook],
  )
  info = _copy_jsonl(model_dir, "success_eval",
                     "qtopt_flagship_success_eval.jsonl")
  _emit("qtopt_flagship_success_eval.jsonl", info)


def run_qtopt_online(tmp: str) -> None:
  """BASELINE.md's offline-vs-online distinction at toy-env scale.

  The QT-Opt paper reports ~78-87% grasp success training offline-only
  and 96% after on-robot online fine-tuning (arXiv:1806.10293, cited
  in BASELINE.md — external anchor, not a reference-repo number). The
  in-repo equivalent of that regime: offline pretrain on logged random
  grasps (phase 1, identical to the flagship protocol run), then
  online fine-tune where ε-greedy CEM actor threads collect on-policy
  episodes into the SAME replay buffer, re-pulling the acting params
  at every checkpoint via ActorStateRefreshHook (phase 2 — the
  in-process stand-in for the robot fleet polling checkpoints,
  SURVEY.md §3 async actor/learner row). Success is scored by the
  same 512-episode CEM protocol per checkpoint in both phases; the
  artifact carries both curves plus a summary row.

  Fine-tune hyperparameters matter (first run, kept as
  `qtopt_online_vs_offline_flood.jsonl`): ε=0.1 actors at full
  collection rate flooded the buffer with ~12.7k success-biased
  episodes and ERODED the policy (63.9% → 62.1%) — with failures
  underrepresented near the argmax, the CEM decision boundary blurs.
  The committed regime therefore explores harder (ε=0.3, so ~a third
  of collected grasps are random-action failures), collects more
  gently (batch_episodes=32), and fine-tunes at a third of the
  pretrain lr — the toy-scale shape of the paper's on-robot recipe.
  """
  from tensor2robot_tpu.hooks import QTOptSuccessEvalHook
  from tensor2robot_tpu.models import optimizers as opt_lib
  from tensor2robot_tpu.replay import ReplayWriteService
  from tensor2robot_tpu.research.qtopt import (
      ActorStateRefreshHook,
      GraspActor,
      GraspingQModel,
      QTOptLearner,
      ReplayBuffer,
      ToyGraspEnv,
      train_qtopt,
  )
  from tensor2robot_tpu.serving import CEMPolicyServer

  model = GraspingQModel(
      create_optimizer_fn=lambda: opt_lib.create_optimizer(
          learning_rate=1e-3))
  learner = QTOptLearner(model, cem_population=64, cem_iterations=2,
                         cem_elites=6)
  env = ToyGraspEnv(image_size=model.image_size,
                    action_dim=model.action_dim, seed=PROTOCOL_SEED)
  replay = ReplayBuffer(learner.transition_specification(),
                        capacity=32768, seed=PROTOCOL_SEED)
  # The "logged dataset": random-policy grasps, the offline corpus.
  replay.add(env.sample_transitions(16384))

  model_dir = os.path.join(tmp, "qtopt_online")
  eval_kwargs = {"num_episodes": 512, "image_size": model.image_size,
                 "seed": 5, "cem_population": 64, "cem_iterations": 3}
  hook = QTOptSuccessEvalHook(learner, eval_kwargs=eval_kwargs)

  # --- Phase 1: offline-only pretrain. steps_per_dispatch=50 is the
  # iterations_per_loop lever: 50 steps per device program pay the
  # host's dispatch latency once (identical numerics, tested). ---
  offline_steps = 2000
  state = train_qtopt(
      learner=learner,
      model_dir=model_dir,
      replay_buffer=replay,
      max_train_steps=offline_steps,
      batch_size=256,
      save_checkpoints_steps=500,
      log_every_steps=250,
      steps_per_dispatch=50,
      seed=PROTOCOL_SEED,
      hooks=[hook],
  )

  # --- Phase 2: online fine-tune (resumes from phase 1's last
  # checkpoint in the same model_dir), through the REPLAY DATA PLANE:
  # the actor commits episode batches via a bounded ingestion queue
  # (drop-and-count overflow — an over-eager collector can never wedge
  # the learner), pulls its actions through the bucketed AOT serving
  # engine (the robot-fleet path), and the per-checkpoint refresh
  # hot-swaps the server's params. The fine-tune learner shares the
  # network but steps at lr/3 (adam moments restore structurally — lr
  # is applied at update time). The staleness the round-5 advisor
  # flagged is MEASURED here: the sampler's age histogram lands in the
  # train log and the committed summary.
  ft_model = GraspingQModel(
      create_optimizer_fn=lambda: opt_lib.create_optimizer(
          learning_rate=3e-4))
  ft_learner = QTOptLearner(ft_model, cem_population=64,
                            cem_iterations=2, cem_elites=6)
  acting0 = state.train_state.replace(opt_state=None)
  server = CEMPolicyServer(ft_learner, acting0, max_batch=32,
                           max_wait_us=2000, seed=PROTOCOL_SEED + 7)
  service = ReplayWriteService(replay.store, queue_batches=16,
                               overflow="drop")
  actor = GraspActor(
      ft_learner, service,
      env=ToyGraspEnv(image_size=model.image_size,
                      action_dim=model.action_dim,
                      seed=PROTOCOL_SEED + 123),
      batch_episodes=32, epsilon=0.3, seed=PROTOCOL_SEED + 11,
      policy_server=server)
  actor.update_state(acting0)
  try:
    train_qtopt(
        learner=ft_learner,
        model_dir=model_dir,
        replay_buffer=replay,
        max_train_steps=2 * offline_steps,
        batch_size=256,
        save_checkpoints_steps=500,
        log_every_steps=250,
        steps_per_dispatch=50,
        seed=PROTOCOL_SEED,
        hooks=[QTOptSuccessEvalHook(ft_learner,
                                    eval_kwargs=eval_kwargs),
               ActorStateRefreshHook([actor])],
    )
  finally:
    service.close()
    server.close()

  src = os.path.join(model_dir, "metrics_success_eval.jsonl")
  records = read_records(src)
  for r in records:
    r["phase"] = "offline" if r["step"] <= offline_steps else "online"
  offline_final = max(
      (r for r in records if r["phase"] == "offline"),
      key=lambda r: r["step"])
  online_final = max(
      (r for r in records if r["phase"] == "online"),
      key=lambda r: r["step"])
  best_online = max(
      (r["success_rate"] for r in records if r["phase"] == "online"),
      default=None)
  staleness = replay.staleness_snapshot()
  summary = {
      "step": online_final["step"],
      "phase": "summary",
      "offline_only_success_rate": offline_final["success_rate"],
      "online_finetuned_success_rate": online_final["success_rate"],
      "online_best_success_rate": best_online,
      "online_episodes_collected": actor.episodes_collected,
      "finetune_regime": "eps=0.3, batch_episodes=32, lr=3e-4",
      "replay_plane": {
          "ingestion": {k: v for k, v in
                        service.metrics_scalars().items()},
          "staleness": staleness,
          "serving_dispatches": server.engine.dispatch_count,
      },
      "paper_anchor": ("QT-Opt (arXiv:1806.10293): ~78-87% offline "
                       "vs 96% online, at robot scale"),
      "see_also": ("qtopt_online_vs_offline_flood.jsonl — the kept "
                   "negative result at eps=0.1/full-rate collection"),
  }
  os.makedirs(ARTIFACTS, exist_ok=True)
  dst = os.path.join(ARTIFACTS, "qtopt_online_vs_offline.jsonl")
  with open(dst, "w") as f:
    for r in records + [summary]:
      f.write(json.dumps(r) + "\n")
  _emit("qtopt_online_vs_offline.jsonl",
        {"records": len(records) + 1, "last": summary})


def run_envs(tmp: str) -> None:
  """Envs-family robustness protocol: Anakin-trained QT-Opt scored on
  a seeded PROCEDURAL scenario sweep, success per scenario bucket.

  The scenario source is `ProcGenGraspEnv` (tensor2robot_tpu/envs/):
  every PRNG key samples fresh geometry/dynamics — workspace scale,
  block size, sensor noise, distractor count, drift — so the sweep is
  a randomized robustness eval with unlimited variation, not a replay
  of a fixed episode set. Training runs `--trainer=anakin`'s
  fully-on-device loop (collection and Bellman updates in one jitted
  program, zero param-refresh lag); the 512-scenario sweep
  (`evaluate_scenarios`) then groups success by distractor count, with
  the random-policy baseline on the SAME scenarios for scale. All
  stochastic inputs derive from PROTOCOL_SEED; the sweep's
  action/scenario digests are the reproducibility handles `seedcheck`
  pins.
  """
  from tensor2robot_tpu.envs import (
      ProcGenGraspEnv,
      evaluate_scenarios,
      train_anakin,
  )
  from tensor2robot_tpu.models import optimizers as opt_lib
  from tensor2robot_tpu.research.qtopt import (
      GraspingQModel,
      QTOptLearner,
  )

  model = GraspingQModel(
      image_size=32, action_dim=2,
      torso_filters=(16, 32), head_filters=(32, 32),
      dense_sizes=(32, 32),
      create_optimizer_fn=lambda: opt_lib.create_optimizer(
          learning_rate=1e-3))
  learner = QTOptLearner(model, cem_population=64, cem_iterations=2,
                         cem_elites=6)
  env = ProcGenGraspEnv(image_size=32, action_dim=2)

  model_dir = os.path.join(tmp, "qtopt_envs")
  state = train_anakin(
      learner=learner,
      model_dir=model_dir,
      env=env,
      num_envs=256,
      rollout_length=4,
      train_batches_per_iter=4,
      batch_size=256,
      replay_capacity=16384,
      max_train_steps=2000,
      log_every_steps=200,
      save_checkpoints_steps=500,
      epsilon=0.1,
      seed=PROTOCOL_SEED,
  )

  sweep = evaluate_scenarios(learner, state, env=env,
                             num_scenarios=512,
                             seed=PROTOCOL_SEED + 5,
                             cem_population=64, cem_iterations=3)
  train_records = read_records(
      os.path.join(model_dir, "metrics_train.jsonl"))
  records = []
  for bucket, stats in sorted(sweep["per_bucket"].items()):
    records.append({"scenario_bucket": bucket,
                    "distractors": int(bucket), **stats})
  summary = {
      "phase": "summary",
      "scenario_family": "procgen",
      "success_rate": sweep["success_rate"],
      "random_baseline_success_rate":
          sweep["random_baseline_success_rate"],
      "num_scenarios": sweep["num_scenarios"],
      "action_digest": sweep["action_digest"],
      "scenario_digest": sweep["scenario_digest"],
      "train_steps": train_records[-1]["step"],
      "final_collect_reward_mean":
          train_records[-1]["collect_reward_mean"],
      "env_steps_per_sec_last": train_records[-1]["env_steps_per_sec"],
      "param_refresh_lag_steps": 0.0,
      "note": ("trained fully on device (--trainer=anakin): the "
               "collection policy reads the current learner params "
               "inside the training program, so lag is structural "
               "zero; scenario buckets = distractor count"),
  }
  os.makedirs(ARTIFACTS, exist_ok=True)
  dst = os.path.join(ARTIFACTS, "qtopt_envs_scenarios.jsonl")
  with open(dst, "w") as f:
    for r in records + [summary]:
      f.write(json.dumps(r) + "\n")
  _emit("qtopt_envs_scenarios.jsonl",
        {"records": len(records) + 1, "last": summary})


def run_seedcheck(tmp: str) -> None:
  """Reproducibility dry run: the online plane, twice, must match.

  Drives the SAME components the online protocol wires — seeded
  `ReplayBuffer` (1-shard store), `ReplayWriteService` ingestion,
  `GraspActor` exploration, `ReplayBatchSampler` — under a synchronous
  collect → flush → sample schedule (the deterministic projection of
  the threaded run: same seeds, interleaving fixed). Two passes must
  produce IDENTICAL sample schedules (SHA-256 over the exact rows
  drawn) and identical action streams; any divergence means an
  unseeded rng crept into the plane. Runs on CPU in seconds.
  """
  import hashlib

  import numpy as np

  from tensor2robot_tpu.replay import (
      ReplayBatchSampler,
      ReplayWriteService,
  )
  from tensor2robot_tpu.research.qtopt import (
      GraspActor,
      GraspingQModel,
      QTOptLearner,
      ReplayBuffer,
      ToyGraspEnv,
  )

  def one_pass():
    model = GraspingQModel(image_size=16, torso_filters=(8,),
                           head_filters=(8,), dense_sizes=(16,),
                           action_dim=2)
    learner = QTOptLearner(model, cem_population=8, cem_iterations=1,
                           cem_elites=2)
    replay = ReplayBuffer(learner.transition_specification(),
                          capacity=1024, seed=PROTOCOL_SEED)
    service = ReplayWriteService(replay.store, queue_batches=8,
                                 overflow="drop")
    env = ToyGraspEnv(image_size=16, action_dim=2,
                      seed=PROTOCOL_SEED + 123)
    actor = GraspActor(learner, service, env=env, batch_episodes=16,
                       epsilon=0.3, seed=PROTOCOL_SEED + 11)
    sampler = ReplayBatchSampler(replay.store, batch_size=32,
                                 record_schedule=True)
    actions = hashlib.sha256()
    import jax
    actor.update_state(learner.create_state(
        jax.random.PRNGKey(PROTOCOL_SEED)))
    for cycle in range(6):
      actor.collect_once()
      service.flush()
      replay.store.set_learner_step(cycle)
      batch = sampler.sample()
      actions.update(
          np.ascontiguousarray(batch.to_flat_dict()["action"]).tobytes())
    service.close()
    return {
        "sample_schedule_sha256": sampler.schedule_digest(),
        "action_stream_sha256": actions.hexdigest(),
        "staleness_mean": sampler.staleness_snapshot()["mean_age_steps"],
        "episodes": actor.episodes_collected,
    }

  def envs_pass():
    # The envs-family half of the protocol (ISSUE 9): the procedural
    # scenario sweep must reproduce its scenario AND action digests
    # bit-for-bit from PROTOCOL_SEED — scenarios are pure functions of
    # keys, so any divergence means an unseeded input crept in.
    import jax

    from tensor2robot_tpu.envs import ProcGenGraspEnv, evaluate_scenarios

    model = GraspingQModel(image_size=16, torso_filters=(8,),
                           head_filters=(8,), dense_sizes=(16,),
                           action_dim=2)
    learner = QTOptLearner(model, cem_population=8, cem_iterations=1,
                           cem_elites=2)
    state = learner.create_state(jax.random.PRNGKey(PROTOCOL_SEED))
    sweep = evaluate_scenarios(
        learner, state,
        env=ProcGenGraspEnv(image_size=16, action_dim=2),
        num_scenarios=64, seed=PROTOCOL_SEED)
    return {"scenario_sweep_action_sha256": sweep["action_digest"],
            "scenario_sweep_scenario_sha256": sweep["scenario_digest"]}

  def pod_pass():
    # Pod-scale Anakin reproducibility (ISSUE 10): the pmap'd
    # collect-and-learn program must reproduce the SAME final learner
    # params from PROTOCOL_SEED at EVERY device count — per-device
    # PRNG folds by absolute step + axis index, so each count is its
    # own deterministic experiment. Digests are recorded per count
    # (1 = the PR-9 single-device jit program, >=2 = the pmap'd pod;
    # counts above the visible device count are skipped and recorded
    # as such).
    import hashlib

    import jax
    import numpy as np

    from tensor2robot_tpu.envs import train_anakin

    visible = len(jax.local_devices())
    digests = {"pod_visible_devices": visible}
    for count in (1, 2):
      key = f"pod_params_sha256_devices_{count}"
      if count > visible:
        digests[key] = "skipped: not enough local devices"
        continue
      model = GraspingQModel(image_size=16, torso_filters=(8,),
                             head_filters=(8,), dense_sizes=(16,),
                             action_dim=2)
      learner = QTOptLearner(model, cem_population=8,
                             cem_iterations=1, cem_elites=2)
      with tempfile.TemporaryDirectory() as pod_tmp:
        state = train_anakin(
            learner=learner, model_dir=pod_tmp, env_family="procgen",
            num_envs=8, rollout_length=2, train_batches_per_iter=2,
            batch_size=8, replay_capacity=64, max_train_steps=4,
            log_every_steps=2, save_checkpoints_steps=4,
            # count 1 runs the PR-9 jit program (num_devices=None),
            # >=2 the pmap'd pod (docs/ENVS.md, "Pod mode").
            num_devices=None if count == 1 else count,
            seed=PROTOCOL_SEED)
      digest = hashlib.sha256()
      for leaf in jax.tree_util.tree_leaves(
          jax.device_get(state.train_state.params)):
        digest.update(np.ascontiguousarray(leaf).tobytes())
      digests[key] = digest.hexdigest()
    return digests

  a, b = one_pass(), one_pass()
  ea, eb = envs_pass(), envs_pass()
  pa, pb = pod_pass(), pod_pass()
  a.update(ea)
  a.update(pa)
  b.update(eb)
  b.update(pb)
  ok = (a["sample_schedule_sha256"] == b["sample_schedule_sha256"]
        and a["action_stream_sha256"] == b["action_stream_sha256"]
        and ea == eb and pa == pb)
  print(json.dumps({"artifact": "seedcheck", "reproducible": ok,
                    "run_a": a, "run_b": b}))
  if not ok:
    raise SystemExit("seedcheck FAILED: two seeded dry runs diverged")


def run_gripper(tmp: str) -> None:
  """Gripper BC twice over: per-step clone through SuccessEvalHook
  (500 episodes/checkpoint) and the long-context transformer clone
  through its history-accumulating EpisodeContextPolicy (500
  episodes)."""
  import jax

  from tensor2robot_tpu import train_eval
  from tensor2robot_tpu.data.tfrecord_input_generator import (
      TFRecordEpisodeInputGenerator,
  )
  from tensor2robot_tpu.hooks import SuccessEvalHook
  from tensor2robot_tpu.models import optimizers as opt_lib
  from tensor2robot_tpu.research.vrgripper import (
      TransitionInputGenerator,
      VRGripperRegressionModel,
      VRGripperTransformerModel,
      collect_demo_episodes,
      evaluate_gripper_policy,
  )
  from tensor2robot_tpu.train_eval import MetricLogger
  from tensor2robot_tpu.utils import checkpoints as ckpt_lib

  img = 24
  demos = os.path.join(tmp, "demos.tfrecord")
  collect_demo_episodes(demos, num_episodes=96, image_size=img,
                        seed=0, action_noise=0.1)

  # --- Per-step BC clone, protocol through the checkpoint hook. ---
  bc = VRGripperRegressionModel(
      image_size=img, filters=(8, 16), embedding_size=32,
      hidden_sizes=(32,),
      create_optimizer_fn=lambda: opt_lib.create_optimizer(
          learning_rate=3e-3))
  bc_dir = os.path.join(tmp, "bc")
  train_eval.train_eval_model(
      model=bc,
      model_dir=bc_dir,
      input_generator_train=TransitionInputGenerator(
          TFRecordEpisodeInputGenerator(
              file_patterns=demos, sequence_length=12, seed=1),
          batch_size=32, seed=1),
      max_train_steps=500,
      batch_size=32,
      save_checkpoints_steps=500,
      log_every_steps=200,
      hooks=[SuccessEvalHook(
          eval_fn=evaluate_gripper_policy,
          eval_kwargs={"num_episodes": 500, "image_size": img,
                       "seed": 5})],
  )
  info = _copy_jsonl(bc_dir, "success_eval",
                     "vrgripper_bc_success_eval.jsonl")
  _emit("vrgripper_bc_success_eval.jsonl", info)

  # --- Long-context transformer clone, full-history policy. ---
  tr = VRGripperTransformerModel(
      image_size=img, filters=(8, 16), embedding_size=32, width=48,
      depth=1, num_heads=2, max_context_length=64,
      attention_impl="reference",
      create_optimizer_fn=lambda: opt_lib.create_optimizer(
          learning_rate=3e-3))
  tr_dir = os.path.join(tmp, "transformer")
  train_eval.train_eval_model(
      model=tr,
      model_dir=tr_dir,
      input_generator_train=TFRecordEpisodeInputGenerator(
          file_patterns=demos, sequence_length=16, batch_size=16,
          shuffle_buffer_size=96, seed=1),
      max_train_steps=400,
      batch_size=8,
      save_checkpoints_steps=400,
      log_every_steps=100,
  )
  state = tr.create_inference_state(jax.random.PRNGKey(0))
  variables = ckpt_lib.restore_variables(
      tr_dir, like={"params": state.params,
                    "batch_stats": state.batch_stats or {}})
  state = state.replace(params=variables["params"])
  policy = tr.make_context_policy(state, context_length=16)
  metrics = evaluate_gripper_policy(
      policy, num_episodes=500, image_size=img, seed=5)
  logger = MetricLogger(tr_dir)
  try:
    logger.write("success_eval", 400, metrics)
  finally:
    logger.close()
  info = _copy_jsonl(tr_dir, "success_eval",
                     "vrgripper_transformer_success_eval.jsonl")
  _emit("vrgripper_transformer_success_eval.jsonl", info)


def main():
  mode = sys.argv[1] if len(sys.argv) > 1 else ""
  runners = {"qtopt": run_qtopt, "gripper": run_gripper,
             "online": run_qtopt_online, "envs": run_envs,
             "seedcheck": run_seedcheck}
  if mode not in runners:
    raise SystemExit(
        "usage: run_success_protocol.py "
        "{qtopt|gripper|online|envs|seedcheck}")
  with tempfile.TemporaryDirectory() as tmp:
    runners[mode](tmp)


if __name__ == "__main__":
  main()
