"""Anakin-style fully-on-device rollouts closing the loop into QT-Opt.

Podracer's Anakin architecture (PAPERS.md, arXiv:2104.06272): when the
env is a pure function (envs/core.py), acting and environment stepping
compile into the SAME device program as training — `lax.scan` over
steps × `vmap` over envs — so thousands of parallel envs run per
dispatch and no transition ever crosses the host data plane. Compare
the fleet topology (docs/FLEET.md): there every transition pays
RPC + ingestion queue + sampling, and actors act on params up to a
publish-cadence stale. Here the rollout policy reads the CURRENT
learner params inside the very program that updates them —
``param_refresh_lag`` is zero by construction, and the only host
traffic is the metrics scalar pull at the log cadence.

Three layers, composable separately:

  * ``rollout`` / ``make_collect_fn`` — the scan×vmap engine producing
    replay-wire-spec transition batches ([T·N] rows matching
    `QTOptLearner.transition_specification`).
  * ``train_anakin`` — the `--trainer=anakin` online mode: one jitted
    iteration = collect a segment into a DEVICE-RESIDENT replay ring +
    K Bellman grad steps on uniform samples from it. The ring is part
    of the donated carry — QT-Opt stays off-policy-capable without a
    host replay service.
  * ``JaxEnvBandit`` / ``evaluate_scenarios`` — the host seams: the
    batched-bandit adapter `GraspActor` drives (a functional env as a
    scenario source), and the seeded procedural scenario sweep
    `run_success_protocol envs` reports per-bucket success over.
"""

from __future__ import annotations

import logging
from typing import Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu import config as gin
from tensor2robot_tpu.envs.core import (
    AutoResetEnv,
    BatchedEnv,
    FunctionalEnv,
)
from tensor2robot_tpu.envs.pose import PoseBanditEnv
from tensor2robot_tpu.envs.procgen import ProcGenGraspEnv

log = logging.getLogger(__name__)

# The replay wire keys a single-camera transition batch carries
# (`QTOptLearner.transition_specification` for the flagship model).
WIRE_KEYS = ("image", "action", "reward", "done", "next_image")


def make_batched(env: FunctionalEnv, num_envs: int) -> BatchedEnv:
  """The canonical composition: auto-reset inside, vmap outside."""
  return BatchedEnv(AutoResetEnv(env), num_envs)


def rollout(batched: BatchedEnv,
            policy_fn: Callable[[Dict[str, jax.Array], jax.Array],
                                jax.Array],
            env_states, key: jax.Array, length: int):
  """`length` steps of every env in one `lax.scan`.

  ``policy_fn(obs, key) -> actions [N, A]`` acts on the batched
  observation. Returns ``(env_states', traj)`` where every traj leaf
  is [length, num_envs, ...] — transitions in wire order: ``image`` is
  the acting observation, ``next_image`` the post-transition one
  (terminal frame at episode ends, the auto-reset contract).
  """

  def body(states, step_key):
    # Two renders per env-step land here: this observe, and the
    # terminal observe inside step. For a continuing env they compute
    # the same frame, but XLA cannot CSE across the scan carry — and
    # restructuring to carry obs does NOT reduce the count: the next
    # acting obs needs the RESET frame where done, and under vmap the
    # done-select computes both branches for every env regardless.
    # One render/step is only reachable by storing the post-reset
    # frame as next_obs for done rows (wire-dishonest: replay would
    # carry the next episode's frame as a terminal observation).
    # The waste is bounded by render+step's share of a CEM-acting
    # iteration, which the CEM's scoring dominates (not measured on
    # the chip: ROADMAP W2) — not worth breaking the wire contract.
    obs = batched.observe(states)
    key_act, key_step = jax.random.split(step_key)
    actions = policy_fn(obs, key_act)
    next_states, next_obs, reward, done = batched.step(
        states, actions, key_step)
    transition = {
        "image": obs["image"],
        "action": actions,
        "reward": reward[:, None].astype(jnp.float32),
        "done": done[:, None].astype(jnp.float32),
        "next_image": next_obs["image"],
    }
    return next_states, transition

  return jax.lax.scan(body, env_states,
                      jax.random.split(key, length))


def flatten_time(traj):
  """[T, N, ...] → [T·N, ...]: a traj as one replay-wire batch."""
  return jax.tree_util.tree_map(
      lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]),
      traj)


def _check_wire_spec(learner) -> None:
  """train_anakin covers models whose transition spec is exactly the
  single-camera wire (image/action/reward/done/next_image): an env
  only renders images, so extra state features would sample as
  garbage. Fail loudly at setup instead."""
  spec = learner.transition_specification().to_flat_dict()
  extra = sorted(set(spec) - set(WIRE_KEYS))
  if extra:
    raise ValueError(
        "train_anakin needs a {image, action} model; the transition "
        f"spec carries extra keys the env cannot produce: {extra}")


def make_collect_fn(learner, env: FunctionalEnv, num_envs: int,
                    rollout_length: int, epsilon: float = 0.1,
                    cem_population: Optional[int] = None,
                    cem_iterations: Optional[int] = None):
  """(init_fn, collect_fn) for ε-greedy CEM collection on device.

  ``init_fn(key) -> env_states`` resets the batch;
  ``collect_fn(learner_state, env_states, key) -> (env_states',
  batch)`` rolls ``rollout_length`` steps of ``num_envs`` envs with
  the CEM policy over the passed learner params (ε-greedy per env-step
  — the actor fleet's exploration rule) and returns a flat
  [T·N]-row wire batch.
  """
  _check_wire_spec(learner)
  batched = make_batched(env, num_envs)
  policy = learner.build_policy(cem_population=cem_population,
                                cem_iterations=cem_iterations)
  epsilon = float(epsilon)
  from tensor2robot_tpu.specs import TensorSpecStruct

  def init_fn(key):
    return batched.reset(key)

  def collect_fn(learner_state, env_states, key):
    def policy_fn(obs, act_key):
      key_cem, key_eps, key_rand = jax.random.split(act_key, 3)
      greedy = policy(learner_state,
                      TensorSpecStruct.from_flat_dict(obs), key_cem)
      random_actions = jax.random.uniform(
          key_rand, greedy.shape, minval=-1.0, maxval=1.0)
      explore = (jax.random.uniform(key_eps, (num_envs,)) < epsilon)
      return jnp.where(explore[:, None], random_actions,
                       greedy).astype(jnp.float32)

    env_states, traj = rollout(batched, policy_fn, env_states, key,
                               rollout_length)
    return env_states, flatten_time(traj)

  return init_fn, collect_fn


def make_anakin_collect_fn(learner, env: FunctionalEnv,
                           num_envs: int, rollout_length: int,
                           epsilon: float = 0.1,
                           devices=None,
                           cem_population: Optional[int] = None,
                           cem_iterations: Optional[int] = None):
  """The full Anakin topology: vmap over envs INSIDE pmap over devices.

  Podracer's Anakin diagram verbatim (PAPERS.md): each device runs
  ``num_envs / D`` vmapped envs through the scan; the learner state
  broadcasts (in_axes=None) so every device acts with the same — and
  current — params. On a TPU host the pmap axis is the local chips; on
  CPU the 8-virtual-device mesh stands in AND sidesteps XLA:CPU's
  intra-op parallelism ceiling (one jitted rollout program leaves
  most of a many-core host idle while the pmap'd twin uses every
  core; a CPU observation, no device metric).

  Returns ``(init_fn, collect_fn)`` shaped like `make_collect_fn` but
  with a leading device axis on env states and collected batches
  ([D, T·N/D, ...] — `flatten_devices` folds it away).
  """
  devices = list(devices if devices is not None
                 else jax.local_devices())
  num_devices = len(devices)
  if num_envs % num_devices:
    raise ValueError(
        f"num_envs={num_envs} must divide across {num_devices} "
        "devices (pass devices= to pin a subset)")
  per_device = num_envs // num_devices
  inner_init, inner_collect = make_collect_fn(
      learner, env, per_device, rollout_length, epsilon=epsilon,
      cem_population=cem_population, cem_iterations=cem_iterations)
  pmap_init = jax.pmap(inner_init, devices=devices)
  pmap_collect = jax.pmap(inner_collect, in_axes=(None, 0, 0),
                          devices=devices)

  def init_fn(key):
    return pmap_init(jax.random.split(key, num_devices))

  def collect_fn(learner_state, env_states, key):
    return pmap_collect(learner_state, env_states,
                        jax.random.split(key, num_devices))

  return init_fn, collect_fn


def flatten_devices(batch):
  """[D, R, ...] → [D·R, ...]: a pmap'd collection as one wire batch."""
  return jax.tree_util.tree_map(
      lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]),
      batch)


def _build_env(env_family: str, model) -> FunctionalEnv:
  if env_family == "pose":
    return PoseBanditEnv(image_size=model.image_size,
                         action_dim=model.action_dim)
  if env_family == "procgen":
    return ProcGenGraspEnv(image_size=model.image_size,
                           action_dim=model.action_dim)
  raise ValueError(f"env_family={env_family!r} not in "
                   "('pose', 'procgen') and no env was passed")


# The pod axis name of the pod-mode SPMD program (docs/ENVS.md): the
# pmap device axis in the pmap program, and the NAMED MESH AXIS env
# shards / replay rings / the ZeRO update ride in the shard_map
# program (docs/SHARDING.md).
POD_AXIS = "pod"


def _param_checksum(qstate) -> jax.Array:
  """f32 digest of the online params: the cross-device agreement
  probe. Replicated params produce bit-identical per-device sums
  (same reduction order on every replica), so any drift — a missed
  pmean, a per-device RNG leaking into the update — shows up as
  checksum disagreement at the next log boundary."""
  leaves = jax.tree_util.tree_leaves(qstate.train_state.params)
  total = jnp.zeros((), jnp.float32)
  for leaf in leaves:
    total = total + jnp.sum(jnp.abs(leaf).astype(jnp.float32))
  return total


@gin.configurable
def train_anakin(
    learner=gin.REQUIRED,
    model_dir: str = gin.REQUIRED,
    env: Optional[FunctionalEnv] = None,
    env_family: str = "pose",
    num_envs: int = 256,
    rollout_length: int = 4,
    train_batches_per_iter: int = 4,
    batch_size: int = 256,
    replay_capacity: int = 16384,
    max_train_steps: int = 1000,
    log_every_steps: int = 100,
    save_checkpoints_steps: int = 500,
    max_checkpoints_to_keep: int = 5,
    epsilon: float = 0.1,
    cem_population: Optional[int] = None,
    cem_iterations: Optional[int] = None,
    num_devices: Optional[int] = None,
    pod_program: str = "pmap",
    sharding_rules: Optional[str] = None,
    shard_weight_update: bool = False,
    update_shard_min_size: int = 2 ** 10,
    hooks: Iterable = (),
    seed: int = 0,
):
  """QT-Opt online training with fully-on-device collection.

  One device iteration (traced ONCE — the jit-once pin in
  tests/test_envs.py):

    1. roll ``rollout_length`` steps of ``num_envs`` auto-resetting
       envs with the ε-greedy CEM policy over the CURRENT params,
    2. write the [T·N] wire batch into a device-resident replay ring
       (part of the donated carry; capacity rounds up to a multiple of
       the segment so inserts are one contiguous dynamic slice),
    3. run ``train_batches_per_iter`` Bellman grad steps on uniform
       samples from the filled prefix.

  ``num_devices`` selects the program topology:

    * ``None`` (default) — the single-device jitted program (PR-9
      semantics, unchanged and bitwise-preserved).
    * ``0`` / ``D`` — POD MODE: the ENTIRE iteration is one SPMD
      program over all / the first ``D`` local devices
      (Podracer's full Anakin diagram, PAPERS.md). Each device runs
      ``num_envs / D`` envs feeding its OWN replay-ring shard (a
      ``[D, ...]`` leaf of the donated carry) and samples its OWN
      ``batch_size``-row Bellman batch (global batch ``D·batch_size``)
      — gradients are `lax.pmean`'d over the axis before the
      replicated Adam+Polyak update, so acting params stay EXACTLY
      the training params on every device and ``param_refresh_lag``
      remains 0 by construction at any device count. Per-device PRNG
      folds by absolute step then device index (``D=1`` reduces to
      the single-device key stream exactly). Hooks observe device-0
      metrics (pmean'd where they are means, so they read as global);
      each log boundary asserts a cross-device param-checksum
      agreement. Checkpoints save the device-0 replica — resume
      restores the learner exactly and re-replicates, and a pod
      checkpoint resumes on any device count (including ``None``).

  ``pod_program`` selects the pod-mode SPMD substrate (docs/
  SHARDING.md "The shard_map pod program"):

    * ``"pmap"`` (default) — the PR-10 program: one pmap'd replica per
      device, gradients pmean'd over the hard device axis.
    * ``"shard_map"`` — ONE jitted program over a named `pod` mesh
      axis: env shards, per-device replay rings, and sampled Bellman
      batches ride ``PartitionSpec("pod")`` through a `shard_map`
      collect stage, while the K Bellman train steps run as plain
      GSPMD jit on the pod-sharded global batch (gradient all-reduce
      inserted by the compiler). At ``num_devices=1`` the program is
      bitwise-pinned against the pmap program (tests/test_envs.py,
      the PR-10 FMA-less subprocess methodology). Because training is
      jit+mesh, ``shard_weight_update`` COMPOSES with the pod axis
      here — the composition pmap could never express.

  ``sharding_rules`` optionally names a `parallel.FAMILY_RULES` table
  (e.g. ``"qtopt"``); the shard_map program derives the param
  placement through that table on the pod mesh (resolving to
  replicated on a pod-only mesh — anything else raises, since the
  collect stage broadcasts params).

  ``shard_weight_update=True`` composes the PR-6 ZeRO-style update
  sharding where a mesh exists for the GSPMD constraint to act on: in
  the single-program path the optimizer is wrapped with
  `optimizers.shard_weight_update` over `parallel.mesh.create_mesh()`
  (moments live sharded across steps; a 1-device mesh is the pinned
  bitwise no-op). In the shard_map pod program the wrap rides the POD
  mesh axis (``axis="pod"``): gradients reduce-scatter over the pod,
  each device updates 1/D of each weight's moments, and one
  all-gather republishes params — optimizer state genuinely sharded
  across the pod (spec-pinned by tests). Only the legacy pmap program
  still warn-ignores the flag (each pmap replica is a single-device
  program with no mesh); use ``pod_program="shard_map"`` there.

  The iteration quantum is `train_qtopt`'s ``steps_per_dispatch``:
  every cadence must be a multiple of ``train_batches_per_iter``, and
  per-step PRNG folds by absolute step. Collection state (env states,
  rings) is ephemeral — a resume restarts collection but restores the
  learner exactly.

  Because acting params == training params inside one program,
  ``param_refresh_lag`` is 0 by construction (logged as such, so the
  fleet's lag dashboards stay comparable); replay staleness is bounded
  by ``capacity / (num_envs · rollout_length)`` iterations.
  """
  from tensor2robot_tpu import train_loop
  from tensor2robot_tpu.specs import TensorSpecStruct
  from tensor2robot_tpu.utils import checkpoints as ckpt_lib
  from tensor2robot_tpu.utils import profiling

  if env is None:
    env = _build_env(env_family, learner.model)

  if pod_program not in ("pmap", "shard_map"):
    raise ValueError(f"pod_program={pod_program!r} not in "
                     "('pmap', 'shard_map')")
  spmd = num_devices is not None
  use_shard_map = spmd and pod_program == "shard_map"
  if spmd:
    local = jax.local_devices()
    d = len(local) if num_devices == 0 else int(num_devices)
    if not 1 <= d <= len(local):
      raise ValueError(
          f"num_devices={num_devices} asks for {d} devices; "
          f"{len(local)} local devices are visible")
    devices = local[:d]
    if num_envs % d:
      raise ValueError(
          f"num_envs={num_envs} must divide across {d} devices")
  else:
    d = 1
    devices = None
  per_env = num_envs // d
  rows = num_envs * rollout_length      # total transitions / iteration
  rows_d = per_env * rollout_length     # per-device ring segment
  capacity = max(int(replay_capacity) // d, batch_size, rows_d)
  capacity = ((capacity + rows_d - 1) // rows_d) * rows_d
  _check_wire_spec(learner)
  spec = learner.transition_specification().to_flat_dict()

  # The anakin trainer's records carry its own envelope role without
  # touching the process-global tracer identity.
  loop = train_loop.TrainLoop(
      model_dir, hooks, dispatch_span="anakin.dispatch",
      steps_per_dispatch=train_batches_per_iter,
      max_train_steps=max_train_steps,
      log_every_steps=log_every_steps,
      save_checkpoints_steps=save_checkpoints_steps,
      max_checkpoints_to_keep=max_checkpoints_to_keep, role="anakin")
  k = loop.k

  from tensor2robot_tpu.parallel import mesh as mesh_lib

  mesh = None
  pod_mesh = None
  if use_shard_map:
    # The named pod mesh the shard_map program (and the ZeRO update)
    # rides. Axis name POD_AXIS — PartitionSpec(POD_AXIS) IS the env-
    # shard/ring/batch layout.
    pod_mesh = mesh_lib.create_mesh({POD_AXIS: d}, devices=devices)
  # The keyed wrap is RE-INSTALLED on every invocation — identity when
  # the flag is off or warn-ignored — so a previous run's mesh-pinned
  # ZeRO wrapper on this (possibly reused) learner can never leak into
  # a run that didn't ask for it.
  swu_wrapper = lambda tx: tx  # noqa: E731
  if shard_weight_update:
    from tensor2robot_tpu.models import optimizers as opt_lib
    if use_shard_map:
      # The composition the shard_map port exists for: training is
      # jit+mesh, so the ZeRO constraint acts on the POD axis —
      # reduce-scatter'd grads, 1/D of each weight's moments per
      # device, one all-gather republishing params. No warn-ignore.
      swu_wrapper = lambda tx: opt_lib.shard_weight_update(  # noqa: E731
          tx, pod_mesh, min_size_to_shard=update_shard_min_size,
          axis=POD_AXIS)
    elif spmd:
      # Each pmap replica is a single-device program: the GSPMD
      # sharding constraint `optimizers.shard_weight_update` rides on
      # needs a jit+mesh program to act on. The shard_map pod program
      # composes the two; pmap keeps the pmean'd replicated update.
      log.warning(
          "shard_weight_update=True is ignored by the pmap pod "
          "program (num_devices=%s): pmap replicas are single-device "
          "programs. Use pod_program='shard_map' to shard the update "
          "across the pod axis.", num_devices)
    else:
      mesh = mesh_lib.create_mesh()
      swu_wrapper = lambda tx: opt_lib.shard_weight_update(  # noqa: E731
          tx, mesh, min_size_to_shard=update_shard_min_size)
  # Wrap BEFORE the state exists so tx is final when the step traces
  # (the train_qtopt wiring).
  learner.model.wrap_optimizer(swu_wrapper, key="shard_weight_update")

  rng = jax.random.PRNGKey(seed)
  state = learner.create_state(rng, batch_size=2)
  # Live MFU attribution, device-count aware: one optimizer step
  # consumes `batch_size` rows PER DEVICE (global batch d·B), so the
  # global-step denominator is the per-device analytic count × d and
  # the peak scales by d — perf.mfu stays the per-chip
  # fraction-of-peak of the Bellman model (collection flops ride the
  # same program but are not model flops; docs/PERF.md).
  per_device_flops = profiling.qtopt_step_flops(
      learner, batch_size, params=state.train_state.params)
  resume_step = ckpt_lib.latest_step(model_dir)
  if resume_step is not None:
    log.info("Resuming anakin QT-Opt from step %d", resume_step)
    state = ckpt_lib.restore_state(model_dir, like=state,
                                   step=resume_step)
  from tensor2robot_tpu.parallel import sharding as sharding_lib

  state_shardings = None
  if mesh is not None:
    # Moments must STAY sharded across steps: place the carried state
    # with the update sharding so the jitted iteration round-trips it.
    state = jax.device_put(
        state, sharding_lib.train_state_update_sharding(
            mesh, state, min_size_to_shard=update_shard_min_size))
  if use_shard_map:
    from jax.sharding import NamedSharding, PartitionSpec
    if sharding_rules is not None:
      # The rules seam: param placement derives from the family table
      # on the pod mesh. A pod-only mesh has no fsdp/model axes, so
      # every placement resolves to replicated — which the collect
      # stage (params broadcast into shard_map) REQUIRES; a mesh/table
      # combination that shards params fails loudly here.
      from tensor2robot_tpu.parallel import rules as rules_lib
      param_specs = rules_lib.match_partition_rules(
          rules_lib.family_rules(sharding_rules),
          state.train_state.params, pod_mesh)
      bad = [rules_lib.tree_path_str(path)
             for path, spec in
             jax.tree_util.tree_leaves_with_path(
                 param_specs,
                 is_leaf=lambda x: isinstance(x, PartitionSpec))
             if spec != PartitionSpec()]
      if bad:
        raise ValueError(
            "the shard_map pod program broadcasts params into the "
            f"collect stage; rules table {sharding_rules!r} shards "
            f"{bad[:3]} on the pod mesh")
    if shard_weight_update:
      # ZeRO over the pod axis: moments sharded P("pod"), everything
      # else (params, targets, batch stats, step) replicated.
      state_shardings = sharding_lib.train_state_update_sharding(
          pod_mesh, state, min_size_to_shard=update_shard_min_size,
          axis=POD_AXIS)
    else:
      repl = NamedSharding(pod_mesh, PartitionSpec())
      state_shardings = jax.tree_util.tree_map(lambda _: repl, state)
    state = jax.device_put(state, state_shardings)
  step = int(np.asarray(jax.device_get(state.step)))

  init_fn, collect_fn = make_collect_fn(
      learner, env, per_env, rollout_length, epsilon=epsilon,
      cem_population=cem_population, cem_iterations=cem_iterations)
  init_key = jax.random.PRNGKey(seed + 2)
  if use_shard_map:
    from jax.sharding import PartitionSpec as P
    # Same per-device key schedule as the pmap program (D=1 uses the
    # key itself), but the reset runs under shard_map: each mesh shard
    # resets its own per_env envs and the results assemble into
    # GLOBAL [num_envs] leaves sharded P("pod") — the layout the
    # whole program keeps them in.
    init_keys = (init_key[None] if d == 1 else
                 jnp.stack([jax.random.fold_in(init_key, i)
                            for i in range(d)]))
    sm_init = mesh_lib.shard_map_compat(
        lambda ks: init_fn(ks[0]), pod_mesh,
        in_specs=P(POD_AXIS), out_specs=P(POD_AXIS))
    env_states = jax.jit(sm_init)(init_keys)
  elif spmd:
    # Device i resets its own env shard from fold_in(key, i); D=1
    # uses the key itself so the shard equals the single-device batch.
    init_keys = (init_key[None] if d == 1 else
                 jnp.stack([jax.random.fold_in(init_key, i)
                            for i in range(d)]))
    env_states = jax.pmap(init_fn, devices=devices)(init_keys)
  else:
    env_states = jax.jit(init_fn)(init_key)

  if getattr(learner, "needs_calibration", False):
    # int8 CEM tower: activation scales are trace-time constants.
    # Calibrate on REAL rendered frames — the batched envs' first
    # observations (device-0 shard in pod mode) — before anything
    # traces the quantized tower.
    sample = min(per_env, 64)
    # Pod layouts: pmap carries a leading device dim (device-0 shard
    # at [0, :sample]); shard_map keeps GLOBAL [num_envs] leaves, so
    # the first rows ARE device-0's shard.
    obs0 = jax.jit(jax.vmap(env.observe))(
        jax.tree_util.tree_map(
            (lambda x: x[0, :sample]) if (spmd and not use_shard_map)
            else (lambda x: x[:sample]), env_states))
    learner.calibrate(state, {
        "image": obs0["image"],
        "action": jax.random.uniform(
            jax.random.PRNGKey(seed + 3),
            (obs0["image"].shape[0], learner.model.action_dim),
            minval=-1.0, maxval=1.0),
    })

  if use_shard_map:
    # GLOBAL ring: [D·capacity] rows sharded P("pod") — device i owns
    # rows [i·capacity, (i+1)·capacity), its per-device ring shard.
    # size/ptr are per-device-identical, so they live as replicated
    # scalars instead of pmap's [D] per-device copies.
    from jax.sharding import NamedSharding, PartitionSpec as P
    pod_sharding = NamedSharding(pod_mesh, P(POD_AXIS))
    repl_sharding = NamedSharding(pod_mesh, P())
    replay = {
        key: jax.device_put(
            jnp.zeros((d * capacity,) + tuple(sp.shape),
                      dtype=sp.dtype), pod_sharding)
        for key, sp in spec.items()}
    size0 = jax.device_put(jnp.zeros((), jnp.int32), repl_sharding)
    ptr0 = jax.device_put(jnp.zeros((), jnp.int32), repl_sharding)
    env_states = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, pod_sharding), env_states)
  else:
    lead = (d,) if spmd else ()
    replay = {
        key: jnp.zeros(lead + (capacity,) + tuple(sp.shape),
                       dtype=sp.dtype)
        for key, sp in spec.items()}
    size0 = jnp.zeros(lead, jnp.int32)
    ptr0 = jnp.zeros(lead, jnp.int32)
  step_rng = jax.random.PRNGKey(seed + 1)
  axis = POD_AXIS if spmd else None

  def iteration(carry, key):
    qstate, states, ring, size, ptr = carry
    if axis is not None and d > 1:
      # Per-device key stream: the host folds by absolute step, each
      # device folds its axis index on top. d is trace-time static,
      # so D=1 keeps the single-device stream bit-exactly.
      key = jax.random.fold_in(key, jax.lax.axis_index(axis))
    key_collect, _ = jax.random.split(key)
    states, batch = collect_fn(qstate, states, key_collect)
    ring = {
        name: jax.lax.dynamic_update_slice(
            ring[name], batch[name],
            (ptr,) + (0,) * (ring[name].ndim - 1))
        for name in ring}
    size = jnp.minimum(size + rows_d, capacity)
    ptr = (ptr + rows_d) % capacity

    def train_body(st, _):
      base = jax.random.fold_in(step_rng, st.step)
      key_sample, key_net = jax.random.split(base)
      if axis is not None and d > 1:
        di = jax.lax.axis_index(axis)
        key_sample = jax.random.fold_in(key_sample, di)
        key_net = jax.random.fold_in(key_net, di)
      idx = jax.random.randint(key_sample, (batch_size,), 0, size)
      minibatch = TensorSpecStruct.from_flat_dict(
          {name: ring[name][idx] for name in ring})
      return learner.train_step(st, minibatch, key_net,
                                axis_name=axis)

    qstate, metrics_seq = jax.lax.scan(
        train_body, qstate, jnp.arange(k))
    # Per-step hooks observe each dispatch's LAST metrics — the
    # train_qtopt K>1 convention.
    metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics_seq)
    metrics["collect_reward_mean"] = jnp.mean(batch["reward"])
    metrics["replay_fill"] = size.astype(jnp.float32) / capacity
    if axis is not None:
      metrics["collect_reward_mean"] = jax.lax.pmean(
          metrics["collect_reward_mean"], axis)
      metrics["param_checksum"] = _param_checksum(qstate)
    return (qstate, states, ring, size, ptr), metrics

  def make_shard_map_iteration():
    """The jit+shard_map pod iteration (docs/SHARDING.md).

    One jitted program over the named pod mesh, two regimes inside:

      * COLLECT under `shard_map` — each mesh shard rolls its env
        shard, inserts into its ring shard, and samples its K
        per-device Bellman batches; env states, rings, and batches
        ride ``P("pod")``.
      * Each Bellman step = GRADS under `shard_map` (per-device
        forward/backward on the device's own batch, one `lax.pmean`
        — the pmap program's exact semantics, and the fast path on
        every backend) + UPDATE as plain GSPMD jit
        (`learner.apply_gradients`: elementwise weight-sized math,
        which under ``shard_weight_update`` the ZeRO constraints
        shard across the pod — each device updates 1/D of every
        weight's moments, one all-gather republishes params). This
        is the "Automatic Cross-Replica Sharding of Weight Update"
        split verbatim: everything data-parallel except the update.

    PRNG schedule is the pmap program's exactly (device folds apply
    only at d>1), so ``num_devices=1`` reproduces it bitwise — the
    pinned equivalence in tests/test_envs.py.
    """
    from jax.sharding import PartitionSpec as P

    def sm_collect(acting_ts, step0, states, ring, size_new, ptr_in,
                   key):
      if d > 1:
        key = jax.random.fold_in(key, jax.lax.axis_index(POD_AXIS))
      key_collect, _ = jax.random.split(key)
      states, batch = collect_fn(acting_ts, states, key_collect)
      ring = {
          name: jax.lax.dynamic_update_slice(
              ring[name], batch[name],
              (ptr_in,) + (0,) * (ring[name].ndim - 1))
          for name in ring}
      minibatches = []
      for j in range(k):
        base = jax.random.fold_in(step_rng, step0 + j)
        key_sample, _ = jax.random.split(base)
        if d > 1:
          key_sample = jax.random.fold_in(
              key_sample, jax.lax.axis_index(POD_AXIS))
        idx = jax.random.randint(key_sample, (batch_size,), 0,
                                 size_new)
        minibatches.append({name: ring[name][idx] for name in ring})
      stacked = {
          name: jnp.stack([mb[name] for mb in minibatches])
          for name in ring}
      reward = jnp.mean(batch["reward"])
      if d > 1:
        reward = jax.lax.pmean(reward, POD_AXIS)
      return states, ring, stacked, reward

    sm_collect_sharded = mesh_lib.shard_map_compat(
        sm_collect, pod_mesh,
        in_specs=(P(), P(), P(POD_AXIS), P(POD_AXIS), P(), P(), P()),
        out_specs=(P(POD_AXIS), P(POD_AXIS), P(None, POD_AXIS), P()))

    def sm_grads(acting, mb, key_net):
      # Per-device backward, the pmap train_body's exact schedule:
      # d>1 folds the device index into the net key (per-device
      # dropout/CEM streams), d=1 does not; gradients/stats/metrics
      # come out pmean'd (replicated).
      if d > 1:
        key_net = jax.random.fold_in(key_net,
                                     jax.lax.axis_index(POD_AXIS))
      minibatch = TensorSpecStruct.from_flat_dict(mb)
      return learner.train_grads(acting, minibatch, key_net,
                                 axis_name=POD_AXIS)

    sm_grads_sharded = mesh_lib.shard_map_compat(
        sm_grads, pod_mesh,
        in_specs=(P(), P(POD_AXIS), P()),
        out_specs=(P(), P(), P()))

    def sm_iteration(carry, key):
      qstate, states, ring, size, ptr = carry
      size_new = jnp.minimum(size + rows_d, capacity)
      # Acting reads only params/batch_stats; the opt_state (sharded
      # under ZeRO) must not cross the shard_map boundary replicated.
      acting_ts = qstate.train_state.replace(opt_state=())
      step0 = qstate.train_state.step
      states, ring, minibatches, collect_reward = sm_collect_sharded(
          acting_ts, step0, states, ring, size_new, ptr, key)
      new_ptr = (ptr + rows_d) % capacity

      def train_body(st, mb):
        base = jax.random.fold_in(step_rng, st.train_state.step)
        key_net = jax.random.split(base)[1]
        acting = st.replace(
            train_state=st.train_state.replace(opt_state=()))
        grads, new_stats, metrics = sm_grads_sharded(acting, mb,
                                                     key_net)
        # The GSPMD half: elementwise update (ZeRO-sharded when
        # shard_weight_update wrapped the tx) + Polyak target sync.
        return learner.apply_gradients(st, grads, new_stats), metrics

      qstate, metrics_seq = jax.lax.scan(train_body, qstate,
                                         minibatches)
      metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics_seq)
      metrics["collect_reward_mean"] = collect_reward
      metrics["replay_fill"] = size_new.astype(jnp.float32) / capacity
      if shard_weight_update:
        # Moments STAY pod-sharded across iterations: constrain the
        # carried-out state so the boundary never all-gathers them.
        qstate = jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, qstate, state_shardings)
      return (qstate, states, ring, size_new, new_ptr), metrics

    return sm_iteration

  if use_shard_map:
    anakin_step = jax.jit(make_shard_map_iteration(),
                          donate_argnums=(0,))
  elif spmd:
    anakin_step = jax.pmap(iteration, axis_name=POD_AXIS,
                           devices=devices, in_axes=(0, None),
                           donate_argnums=(0,))
    # One replica per pod device along a new leading axis, like the
    # ring and the env states above; pmap places slice i on device i.
    state = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (d,) + x.shape), state)
  else:
    anakin_step = jax.jit(iteration, donate_argnums=(0,))

  def device0(tree):
    """The device-0 replica view (identity in single-program and
    shard_map modes, whose arrays are global)."""
    if not spmd or use_shard_map:
      return tree
    return jax.tree_util.tree_map(lambda x: x[0], tree)

  def own_scalars(scalars, steps, dt, stall_secs):
    del stall_secs  # the rates are the interval's, saves and all
    if spmd and not use_shard_map:
      # shard_map metrics are already global scalars, and its params
      # are ONE logical replicated array — there are no per-replica
      # copies to checksum-compare.
      checks = np.asarray(scalars.pop("param_checksum"))
      if np.unique(checks).size != 1:
        raise RuntimeError(
            "pod replicas diverged: per-device param checksums "
            f"{checks.tolist()} at step {loop.step} — a gradient or "
            "state update escaped the pmean")
      for name in scalars:
        scalars[name] = scalars[name][0]
    scalars["grad_steps_per_sec"] = steps / max(dt, 1e-9)
    scalars["env_steps_per_sec"] = (steps // k * rows) / max(dt, 1e-9)
    if spmd:
      scalars["devices"] = d
      scalars["global_batch_size"] = d * batch_size
      # Bellman THROUGHPUT: each optimizer step consumed one
      # batch_size-row batch per device.
      scalars["bellman_batches_per_sec"] = (
          scalars["grad_steps_per_sec"] * d)
    # Zero BY CONSTRUCTION (acting params == training params in one
    # program) — logged so fleet-mode dashboards compare.
    scalars["param_refresh_lag_steps"] = 0.0
    return "grad_steps_per_sec"

  carry = (state, env_states, replay, size0, ptr0)
  loop.begin(
      learner.model, step,
      flops_per_step=per_device_flops * d if per_device_flops else None,
      devices=d,
      state=lambda: device0(carry[0]),
      save_payload=train_loop.host_payload,
      hook_state=lambda st: st.train_state,
      hook_metrics=device0,
      own_scalars=own_scalars)
  iter_key = jax.random.PRNGKey(seed + 4)
  with loop:
    for _ in loop.dispatches():
      # One collect-and-learn device program: rollout segment + ring
      # insert + K Bellman steps.
      with loop.dispatch():
        carry, metrics = anakin_step(
            carry, jax.random.fold_in(iter_key, loop.step))
      loop.after_dispatch(metrics)
  return device0(carry[0])


@gin.configurable
class JaxEnvBandit:
  """Functional env → the host batched-bandit interface.

  `GraspActor` (and the success-protocol evals) speak
  ``reset_batch / grade / action_dim / sample_transitions`` —
  `ToyGraspEnv`'s vectorized single-step contract. This adapter lets
  any functional env serve as that scenario source: reset+render run
  as one jitted program per batch size, ``grade`` is the env's own
  reward function (vmapped, so host and device rewards can never
  drift). Intended for in-process actors and evals; fleet actor
  processes stay jax-free and keep using the MuJoCo adapter.
  """

  def __init__(self, env: Optional[FunctionalEnv] = None,
               seed: int = 0, **env_kwargs):
    self._env = env if env is not None else ProcGenGraspEnv(
        **env_kwargs)
    self._key = jax.random.PRNGKey(seed)
    self._reset_cache: Dict[int, Callable] = {}
    self._grade = jax.jit(jax.vmap(self._env.grasp_reward))
    self._rng = np.random.default_rng(seed)
    # Scenario attribution for robustness summaries: the bucket ids of
    # the most recent reset_batch (procgen; None for bucketless envs).
    self.last_buckets: Optional[np.ndarray] = None

  @property
  def env(self) -> FunctionalEnv:
    return self._env

  @property
  def action_dim(self) -> int:
    return self._env.action_dim

  def _reset_fn(self, n: int):
    fn = self._reset_cache.get(n)
    if fn is None:
      env = self._env

      def reset_and_observe(key):
        states = jax.vmap(env.reset)(jax.random.split(key, n))
        obs = jax.vmap(env.observe)(states)
        poses = states.pose
        bucket = (jax.vmap(env.scenario_bucket)(states)
                  if hasattr(env, "scenario_bucket") else None)
        return obs, poses, bucket

      fn = jax.jit(reset_and_observe)
      self._reset_cache[n] = fn
    return fn

  def reset_batch(self, n: int
                  ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """N fresh scenarios: ({image: [N, S, S, 3]}, target poses)."""
    self._key, sub = jax.random.split(self._key)
    obs, poses, bucket = self._reset_fn(n)(sub)
    self.last_buckets = (None if bucket is None
                         else np.asarray(jax.device_get(bucket)))
    return ({k: np.asarray(jax.device_get(v))
             for k, v in obs.items()},
            np.asarray(jax.device_get(poses)))

  def grade(self, actions: np.ndarray,
            positions: np.ndarray) -> np.ndarray:
    return np.asarray(jax.device_get(self._grade(
        jnp.asarray(actions, jnp.float32),
        jnp.asarray(positions, jnp.float32))))

  def sample_transitions(self, n: int) -> Dict[str, np.ndarray]:
    """N random-policy transitions in the learner's replay layout."""
    observations, positions = self.reset_batch(n)
    actions = self._rng.uniform(
        -1, 1, (n, self._env.action_dim)).astype(np.float32)
    reward = self.grade(actions, positions)
    return {
        "image": observations["image"],
        "action": actions,
        "reward": reward[:, None].astype(np.float32),
        "done": np.ones((n, 1), np.float32),
        "next_image": observations["image"],
    }


@gin.configurable
def evaluate_scenarios(
    learner,
    state,
    env: Optional[FunctionalEnv] = None,
    num_scenarios: int = 512,
    seed: int = 0,
    cem_population: Optional[int] = None,
    cem_iterations: Optional[int] = None,
) -> Dict[str, object]:
  """Seeded procedural robustness sweep: success per scenario bucket.

  One device program resets ``num_scenarios`` key-sampled scenarios,
  selects every action with the CEM policy, and grades them; results
  group by ``scenario_bucket`` (distractor count for procgen). The
  same seed reproduces the same scenarios AND the same action stream —
  ``action_digest`` (SHA-256 over the action bytes) is the
  reproducibility handle `run_success_protocol seedcheck` pins.
  """
  import hashlib

  from tensor2robot_tpu.specs import TensorSpecStruct

  if env is None:
    env = ProcGenGraspEnv(image_size=learner.model.image_size,
                          action_dim=learner.model.action_dim)
  policy = learner.build_policy(cem_population=cem_population,
                                cem_iterations=cem_iterations)

  def sweep(policy_state, key):
    key_env, key_cem = jax.random.split(key)
    states = jax.vmap(env.reset)(
        jax.random.split(key_env, num_scenarios))
    obs = jax.vmap(env.observe)(states)
    actions = policy(policy_state,
                     TensorSpecStruct.from_flat_dict(obs), key_cem)
    rewards = jax.vmap(env.grasp_reward)(actions, states.pose)
    bucket = (jax.vmap(env.scenario_bucket)(states)
              if hasattr(env, "scenario_bucket")
              else jnp.zeros((num_scenarios,), jnp.int32))
    return actions, rewards, bucket, states.pose

  actions, rewards, bucket, poses = jax.jit(sweep)(
      state, jax.random.PRNGKey(seed))
  actions = np.asarray(jax.device_get(actions))
  rewards = np.asarray(jax.device_get(rewards))
  bucket = np.asarray(jax.device_get(bucket))
  poses = np.asarray(jax.device_get(poses))

  num_buckets = int(getattr(env, "num_buckets", 1))
  per_bucket = {}
  for b in range(num_buckets):
    mask = bucket == b
    per_bucket[str(b)] = {
        "count": int(mask.sum()),
        "success_rate": (float(rewards[mask].mean())
                         if mask.any() else None),
    }
  random_actions = np.random.default_rng(seed + 1).uniform(
      -1, 1, actions.shape).astype(np.float32)
  random_rewards = np.asarray(jax.device_get(jax.vmap(
      env.grasp_reward)(jnp.asarray(random_actions),
                        jnp.asarray(poses))))
  return {
      "success_rate": float(rewards.mean()),
      "random_baseline_success_rate": float(random_rewards.mean()),
      "per_bucket": per_bucket,
      "num_scenarios": int(num_scenarios),
      "action_digest": hashlib.sha256(
          np.ascontiguousarray(actions).tobytes()).hexdigest(),
      "scenario_digest": hashlib.sha256(
          np.ascontiguousarray(poses).tobytes()).hexdigest(),
  }
