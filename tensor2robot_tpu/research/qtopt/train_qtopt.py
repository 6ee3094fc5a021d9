"""QT-Opt training orchestrator: replay → sharded infeed → fused step.

The in-repo replacement for the reference's external distributed QT-Opt
system, arranged for the north-star throughput target: the host thread
only samples/collates; CEM targets + critic update are one jitted
program; checkpoints are async orbax; the robot handoff is the same
async SavedModel export the supervised trainer uses.
"""

from __future__ import annotations

import json
import logging
import os
from typing import Iterable, Optional

import jax
import numpy as np

from tensor2robot_tpu import config as gin
from tensor2robot_tpu import train_loop
from tensor2robot_tpu.data import prefetch as prefetch_lib
from tensor2robot_tpu.hooks import Hook
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.parallel import sharding as sharding_lib
from tensor2robot_tpu.research.qtopt.qtopt_learner import (
    QTOptLearner,
    QTOptState,
)
from tensor2robot_tpu.research.qtopt.replay_buffer import ReplayBuffer
from tensor2robot_tpu.specs import make_random_tensors
from tensor2robot_tpu.startup import orchestrator
from tensor2robot_tpu.utils import checkpoints as ckpt_lib
from tensor2robot_tpu.utils import profiling

log = logging.getLogger(__name__)

ACT_SCALES_FILE = "int8_act_scales.json"


def _calibrate_once_per_run(learner, state, batch, model_dir: str,
                            write: bool) -> None:
  """Calibrates the int8 activation scales on a run's FIRST start and
  keeps them beside its checkpoints; every resume adopts them.

  The scales are trace-time constants of the train step: a resume that
  recalibrated on its restored params would train a different program
  than the run it continues — and compile it again, where every other
  program of a restart comes out of the persistent cache.
  """
  path = os.path.join(model_dir, ACT_SCALES_FILE)
  if os.path.exists(path):
    with open(path) as f:
      learner.set_activation_scales(json.load(f))
    return
  scales = learner.calibrate(state, batch)
  if write:
    with open(path + ".tmp", "w") as f:
      json.dump(scales, f)
    os.replace(path + ".tmp", path)


@gin.configurable
def train_qtopt(
    learner: QTOptLearner = gin.REQUIRED,
    model_dir: str = gin.REQUIRED,
    replay_buffer: Optional[ReplayBuffer] = None,
    max_train_steps: int = 1000,
    batch_size: int = 256,
    min_replay_size: Optional[int] = None,
    save_checkpoints_steps: int = 500,
    max_checkpoints_to_keep: int = 5,
    log_every_steps: int = 100,
    mesh: Optional[jax.sharding.Mesh] = None,
    hooks: Iterable[Hook] = (),
    seed: int = 0,
    prefill_random: bool = False,
    steps_per_dispatch: int = 1,
    prefetch_buffer_size: Optional[int] = None,
    shard_weight_update: bool = False,
) -> QTOptState:
  """Runs the QT-Opt learner loop; resumes from model_dir checkpoints.

  `replay_buffer` must be fed by actors (or pre-filled from logged
  episodes); `prefill_random=True` fills it with spec-random
  transitions instead (benchmarks / smoke tests).

  `steps_per_dispatch` (K) is the reference TPUEstimator's
  `iterations_per_loop` (SURVEY.md §4.1: "the hot loop"): K train
  steps run as ONE device program per host call — a `lax.scan` over K
  host-stacked replay batches — so host/dispatch latency is paid once
  per K steps instead of every step. The reference's quantization
  semantics apply: every cadence (log, checkpoint, max steps) must be
  a multiple of K, per-step hooks observe only each dispatch's LAST
  metrics, and the per-step PRNG stream is identical to K=1 (folded
  by absolute step inside the scan).

  ONLINE-run caveat (K>1 sampling lead): replay batches for a whole
  K-step dispatch are sampled BEFORE the dispatch runs, and each
  prefetched dispatch adds another K steps of lead, so with actors
  feeding the buffer concurrently the last step of a dispatch can
  train on samples drawn up to ~(depth+1)·K steps of parameter
  updates ago. Two things bound this now: `prefetch_buffer_size`
  (None = auto via `prefetch_lib.prefetch_buffer_size`, gin-tunable:
  depth 1 when any hook drives online collection — the round-5
  finding — else the throughput-friendly 2; the same predicate keeps
  the loop from running a dispatch ahead of its hooks, log and save,
  which is one more dispatch of lead, and where the loop does run
  ahead that dispatch counts as one of the depth), and the replay data
  plane MEASURES it — when the buffer exposes `set_learner_step` /
  `metrics_scalars` (the `replay/` plane and its `ReplayBuffer`
  adapter do), every sampled batch's age-in-steps lands in a
  staleness histogram logged alongside the train metrics. The
  exact-K=1-equivalence claim (and its tests) remains scoped to
  static/offline buffers — logged episodes, prefill_random — where
  sample timing is irrelevant; online runs should treat K as a
  throughput/off-policy-staleness trade-off, now a measured one.

  `shard_weight_update=True` shards the optimizer step + moments over
  the mesh's data axis (reduce-scatter grads / all-gather params —
  `optimizers.shard_weight_update`, docs/PERF.md): each replica
  updates 1/N of every weight instead of all replicas repeating the
  full update. On a 1-device mesh it is a bitwise no-op (pinned);
  checkpoints are unaffected (save gathers to host either way).

  The loop itself (services, cadences, log record, teardown, a span for
  every stage around this trainer's `qtopt.dispatch`) is
  `train_loop.TrainLoop`; the feed thread's stages are spans as well.
  """
  if mesh is None:
    mesh = mesh_lib.create_mesh()
  loop = train_loop.TrainLoop(
      model_dir, hooks, dispatch_span="qtopt.dispatch",
      steps_per_dispatch=steps_per_dispatch,
      max_train_steps=max_train_steps,
      log_every_steps=log_every_steps,
      save_checkpoints_steps=save_checkpoints_steps,
      max_checkpoints_to_keep=max_checkpoints_to_keep)
  k = loop.k

  with orchestrator.Phase("init_state") as init_state:
    if replay_buffer is None:
      replay_buffer = ReplayBuffer(learner.transition_specification())
    if prefill_random:
      fill = make_random_tensors(
          learner.transition_specification(),
          batch_size=min(replay_buffer.capacity, 4 * batch_size),
          seed=seed)
      replay_buffer.add(fill)
    rng = jax.random.PRNGKey(seed)
    # Keyed re-wrap on EVERY invocation (identity when the flag is
    # off): a reused learner must not keep a previous run's mesh-pinned
    # ZeRO wrapper. Wrap BEFORE the state exists so tx is final when
    # the step traces; init stays untouched (shardings come from
    # placement).
    swu_wrapper = lambda tx: tx  # noqa: E731
    if shard_weight_update:
      from tensor2robot_tpu.models import optimizers as opt_lib
      swu_wrapper = lambda tx: opt_lib.shard_weight_update(tx, mesh)  # noqa: E731
    learner.model.wrap_optimizer(swu_wrapper, key="shard_weight_update")
    state = learner.create_state(rng, batch_size=2)
    repl = mesh_lib.replicated(mesh)
    data_sharding = mesh_lib.batch_sharding(mesh)
    # The carried-state sharding: fully replicated, or — under
    # shard_weight_update — optimizer moments sharded over the data
    # axis (they must STAY sharded across steps, so this pytree is used
    # for placement and both jit sharding sides).
    state_sharding = (
        sharding_lib.train_state_update_sharding(mesh, state)
        if shard_weight_update else repl)
    state = jax.device_put(state, state_sharding)
    init_state.args["bytes"] = train_loop.state_bytes(state)
  resume_step = ckpt_lib.latest_step(model_dir)
  if resume_step is not None:
    log.info("Resuming QT-Opt from step %d", resume_step)
    with orchestrator.Phase("restore", step=resume_step,
                            bytes=init_state.args["bytes"]):
      state = ckpt_lib.restore_state(model_dir, like=state,
                                     step=resume_step)

  def own_scalars(scalars, steps, dt, stall_secs):
    del stall_secs  # the rate is the interval's, saves and all
    scalars["grad_steps_per_sec"] = steps / max(dt, 1e-9)
    # Data-plane instrumentation rides the train log: fill, add/sample
    # rates, drops/evictions, staleness — next to the loop's own
    # throughput.
    replay_metrics = getattr(replay_buffer, "metrics_scalars", None)
    if replay_metrics is not None:
      scalars.update(replay_metrics())
    return "grad_steps_per_sec"

  # Hooks begin BEFORE the replay wait: an ActorStateRefreshHook whose
  # actors bootstrap an empty buffer must start collecting now, or
  # this wait would deadlock. Live MFU attribution: the one analytic
  # denominator of the repo (utils.profiling.analytic_flops — the
  # ISSUE-15 shared-path pin), scaled to the mesh (batch_size is
  # PER-PROCESS, so × process_count is the global batch; peak × devices
  # keeps perf.mfu the per-chip fraction).
  loop.begin(
      learner.model, int(np.asarray(jax.device_get(state.step))),
      flops_per_step=profiling.qtopt_step_flops(
          learner, batch_size * jax.process_count(),
          params=state.train_state.params),
      devices=mesh.size,
      state=lambda: state, save_payload=train_loop.host_payload,
      hook_state=lambda st: st.train_state,
      own_scalars=own_scalars,
      tag_step=getattr(replay_buffer, "set_learner_step", None))
  with orchestrator.Phase("wait_replay"):
    replay_buffer.wait_until_size(min_replay_size or batch_size)

  # int8 CEM tower: activation scales calibrate on a real held-out
  # replay batch BEFORE the step is traced (the scales are trace-time
  # constants; see QTOptLearner.calibrate / docs/PERF.md).
  if getattr(learner, "needs_calibration", False):
    with orchestrator.Phase("calibrate"):
      _calibrate_once_per_run(learner, state,
                              replay_buffer.sample(batch_size),
                              model_dir, write=loop.chief)

  if k == 1:
    train_step = jax.jit(
        learner.train_step,
        in_shardings=(state_sharding, data_sharding, repl),
        out_shardings=(state_sharding, repl),
        donate_argnums=(0,),
    )
    stream = replay_buffer.as_stream(batch_size)
    stream_sharding = data_sharding
  else:
    def k_steps(st, stacked, rng, step0):
      return prefetch_lib.scan_k_steps(
          learner.train_step, st, (stacked,), rng, step0)

    stacked_sharding = prefetch_lib.stacked_sharding(data_sharding)
    train_step = jax.jit(
        k_steps,
        in_shardings=(state_sharding, stacked_sharding, repl, repl),
        out_shardings=(state_sharding, repl),
        donate_argnums=(0,),
    )
    # A buffer that can gather a batch where it is told to puts each
    # straight into its slice of the dispatch (`RemoteReplay` cannot).
    stream = prefetch_lib.stack_batches(
        replay_buffer.as_stream(batch_size), k,
        lend=getattr(replay_buffer, "gather_next_into", None))
    stream_sharding = stacked_sharding

  # buffer_size is forwarded ONLY when the caller set it: a positional
  # (or keyword) arg would shadow a `prefetch_buffer_size.buffer_size`
  # gin binding — explicit caller args win over config in ginlite.
  depth = prefetch_lib.prefetch_buffer_size(
      online=loop.hook_list.drives_online_collection,
      **({} if prefetch_buffer_size is None
         else {"buffer_size": prefetch_buffer_size}))
  if loop.runs_ahead:
    # The dispatch the loop keeps in flight is one of those resident
    # ahead of compute, and takes its place among them: beside it a
    # queue of two held five dispatches on the device, 13.73 of the
    # v5e's 16.9 GB in qtopt_472 with the step program's 3 GB of
    # temporaries still to come (PERF.md §6, PR 31).
    depth = max(depth - 1, 1)
  with orchestrator.Phase("input", k=k):
    loop.attach_feed(prefetch_lib.ShardedPrefetcher(
        stream, stream_sharding, buffer_size=depth))
  step_rng = jax.random.PRNGKey(seed + 1)
  with loop:
    for transitions in loop.dispatches():
      with loop.dispatch():
        if k == 1:
          state, metrics = train_step(
              state, transitions,
              jax.random.fold_in(step_rng, loop.step))
        else:
          # Same per-step PRNG stream as K=1: the scan body folds
          # step_rng by ABSOLUTE step (step0 + i).
          state, metrics = train_step(state, transitions, step_rng,
                                      np.int32(loop.step))
      loop.after_dispatch(metrics)
  return state
