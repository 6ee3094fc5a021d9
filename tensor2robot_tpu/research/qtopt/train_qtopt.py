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
import time
from typing import Dict, Iterable, Optional

import jax
import numpy as np

from tensor2robot_tpu import config as gin
from tensor2robot_tpu import telemetry
from tensor2robot_tpu.data import prefetch as prefetch_lib
from tensor2robot_tpu.hooks import Hook, HookList
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.parallel import sharding as sharding_lib
from tensor2robot_tpu.research.qtopt.qtopt_learner import (
    QTOptLearner,
    QTOptState,
)
from tensor2robot_tpu.research.qtopt.replay_buffer import ReplayBuffer
from tensor2robot_tpu.specs import make_random_tensors
from tensor2robot_tpu.train_eval import MetricLogger
from tensor2robot_tpu.utils import checkpoints as ckpt_lib

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
  finding — else the throughput-friendly 2), and the replay data
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

  Every stage of the loop thread is a telemetry span
  (docs/OBSERVABILITY.md, "Standard spans": `loop.wait_feed`,
  `qtopt.dispatch`, `loop.after_step`, `loop.log` > `loop.log_sync`,
  `loop.save` > `loop.save_d2h` / `loop.save_write` /
  `loop.after_checkpoint`), as is every stage of the feed thread. A
  process that has not configured the tracer gets the role `trainer`
  in memory mode here: a bounded ring, nothing written, and a
  sentinel page's flight record holds the loop's last spans. A
  caller's configuration, `enabled=False` included, is left alone.
  """
  if mesh is None:
    mesh = mesh_lib.create_mesh()
  if telemetry.get_tracer().role is None:
    telemetry.configure("trainer")
  # Validate the dispatch quantization BEFORE any side effects
  # (hook begin() starts actor threads; a late ValueError would leak
  # them past their teardown owner, the loop's try/finally).
  k = prefetch_lib.validate_steps_per_dispatch(
      steps_per_dispatch,
      log_every_steps=log_every_steps,
      save_checkpoints_steps=save_checkpoints_steps,
      max_train_steps=max_train_steps)
  os.makedirs(model_dir, exist_ok=True)
  # Multi-process learner group (ISSUE 19): every rank runs the SAME
  # jitted program (one GSPMD computation over the shared mesh, each
  # rank feeding its local batch shard), but HOST-side effects —
  # metric logs, sentinel pages, replay step-tags — belong to the
  # chief alone. Rank > 0 would otherwise race the chief on the same
  # model_dir files. Checkpoint saves are the one exception: orbax
  # save/wait are COLLECTIVE (`sync_global_processes` barriers inside
  # the writer), so every rank must make the calls — orbax's
  # primary-host ownership still makes process 0 the only rank that
  # writes checkpoint data. Single-process runs are process 0, so this
  # is bitwise the existing path there.
  chief = jax.process_index() == 0
  metric_logger = MetricLogger(model_dir) if chief else None
  hook_list = HookList(list(hooks))
  # Places the persistent compile cache and taps its traffic into the
  # telemetry registry: a warm-path recompile lands in this loop's
  # log, not only under bench --coldstart.
  from tensor2robot_tpu.startup import compile_cache
  compile_cache.configure_compilation_cache()
  # The always-on perf plane (ISSUE 15): resource watermarks sampled
  # per process, sentinel rules evaluated at log cadence, and the live
  # MFU gauges published below (the PerfMeter built once the state
  # exists — the analytic denominator wants the param count).
  from tensor2robot_tpu.telemetry import perf as perf_lib
  from tensor2robot_tpu.telemetry import sentinel as sentinel_lib
  from tensor2robot_tpu.utils import profiling
  perf_lib.start_resource_sampler(
      sources=[profiling.device_memory_source()])
  watch_sentinel = (sentinel_lib.build_for_run(model_dir)
                    if chief else None)

  if replay_buffer is None:
    replay_buffer = ReplayBuffer(learner.transition_specification())
  if prefill_random:
    fill = make_random_tensors(
        learner.transition_specification(),
        batch_size=min(replay_buffer.capacity, 4 * batch_size),
        seed=seed)
    replay_buffer.add(fill)
  rng = jax.random.PRNGKey(seed)
  # Keyed re-wrap on EVERY invocation (identity when the flag is off):
  # a reused learner must not keep a previous run's mesh-pinned ZeRO
  # wrapper. Wrap BEFORE the state exists so tx is final when the step
  # traces; init stays untouched (shardings come from placement).
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
  resume_step = ckpt_lib.latest_step(model_dir)
  if resume_step is not None:
    log.info("Resuming QT-Opt from step %d", resume_step)
    state = ckpt_lib.restore_state(model_dir, like=state,
                                   step=resume_step)

  # Resume-alignment check BEFORE hooks begin (actor threads) and
  # before the prefetcher exists: raising later would leak both past
  # their teardown owner (the loop's try/finally).
  step = int(np.asarray(jax.device_get(state.step)))
  if k > 1 and step % k and step < max_train_steps:
    if metric_logger is not None:
      metric_logger.close()
    raise ValueError(
        f"Resumed at step {step}, not a multiple of "
        f"steps_per_dispatch={k}: the checkpoint/log boundaries "
        "would never align. Resume with K=1 (or a K dividing the "
        "resume step) first.")

  # Hooks begin BEFORE the replay wait: an ActorStateRefreshHook whose
  # actors bootstrap an empty buffer must start collecting now, or
  # this wait would deadlock.
  hook_list.begin(learner.model, model_dir)
  replay_buffer.wait_until_size(min_replay_size or batch_size)

  # int8 CEM tower: activation scales calibrate on a real held-out
  # replay batch BEFORE the step is traced (the scales are trace-time
  # constants; see QTOptLearner.calibrate / docs/PERF.md).
  if getattr(learner, "needs_calibration", False):
    _calibrate_once_per_run(learner, state,
                            replay_buffer.sample(batch_size), model_dir,
                            write=chief)

  writer = ckpt_lib.CheckpointWriter(
      model_dir, max_to_keep=max_checkpoints_to_keep)

  # Live MFU attribution: the SAME analytic denominator bench.py uses
  # (utils.profiling.analytic_flops — the ISSUE-15 shared-path pin),
  # scaled to the mesh (batch_size is PER-PROCESS, so × process_count
  # is the global batch; peak × devices keeps perf.mfu the per-chip
  # fraction).
  perf_meter = perf_lib.PerfMeter(
      flops_per_step=profiling.qtopt_step_flops(
          learner, batch_size * jax.process_count(),
          params=state.train_state.params),
      peak_flops=profiling.device_peak_flops(),
      devices=mesh.size)

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
      online=hook_list.drives_online_collection,
      **({} if prefetch_buffer_size is None
         else {"buffer_size": prefetch_buffer_size}))
  prefetcher = prefetch_lib.ShardedPrefetcher(
      stream, stream_sharding, buffer_size=depth)
  # The data plane tags rows with the learner step at add time; seed
  # the tag before actors race the first dispatch. Chief-only: on the
  # sharded plane the tag is an RPC fan-out to every shard, and N
  # ranks tagging the same step would N-plicate it.
  tag_step = (getattr(replay_buffer, "set_learner_step", None)
              if chief else None)
  if tag_step is not None:
    tag_step(step)
  step_rng = jax.random.PRNGKey(seed + 1)
  t_last = time.time()
  steps_since_log = 0
  last_saved = resume_step
  # input_wait_fraction: the measured input-boundness of the
  # replay→device seam (shared TimedIterator — wall blocked in the
  # prefetcher's __next__ per log interval), logged beside the
  # staleness metrics.
  prefetch_iter = prefetch_lib.TimedIterator(prefetcher)

  def save(step: int) -> None:
    # EVERY rank saves (orbax's save barrier is collective; a
    # chief-only call would wedge the chief in
    # `sync_global_processes` while the peers train on) — orbax's
    # primary-host rule keeps process 0 the only data writer.
    # `after_checkpoint` runs on every rank too (rank > 0 carries
    # no publish hook, so it is a no-op there) to keep per-rank
    # hook bookkeeping in step.
    with telemetry.span("loop.save", step=step):
      with telemetry.span("loop.save_d2h", step=step):
        host_state = jax.device_get(state)
      with telemetry.span("loop.save_write", step=step):
        writer.save(step, host_state,
                    params=host_state.train_state.params,
                    batch_stats=host_state.train_state.batch_stats)
      with telemetry.span("loop.after_checkpoint", step=step):
        hook_list.after_checkpoint(step, state.train_state, model_dir)

  try:
    for transitions in prefetch_iter:  # spans as `loop.wait_feed`
      if step >= max_train_steps:
        break
      with perf_meter.dispatch("qtopt.dispatch", step=step, k=k,
                               seq=prefetch_iter.seq):
        if k == 1:
          state, metrics = train_step(
              state, transitions, jax.random.fold_in(step_rng, step))
        else:
          # Same per-step PRNG stream as K=1: the scan body folds
          # step_rng by ABSOLUTE step (step0 + i).
          state, metrics = train_step(state, transitions, step_rng,
                                      np.int32(step))
      step += k
      steps_since_log += k
      if tag_step is not None:
        tag_step(step)  # one int store; actors tag adds with it
      with telemetry.span("loop.after_step", step=step):
        hook_list.after_step(step, metrics)
      if chief and (step % log_every_steps == 0
                    or step == max_train_steps):
        with telemetry.span("loop.log", step=step):
          # The one place the loop waits for the device: the dispatch
          # enqueued above has to finish before its metrics exist.
          with telemetry.span("loop.log_sync", step=step):
            scalars = jax.device_get(metrics)
          dt = time.time() - t_last
          scalars["grad_steps_per_sec"] = (
              steps_since_log / max(dt, 1e-9))
          scalars["input_wait_fraction"] = (
              prefetch_iter.wait_fraction(dt))
          # Data-plane instrumentation rides the train log: fill,
          # add/sample rates, drops/evictions, staleness — next to the
          # loop's own throughput, the way stall_fraction is.
          replay_metrics = getattr(replay_buffer, "metrics_scalars",
                                   None)
          if replay_metrics is not None:
            scalars.update(replay_metrics())
          # Compile-cache counters from the telemetry registry: a miss
          # delta after the first interval is a warm-path recompile.
          scalars.update(
              telemetry.registry().scalars("compile_cache."))
          # Resource watermarks persist with the run (the report
          # tool's watermark section; the registry alone dies with
          # the process).
          scalars.update(telemetry.registry().scalars("rsrc."))
          telemetry.registry().gauge("train.grad_steps_per_sec").set(
              scalars["grad_steps_per_sec"])
          # Live utilization (perf.mfu / flops_per_sec) — same
          # denominator as bench MFU.
          scalars.update(perf_meter.publish(
              scalars["grad_steps_per_sec"]))
          metric_logger.write("train", step, scalars)
          if watch_sentinel is not None:
            watch_sentinel.evaluate(
                {**telemetry.registry().scalars(), **scalars},
                step=step)
          t_last = time.time()
          steps_since_log = 0
      if step % save_checkpoints_steps == 0 or step == max_train_steps:
        save(step)
        last_saved = step
    if last_saved != step:
      save(step)
  finally:
    # end() in the FINALLY: hooks now own real teardown (actor
    # threads); a training-loop exception must not leak collectors.
    try:
      hook_list.end(step, state.train_state, model_dir)
    except Exception:  # noqa: BLE001 — don't mask the original error
      log.exception("hook end() failed during teardown")
    prefetcher.close()
    writer.close()
    if watch_sentinel is not None:
      watch_sentinel.close()
    if metric_logger is not None:
      metric_logger.close()
  return state
