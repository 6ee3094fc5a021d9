"""train_eval_model: the training/eval/export orchestrator.

Reference parity: tensor2robot `train_eval.py` —
`train_eval_model(model, input_generator_train, input_generator_eval,
max_train_steps, eval_steps, create_exporters_fn, use_tpu, ...)` building
an (TPU)Estimator and running train / eval / continuous-eval / export
(SURVEY.md §4.1).

TPU-native redesign: no Estimator. The model's pure `train_step` is
jitted ONCE over a named device mesh with the batch sharded along the
data axis and state replicated (or sharded per the model's rules);
GSPMD inserts the ICI all-reduce. The host loop is thin: pull a
prefetched sharded batch, call the compiled step, occasionally log /
checkpoint — state stays on device the whole time (the reference paid a
host round-trip per `iterations_per_loop`). Checkpointing is async
orbax; resume is automatic from the latest checkpoint in `model_dir`.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Dict, Iterable, Optional

import jax
import numpy as np

from tensor2robot_tpu import config as gin
from tensor2robot_tpu import telemetry
from tensor2robot_tpu import train_loop
from tensor2robot_tpu.data.abstract_input_generator import (
    AbstractInputGenerator,
    Mode,
)
from tensor2robot_tpu.data import prefetch as prefetch_lib
from tensor2robot_tpu.hooks import Hook
from tensor2robot_tpu.models.model_interface import ModelInterface
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.parallel import state_sharding
from tensor2robot_tpu.startup import compile_cache
from tensor2robot_tpu.startup import orchestrator
from tensor2robot_tpu.train_loop import MetricLogger
from tensor2robot_tpu.utils import checkpoints as ckpt_lib
from tensor2robot_tpu.utils import profiling

log = logging.getLogger(__name__)

# Orbax emits dozens of INFO lines per checkpoint; keep the training log
# readable by default (users can re-raise the level explicitly).
for _noisy in ("orbax", "absl"):
  logging.getLogger(_noisy).setLevel(logging.WARNING)


def _compile_steps(model: ModelInterface, mesh, donate: bool = True,
                   state_shardings=None):
  """Jits train/eval steps with mesh shardings (batch on data axis).

  `state_shardings`: a NamedSharding pytree for the TrainState (from
  `parallel.state_sharding`); None replicates the state — pure data
  parallelism, the reference-equivalent default.
  """
  repl = mesh_lib.replicated(mesh)
  if state_shardings is None:
    state_shardings = repl
  batch = mesh_lib.batch_sharding(mesh)
  train_step = jax.jit(
      model.train_step,
      in_shardings=(state_shardings, batch, batch, repl),
      out_shardings=(state_shardings, repl),
      donate_argnums=(0,) if donate else (),
  )
  eval_step = jax.jit(
      model.eval_step,
      in_shardings=(state_shardings, batch, batch),
      out_shardings=repl,
  )
  return train_step, eval_step


def _spec_batch_avals(spec, batch_size: int, sharding):
  """Abstract [B, ...] batch pytree from a generator's (flat) wire spec.

  The generators' contract is "spec-conforming numpy batches", so the
  spec IS the aval source — AOT compilation never has to wait for the
  input pipeline to produce a first batch.
  """
  if spec is None:
    return None
  return jax.tree_util.tree_map(
      lambda s: jax.ShapeDtypeStruct(
          (batch_size,) + tuple(s.shape), np.dtype(s.dtype),
          sharding=sharding),
      spec)


def _specs_predict_batches(generator) -> bool:
  """Do this generator's wire specs say what its batches look like?

  Not when a leaf is a sequence: episode generators add the time axis
  (and a `sequence_length` feature) themselves, so the flat spec is
  not the batch's aval and the step compiles at its first real batch.
  """
  return not any(
      spec.is_sequence
      for tree in (generator.feature_spec, generator.label_spec)
      if tree is not None
      for spec in jax.tree_util.tree_leaves(tree))


def _batch_matches(avals, batch) -> bool:
  """Does a concrete batch pytree carry exactly the predicted avals?"""
  try:
    if jax.tree_util.tree_structure(avals) != \
        jax.tree_util.tree_structure(batch):
      return False
    return all(
        tuple(a.shape) == tuple(np.shape(b))
        and np.dtype(a.dtype) == np.result_type(b)
        for a, b in zip(jax.tree_util.tree_leaves(avals),
                        jax.tree_util.tree_leaves(batch)))
  except Exception:
    return False


def _checked_aot(compiled, fallback, feature_avals, label_avals, what):
  """Callable routing each batch to the AOT executable iff it matches
  the spec-predicted avals, else to the lazy jit.

  The spec contract makes a mismatch a generator bug, but a wrong
  guess must degrade to a recompile (the pre-AOT behavior), never to
  a crashed run — and a generator may diverge on ANY batch (e.g. a
  short final batch), so every call is checked: a tree compare, ~µs
  against a ms-scale dispatch.
  """
  if compiled is None:
    return fallback
  warned = []

  def call(state, features, labels, *rest):
    if (_batch_matches(feature_avals, features)
        and _batch_matches(label_avals, labels)):
      return compiled(state, features, labels, *rest)
    if not warned:
      warned.append(True)
      log.warning(
          "A batch does not match the AOT-compiled %s program's "
          "spec-predicted avals (generator diverged from its spec?); "
          "falling back to on-demand compilation for such batches.",
          what)
    return fallback(state, features, labels, *rest)

  return call


def _run_eval(model, eval_step, state, input_generator_eval, mesh,
              eval_steps: int, batch_size: Optional[int]) -> Dict[str, float]:
  """Averages eval metrics over `eval_steps` batches."""
  stream = input_generator_eval.create_dataset(
      Mode.EVAL, batch_size=batch_size)
  prefetcher = prefetch_lib.ShardedPrefetcher(
      stream, mesh_lib.batch_sharding(mesh), buffer_size=2)
  totals: Dict[str, float] = {}
  count = 0
  try:
    for features, labels in prefetcher:
      metrics = eval_step(state, features, labels)
      for key, value in metrics.items():
        totals[key] = totals.get(key, 0.0) + float(np.asarray(value))
      count += 1
      if count >= eval_steps:
        break
  finally:
    prefetcher.close()
  if count == 0:
    return {}
  return {k: v / count for k, v in totals.items()}


@gin.configurable
def train_eval_model(
    model: ModelInterface = gin.REQUIRED,
    model_dir: str = gin.REQUIRED,
    input_generator_train: Optional[AbstractInputGenerator] = None,
    input_generator_eval: Optional[AbstractInputGenerator] = None,
    max_train_steps: int = 1000,
    eval_steps: int = 10,
    eval_every_steps: Optional[int] = None,
    save_checkpoints_steps: int = 500,
    max_checkpoints_to_keep: int = 5,
    batch_size: Optional[int] = None,
    eval_batch_size: Optional[int] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    sharding_strategy: str = "replicated",
    min_size_to_shard: int = 2 ** 10,
    create_exporters_fn: Optional[Callable] = None,
    hooks: Iterable[Hook] = (),
    log_every_steps: int = 100,
    seed: int = 0,
    init_batch_size: int = 2,
    steps_per_dispatch: int = 1,
    overlap_startup: bool = True,
):
  """Trains (with interleaved eval) and exports; resumes automatically.

  `sharding_strategy` selects the TrainState placement over the mesh
  (`parallel.state_sharding` rules): "replicated" (pure data
  parallelism, the default), "fsdp" (zero-style param/optimizer
  sharding over the `fsdp` axis), "tp" (megatron-style over `model`),
  "ep" (stacked expert weights over `expert` — MoE models), or
  "pipeline" (stage-stacked weights over `stage`). The batch always
  shards over the data-like axes; GSPMD inserts the collectives each
  layout needs.

  `steps_per_dispatch` (K) is the reference TPUEstimator's
  `iterations_per_loop` (SURVEY.md §4.1): K train steps run as ONE
  device program per host call — a `lax.scan` over K host-stacked
  input batches — paying host/dispatch latency once per K steps.
  Quantization semantics: log/checkpoint/eval cadences and
  max_train_steps must be multiples of K, and per-step hooks observe
  each dispatch's LAST metrics. The per-step PRNG stream is identical
  to K=1.

  `overlap_startup` (default True) runs the three serial cold-start
  phases concurrently — AOT `.lower().compile()` of the train/eval
  programs (avals predicted from the generators' wire specs), the
  orbax resume restore, and the input pipeline's spin-up/first-batch
  prep — each a span `startup.<phase>` on its own thread (see
  docs/STARTUP.md). False is the reference serial path: restore, then
  lazy jit at the first step. Both paths are bitwise-identical in
  results; with a persistent compilation cache configured
  (`startup.configure_compilation_cache`), a warm restart skips XLA
  entirely.

  Returns the final TrainState (on device, placed per the strategy).
  """
  if mesh is None:
    mesh = mesh_lib.create_mesh()
  loop = train_loop.TrainLoop(
      model_dir, hooks, dispatch_span="train.dispatch",
      steps_per_dispatch=steps_per_dispatch,
      max_train_steps=max_train_steps,
      log_every_steps=log_every_steps,
      save_checkpoints_steps=save_checkpoints_steps,
      max_checkpoints_to_keep=max_checkpoints_to_keep,
      eval_every_steps=eval_every_steps)
  k = loop.k

  # --- bind generators to the model's wire specs ---
  if input_generator_train is not None:
    input_generator_train.set_specification_from_model(model, Mode.TRAIN)
  if input_generator_eval is not None:
    input_generator_eval.set_specification_from_model(model, Mode.EVAL)

  # --- init / resume state ---
  with orchestrator.Phase("init_state") as init_state:
    rng = jax.random.PRNGKey(seed)
    state = model.create_train_state(rng, batch_size=init_batch_size)
    state_shardings = state_sharding(
        mesh, state, strategy=sharding_strategy,
        min_size_to_shard=min_size_to_shard)
    state = jax.device_put(state, state_shardings)
    init_state.args["bytes"] = train_loop.state_bytes(state)
  resume_step = ckpt_lib.latest_step(model_dir)
  # A phase's span holds what is known before it starts.
  span_args = {"restore": {"step": resume_step,
                           "bytes": init_state.args["bytes"]},
               "input": {"k": k}}

  repl = mesh_lib.replicated(mesh)
  batch_sh = mesh_lib.batch_sharding(mesh)
  feed_sharding = batch_sh
  train_step, eval_step = _compile_steps(
      model, mesh, state_shardings=state_shardings)

  if k > 1:
    stacked_sh = prefetch_lib.stacked_sharding(batch_sh)
    feed_sharding = stacked_sh

    def k_steps(st, stacked_features, stacked_labels, rng, step0):
      return prefetch_lib.scan_k_steps(
          model.train_step, st, (stacked_features, stacked_labels),
          rng, step0)

    train_step = jax.jit(
        k_steps,
        in_shardings=(state_shardings, stacked_sh, stacked_sh,
                      repl, repl),
        out_shardings=(state_shardings, repl),
        donate_argnums=(0,),
    )

  # --- overlapped cold-start: AOT compile ∥ restore ∥ input spin-up ---
  # `will_train` over-approximates (a resume may already be past
  # max_train_steps — unknowable until the restore lands); an unused
  # prefetcher is closed without being consumed.
  will_train = input_generator_train is not None and max_train_steps > 0

  def _restore_phase():
    # Restored leaves adopt `state`'s shardings — checkpoints are
    # portable across strategies/layouts (tests/test_checkpoint_resharding).
    return ckpt_lib.restore_state(model_dir, like=state,
                                  step=resume_step)

  def _input_phase():
    stream = input_generator_train.create_dataset(
        Mode.TRAIN, batch_size=batch_size)
    if k > 1:
      # K-stacking retains each batch until the stack closes, past a
      # zero-copy data-plane stream's one-slot view lifetime — such
      # streams must copy out of the ring first.
      require_copies = getattr(stream, "require_copies", None)
      if require_copies is not None:
        require_copies()
      # Finite streams end cleanly mid-stack (the shared helper
      # swallows the inner StopIteration PEP 479 would otherwise
      # convert to a RuntimeError, preserving the final
      # off-interval checkpoint below).
      stream = prefetch_lib.stack_batches(stream, k)
    return prefetch_lib.ShardedPrefetcher(
        stream, feed_sharding, buffer_size=2)

  def _stack_avals(avals, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((k,) + tuple(a.shape), a.dtype,
                                       sharding=sharding), avals)

  def _compile_phase():
    # Avals come from the already-initialized `state` (the restore
    # preserves shapes/dtypes/shardings by construction) and the
    # generators' wire specs — nothing here waits on disk or on the
    # input pipeline, which is the whole point.
    out: Dict[str, Any] = {}
    state_avals = jax.tree_util.tree_map(compile_cache.aval_of, state)
    rng_aval = jax.ShapeDtypeStruct((2,), np.uint32, sharding=repl)
    if will_train and _specs_predict_batches(input_generator_train):
      bs = batch_size or input_generator_train.batch_size
      f_aval = _spec_batch_avals(
          input_generator_train.feature_spec, bs, batch_sh)
      l_aval = _spec_batch_avals(
          input_generator_train.label_spec, bs, batch_sh)
      if k > 1:
        f_aval = _stack_avals(f_aval, stacked_sh)
        l_aval = _stack_avals(l_aval, stacked_sh)
      out["train_avals"] = (f_aval, l_aval)
      if k > 1:
        step0_aval = jax.ShapeDtypeStruct((), np.int32, sharding=repl)
        out["train"] = train_step.lower(
            state_avals, f_aval, l_aval, rng_aval,
            step0_aval).compile()
      else:
        out["train"] = train_step.lower(
            state_avals, f_aval, l_aval, rng_aval).compile()
    if (input_generator_eval is not None
        and _specs_predict_batches(input_generator_eval)):
      ebs = (eval_batch_size or batch_size
             or input_generator_eval.batch_size)
      ef_aval = _spec_batch_avals(
          input_generator_eval.feature_spec, ebs, batch_sh)
      el_aval = _spec_batch_avals(
          input_generator_eval.label_spec, ebs, batch_sh)
      out["eval_avals"] = (ef_aval, el_aval)
      out["eval"] = eval_step.lower(
          state_avals, ef_aval, el_aval).compile()
    return out

  aot: Optional[Dict[str, Any]] = None
  phases: Dict[str, Any] = {}
  if overlap_startup:
    if will_train or input_generator_eval is not None:
      phases["compile"] = _compile_phase
    if resume_step is not None:
      phases["restore"] = _restore_phase
    if will_train:
      phases["input"] = _input_phase
  if phases:
    if resume_step is not None:
      log.info("Resuming from checkpoint at step %d in %s", resume_step,
               model_dir)
    report = orchestrator.run_overlapped(phases, span_args=span_args)
    if report.errors:
      # A failed phase must not leak a sibling's resources: the input
      # prefetcher pins buffered sharded batches in device memory.
      orchestrator.close_quietly(report.results.get("input"))
      loop.close()
      report.raise_first(order=("restore", "input", "compile"))
    aot = report.results.get("compile")
    state = report.results.get("restore", state)
    if report.results.get("input") is not None:
      # The loop's from here on: whatever fails, its teardown closes
      # the worker (it pins buffered sharded batches in HBM).
      loop.attach_feed(report.results["input"])
  elif resume_step is not None:
    # Serial reference path (overlap_startup=False).
    log.info("Resuming from checkpoint at step %d in %s", resume_step,
             model_dir)
    with orchestrator.Phase("restore", **span_args["restore"]):
      state = _restore_phase()

  if aot:
    train_callable = _checked_aot(
        aot.get("train"), train_step, *aot.get("train_avals", (None, None)),
        what="train")
    eval_callable = _checked_aot(
        aot.get("eval"), eval_step, *aot.get("eval_avals", (None, None)),
        what="eval")
  else:
    train_callable, eval_callable = train_step, eval_step

  # Live MFU attribution: the generic trainer has no analytic
  # model-flops formula (arbitrary models), so the denominator is XLA's
  # cost analysis of the AOT-compiled train program (÷ K for the
  # scanned dispatch) — approximate but stable for the run; absent
  # (lazy-jit fallback), perf.mfu is simply not published.
  train_flops = None
  if aot and aot.get("train") is not None:
    flops_per_call = profiling.compiled_flops_per_call(aot["train"])
    if flops_per_call:
      train_flops = flops_per_call / k

  def own_scalars(scalars, steps, dt, stall_secs):
    # `steps_per_sec` is the PURE train-loop rate (checkpoint saves
    # and interleaved evals excluded); `stall_fraction` is the
    # interval's share lost to them — the restart/save regressions
    # that `checkpoint_stall_ms` and `setup_s` watch on the chip.
    scalars["steps_per_sec"] = steps / max(dt - stall_secs, 1e-9)
    scalars["stall_fraction"] = min(
        max(stall_secs / max(dt, 1e-9), 0.0), 1.0)
    telemetry.registry().gauge("train.stall_fraction").set(
        scalars["stall_fraction"])
    return "steps_per_sec"

  def run_eval():
    return _run_eval(
        model, eval_callable, state, input_generator_eval, mesh,
        eval_steps, eval_batch_size or batch_size)

  def interleaved_eval(step):
    # On its own cadence, independent of the checkpoint interval.
    if step % eval_every_steps == 0 and step != max_train_steps:
      loop.write("eval", step, run_eval())

  # Sharded state saves AS-IS: orbax copies device shards to host
  # before save() returns (so the next step's donation is safe),
  # serializes asynchronously, and each process writes only its
  # addressable shards — a host-side device_get here would block,
  # materialize the unsharded state, and crash on a multi-process pod.
  loop.begin(
      model, int(np.asarray(jax.device_get(state.step))),
      flops_per_step=train_flops, devices=mesh.size,
      state=lambda: state, save_payload=lambda st: (st,),
      hook_state=lambda st: st, own_scalars=own_scalars,
      # The eval reads the live state between two dispatches: a run
      # that has one finishes each dispatch before the next.
      boundary_work=(interleaved_eval if input_generator_eval is not None
                     and eval_every_steps else None))
  try:
    with loop:
      if (input_generator_train is not None
          and loop.step < max_train_steps):
        if loop.feed is None:
          # Serial path (or resume landed short of max_train_steps with
          # no overlapped input phase): spin up the pipeline here.
          with orchestrator.Phase("input", **span_args["input"]):
            loop.attach_feed(_input_phase())
        step_rng = jax.random.PRNGKey(seed + 1)
        for features, labels in loop.dispatches():
          with loop.dispatch():
            if k == 1:
              state, metrics = train_callable(
                  state, features, labels,
                  jax.random.fold_in(step_rng, loop.step))
            else:
              state, metrics = train_callable(
                  state, features, labels, step_rng,
                  np.int32(loop.step))
          loop.after_dispatch(metrics)

      # --- final eval ---
      if input_generator_eval is not None:
        eval_metrics = run_eval()
        if eval_metrics:
          loop.write("eval", loop.step, eval_metrics)

      # --- exporters ---
      if create_exporters_fn is not None:
        for exporter in create_exporters_fn(model):
          exporter.export(model, state, model_dir)
  except BaseException:
    # The exception's traceback holds this frame, and a caller that
    # handles it can leave both in a reference cycle until a full
    # collection (a generator-based context manager's `__exit__` does):
    # the frame must not keep the state on the device until then.
    state = None
    raise
  return state


@gin.configurable
def continuous_eval(
    model: ModelInterface = gin.REQUIRED,
    model_dir: str = gin.REQUIRED,
    input_generator_eval: AbstractInputGenerator = gin.REQUIRED,
    eval_steps: int = 10,
    eval_batch_size: Optional[int] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    timeout_secs: Optional[float] = None,
    poll_interval_secs: float = 2.0,
    max_evals: Optional[int] = None,
    seed: int = 0,
    init_batch_size: int = 2,
):
  """Polls `model_dir` for new checkpoints and evals each one.

  Reference parity: the continuous-eval mode of `train_eval_model`
  (SURVEY.md §4.1). Returns {step: metrics} for all evaluated steps.

  Each record carries `restore_secs` / `eval_secs` /
  `restore_and_eval_secs` — the per-checkpoint wall this evaluator
  lags the trainer by, i.e. the predictor-side staleness bound: a
  checkpoint cadence shorter than `restore_and_eval_secs` means this
  loop permanently falls behind.
  """
  compile_cache.configure_compilation_cache()
  if mesh is None:
    mesh = mesh_lib.create_mesh()
  input_generator_eval.set_specification_from_model(model, Mode.EVAL)
  state = model.create_train_state(jax.random.PRNGKey(seed),
                                   batch_size=init_batch_size)
  state = jax.device_put(state, mesh_lib.replicated(mesh))
  _, eval_step = _compile_steps(model, mesh, donate=False)
  metric_logger = MetricLogger(model_dir)

  results: Dict[int, Dict[str, float]] = {}
  last_step = None
  try:
    while max_evals is None or len(results) < max_evals:
      new_step = ckpt_lib.wait_for_new_checkpoint(
          model_dir, last_step, timeout_secs=timeout_secs,
          poll_interval_secs=poll_interval_secs)
      if new_step is None:
        break
      t_restore = time.perf_counter()
      state = ckpt_lib.restore_state(model_dir, like=state, step=new_step)
      restore_secs = time.perf_counter() - t_restore
      t_eval = time.perf_counter()
      metrics = _run_eval(model, eval_step, state, input_generator_eval,
                          mesh, eval_steps, eval_batch_size)
      eval_secs = time.perf_counter() - t_eval
      metrics = dict(metrics)
      metrics["restore_secs"] = restore_secs
      metrics["eval_secs"] = eval_secs
      metrics["restore_and_eval_secs"] = restore_secs + eval_secs
      metric_logger.write("eval", new_step, metrics)
      results[new_step] = metrics
      last_step = new_step
  finally:
    metric_logger.close()
  return results
