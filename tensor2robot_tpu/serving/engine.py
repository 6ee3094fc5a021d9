"""Bucketed AOT serving engine: pre-compiled programs, pinned params.

One `BucketedServingEngine` owns everything shape-dependent about the
hot path:

  * a per-bucket COMPILE CACHE of ahead-of-time compiled executables
    (`jax.jit(...).lower(...).compile()` at warmup) — the hot path
    calls finished executables, so it can never trace or recompile;
  * ONE device-resident state (params + batch stats) pytree shared by
    every bucket's program — buckets multiply compiled code, never
    parameter memory;
  * lock-free hot-swap: `swap_state` transfers the new tree, blocks
    until every buffer is materialized on device, then publishes it
    with a single reference assignment (atomic under the GIL). Calls
    in flight keep the tree they already read — a dispatch observes
    entirely-old or entirely-new params, never a mix;
  * donated request buffers: the padded features are donated into the
    program (`donate_argnums`), letting XLA alias their device memory
    for outputs instead of allocating per call.

The wrapped `fn(state, features[, rng])` must be pure and jittable with
a leading batch dim on every feature/output leaf (a model's
`predict_step`, or a CEM policy closure).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import numpy as np

from tensor2robot_tpu import telemetry
from tensor2robot_tpu.serving import bucketing
from tensor2robot_tpu.telemetry import metrics as tmetrics


class _Published(NamedTuple):
  """One atomically-published params generation.

  The hot path reads this tuple with a single reference load, so the
  state, its monotonic version, and the learner step it was published
  at can never be observed mixed across a swap. `version` is the
  counter fleets log per episode; `learner_step` is the
  `param_refresh_lag` stamp (learner step the publisher trained to
  when it pushed this tree; 0 for the construction-time params).
  """

  state: Any
  version: int
  learner_step: int

# Process-wide count of engine bucket compiles — tests pin "zero
# recompiles after warmup" against it alongside jax.monitoring events.
_COMPILE_COUNT = 0


def compile_count() -> int:
  return _COMPILE_COUNT


class BucketedServingEngine:
  """Serves `fn` over powers-of-two batch buckets, AOT-compiled."""

  def __init__(self,
               fn: Callable,
               state: Any,
               example_features: Any,
               max_batch: int = 8,
               takes_rng: bool = False,
               donate_features: bool = True,
               metric_prefix: str = "serving."):
    """Args:
      fn: pure `(state, features)` or `(state, features, rng)` callable.
      state: the params pytree `fn` closes over per call; transferred
        to device here and pinned (swaps must keep shapes/dtypes).
      example_features: a features pytree with ANY leading batch dim —
        only its per-row shapes/dtypes matter (bucket avals are derived
        from it).
      max_batch: largest servable request; the bucket table covers it.
      takes_rng: whether `fn` threads a PRNG key (CEM policies).
      donate_features: donate the padded request buffers into the
        program.
      metric_prefix: namespace for this engine's registry metrics.
        The multi-tenant arena passes ``serving.<tenant>.`` so every
        tenant gets its own ``serving.<tenant>.bucket_<n>_ms``
        histograms (the SLO-accounting seam, docs/SERVING.md) and the
        Prometheus adapter renders the tenant as a label.
    """
    from tensor2robot_tpu.startup import compile_cache
    compile_cache.configure_compilation_cache()
    self._fn = fn
    self._takes_rng = takes_rng
    self._table = bucketing.bucket_table(max_batch)
    self._row_avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.asarray(a).shape[1:],
                                       np.asarray(a).dtype),
        example_features)
    placed = jax.device_put(state)
    jax.block_until_ready(placed)
    self._state = placed
    # Device bytes this engine pins (the arena's budget unit): params
    # only — compiled executables multiply code, never this.
    self._state_bytes = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(placed)
        if isinstance(leaf, jax.Array))
    self._released = False
    # The versioned publication record; `_state` is kept in sync for
    # introspection, but the hot path and the version/learner-step
    # readers all go through this one reference.
    self._published = _Published(placed, version=0, learner_step=0)
    # Buckets are LOWERED from these avals, never from the live state:
    # a concrete-state lower would key the (persistent) compile cache
    # on whatever tree `swap_state` last published, making a bucket
    # compiled after a checkpoint restore hash differently from the
    # same bucket compiled before it — nondeterministic cache keys
    # across restarts. Swaps keep shapes/dtypes/shardings, so the
    # avals stay valid for the engine's lifetime.
    self._state_avals = jax.tree_util.tree_map(
        compile_cache.aval_of, placed)
    self._compiled: Dict[int, Any] = {}
    donate = (1,) if donate_features else ()
    self._jitted = jax.jit(fn, donate_argnums=donate)
    self._swap_lock = threading.Lock()
    # Serializes bucket compilation: an async warmup (compile-ahead
    # overlapped with a checkpoint restore) must never race a cold
    # `predict` into compiling the same bucket twice.
    self._compile_lock = threading.Lock()
    self._warmup_thread: Optional[threading.Thread] = None
    self._warmup_error: Optional[BaseException] = None
    self.warmup_seconds: float = 0.0
    self.dispatch_count = 0
    self.dispatches_per_bucket: Dict[int, int] = {}
    self.swap_count = 0
    # Telemetry handles cached per engine (per-bucket lazily): the
    # hot path calls .observe()/.inc() without a registry lookup.
    self._metric_prefix = metric_prefix
    self._tm_dispatches = tmetrics.counter(f"{metric_prefix}dispatches")
    self._tm_swaps = tmetrics.counter(f"{metric_prefix}swaps")
    self._tm_bucket_ms: Dict[int, Any] = {}

  @property
  def bucket_sizes(self):
    return self._table

  @property
  def max_batch(self) -> int:
    return self._table[-1]

  @property
  def compiled_buckets(self):
    return tuple(sorted(self._compiled))

  @property
  def state_bytes(self) -> int:
    """Device bytes the pinned params tree occupies (arena budgeting).

    Constant for the engine's lifetime: swaps keep shapes/dtypes."""
    return self._state_bytes

  @property
  def released(self) -> bool:
    return self._released

  def release(self) -> None:
    """Retires the engine and drops its pinned device buffers
    (arena eviction path).

    Drops the engine's REFERENCES to the params tree and the
    compiled-executable table rather than hard-deleting the buffers:
    a dispatch already in flight on another thread holds its own
    reference to the published state and completes safely on the old
    params — the buffers free the moment the last reference dies
    (refcounting; in-flight dispatches are milliseconds, so the
    memory deadline is effectively the release). New `predict` calls
    fail fast with a clear error. A reload builds a FRESH engine;
    with the persistent compile cache configured its bucket compiles
    deserialize instead of recompiling (`cache_misses == 0`, the
    arena's reload contract). Idempotent.
    """
    # Under BOTH coordination locks (swap first, then compile — the
    # one place they nest, so no ordering cycle): _compile_bucket
    # checks the released flag under the compile lock (no cold-compile
    # resurrection into the cleared table, no lowering against None
    # avals), and swap_state re-checks it under the swap lock (a swap
    # losing the race to an eviction must not re-pin params into the
    # retired engine). Dict clears and reference drops only — nothing
    # blocking runs under either lock here.
    with self._swap_lock:
      with self._compile_lock:
        if self._released:
          return
        self._released = True
        self._compiled.clear()
        self._published = _Published(None, version=-1, learner_step=-1)
        self._state = None
        self._state_avals = None

  # ---- compilation ----

  def _feature_avals(self, bucket: int):
    return jax.tree_util.tree_map(
        lambda sd: jax.ShapeDtypeStruct((bucket,) + sd.shape, sd.dtype),
        self._row_avals)

  def _compile_bucket(self, bucket: int):
    """Compiles (or finds) the bucket's executable and RETURNS it —
    callers must dispatch the returned handle, not re-read the table:
    a release() racing in clears the table, and the local handle is
    what keeps the dispatch safe."""
    global _COMPILE_COUNT
    import warnings

    with self._compile_lock:
      if self._released:
        # A dispatch racing a release must not resurrect the engine by
        # cold-compiling into the cleared table.
        raise RuntimeError(
            "BucketedServingEngine was released (arena eviction); "
            "reload the tenant through the arena instead.")
      if bucket in self._compiled:
        return self._compiled[bucket]  # benign race to the warmup thread
      args = [self._state_avals, self._feature_avals(bucket)]
      if self._takes_rng:
        args.append(jax.ShapeDtypeStruct((2,), np.uint32))
      with warnings.catch_warnings():
        # Donation is best-effort: when no output matches a donated
        # input's shape/dtype XLA simply doesn't alias, which is fine —
        # the advisory warning would spam every warmup.
        warnings.filterwarnings(
            "ignore", message=".*donated buffers were not usable.*")
        # Compiling under the lock is the POINT of this lock: it
        # serializes an async warmup against a cold predict so the
        # same bucket never compiles twice; only compilers contend.
        # t2rcheck: disable=CON301
        executable = self._jitted.lower(*args).compile()
        self._compiled[bucket] = executable
      _COMPILE_COUNT += 1
      return executable

  def warmup(self) -> float:
    """AOT-compiles every bucket; returns wall seconds spent.

    Run at startup, BEFORE traffic: after it returns, every request
    size ≤ max_batch hits a finished executable and the control loop
    never absorbs a compile stall.
    """
    t0 = time.perf_counter()
    for bucket in self._table:
      if bucket not in self._compiled:
        self._compile_bucket(bucket)
    self.warmup_seconds = time.perf_counter() - t0
    return self.warmup_seconds

  def warmup_async(self) -> threading.Thread:
    """Starts `warmup()` on a background thread (compile-ahead).

    The cold-start overlap: callers kick this off, run their own
    startup work (typically the checkpoint restore), then
    `wait_warmup()`. Requests arriving mid-warmup are safe — the
    compile lock serializes them with the warmup thread, and an
    already-compiled bucket dispatches without waiting for the rest
    of the table. Idempotent: a second call returns the live thread.
    """
    if self._warmup_thread is None:
      def _run():
        try:
          self.warmup()
        except BaseException as e:  # surfaced by wait_warmup()
          self._warmup_error = e

      self._warmup_thread = threading.Thread(
          target=_run, name="engine-warmup", daemon=True)
      self._warmup_thread.start()
    return self._warmup_thread

  def wait_warmup(self) -> float:
    """Joins an async warmup; returns its wall seconds.

    Re-raises whatever the warmup thread raised — on EVERY join, not
    just the first: a failed warmup means uncompiled buckets, and a
    later caller (a retried restore(), a warmup_seconds read) must
    not be told the hot path is ready when it is not. No-op (0.0) if
    `warmup_async` was never called.
    """
    if self._warmup_thread is None:
      return 0.0
    self._warmup_thread.join()
    if self._warmup_error is not None:
      raise self._warmup_error
    return self.warmup_seconds

  # ---- params hot-swap ----

  @property
  def publication(self) -> _Published:
    """The current (state, version, learner_step) publication as ONE
    atomic read — callers that need version AND learner_step paired
    (the fleet's per-episode lag stamp) must use this, not the two
    scalar properties back to back (a swap between the reads would
    tear the pair)."""
    return self._published

  @property
  def params_version(self) -> int:
    """Monotonic publication counter: 0 = construction-time params,
    +1 per successful `swap_state`. The per-episode policy-version
    stamp actor fleets log (the `param_refresh_lag` measurement seam)."""
    return self._published.version

  @property
  def params_learner_step(self) -> int:
    """Learner step stamped on the currently-published params."""
    return self._published.learner_step

  def swap_state(self, new_state: Any,
                 learner_step: Optional[int] = None) -> None:
    """Publishes a fully-materialized new params tree (lock-free reads).

    The swap lock only serializes concurrent SWAPPERS (checkpoint
    poller vs. manual refresh); readers never take it — they grab the
    current reference once per dispatch. Each swap bumps the monotonic
    `params_version`; `learner_step` stamps the publication with the
    publisher's training progress (kept from the previous publication
    when omitted, so non-learner swappers don't reset the lag clock).
    """
    if self._released:
      raise RuntimeError(
          "BucketedServingEngine was released (arena eviction); "
          "swap through the arena, which reloads evicted tenants "
          "from their loader instead.")
    with self._swap_lock:
      # Re-check under the lock release() also takes: a swap that
      # lost the race to an eviction must not re-pin a fresh params
      # tree into the retired engine (a transient over-budget window
      # on a tight arena) — it fails here and the arena reports the
      # publication as not-landed.
      if self._released:
        raise RuntimeError(
            "BucketedServingEngine was released (arena eviction); "
            "swap through the arena, which reloads evicted tenants "
            "from their loader instead.")
      # Holding the lock across the transfer is intentional: only
      # SWAPPERS contend here (the hot path reads the published tuple
      # lock-free), and overlapping transfers of two checkpoint trees
      # would waste device memory for no ordering benefit.
      # t2rcheck: disable=CON301
      placed = jax.device_put(new_state)
      # Block BEFORE publishing: a dispatch must never race ahead of
      # a half-transferred restore.
      # t2rcheck: disable=CON301
      jax.block_until_ready(placed)
      previous = self._published
      self._published = _Published(
          placed,
          version=previous.version + 1,
          learner_step=(previous.learner_step if learner_step is None
                        else int(learner_step)))
      self._state = placed
      self.swap_count += 1
    telemetry.event("serving.swap_state",
                    version=self._published.version,
                    learner_step=self._published.learner_step)
    self._tm_swaps.inc()

  # ---- the hot path ----

  def predict(self, features: Any,
              rng: Optional[jax.Array] = None) -> Any:
    """One bucketed dispatch; returns host numpy outputs, unpadded."""
    if self._released:
      raise RuntimeError(
          "BucketedServingEngine was released (arena eviction); "
          "reload the tenant through the arena instead.")
    leaves = jax.tree_util.tree_leaves(features)
    n = int(np.asarray(leaves[0]).shape[0])
    bucket = bucketing.bucket_for(n, self._table)
    executable = self._compiled.get(bucket)
    if executable is None:
      # Cold bucket (warmup skipped): compile once, counted. Never
      # taken after warmup() — the table is fully populated there.
      executable = self._compile_bucket(bucket)
    padded = bucketing.pad_batch(features, bucket)
    # LOCAL references to both the executable (above) and the state
    # (one atomic publication read — old or new, never mixed): a
    # release racing in can clear the table and publish the None
    # sentinel, but this dispatch completes safely on what it already
    # holds; only a state read AFTER the release fails, clearly.
    state = self._published.state
    if state is None:
      raise RuntimeError(
          "BucketedServingEngine was released (arena eviction); "
          "reload the tenant through the arena instead.")
    t0 = time.perf_counter()
    with telemetry.span("serving.dispatch", bucket=bucket, rows=n):
      if self._takes_rng:
        outputs = executable(state, padded, rng)
      else:
        outputs = executable(state, padded)
      outputs = jax.tree_util.tree_map(
          lambda a: np.asarray(jax.device_get(a)), outputs)
    # Registry publication: per-bucket latency (the serving p50/p95
    # the telemetry RPC serves) next to the existing counters.
    hist = self._tm_bucket_ms.get(bucket)
    if hist is None:
      hist = self._tm_bucket_ms[bucket] = tmetrics.histogram(
          f"{self._metric_prefix}bucket_{bucket}_ms")
    hist.observe((time.perf_counter() - t0) * 1e3)
    self.dispatch_count += 1
    self.dispatches_per_bucket[bucket] = (
        self.dispatches_per_bucket.get(bucket, 0) + 1)
    self._tm_dispatches.inc()
    return bucketing.unpad_batch(outputs, n)
