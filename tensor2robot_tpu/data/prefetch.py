"""Host→device pipelining: sharded, double-buffered batch placement.

This replaces the reference's TPUEstimator infeed queue (SURVEY.md §4.1
"host↔device boundary is the infeed queue fed by tf.data"). TPU-native
version: each host batch is placed onto the mesh as a global `jax.Array`
sharded along the data axis via `jax.make_array_from_process_local_data`
(multi-host correct: each process contributes its local shard), with a
lookahead buffer so device compute of step N overlaps host prep + H2D
transfer of step N+1.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Any, Callable, Iterator, Optional

import jax
import numpy as np

from tensor2robot_tpu import config as gin
from tensor2robot_tpu.specs import TensorSpecStruct
from tensor2robot_tpu.telemetry import core as tracing
from tensor2robot_tpu.telemetry import metrics as tmetrics


@gin.configurable
def prefetch_buffer_size(buffer_size: Optional[int] = None,
                         online: bool = False,
                         offline_default: int = 2,
                         online_default: int = 1) -> int:
  """Resolves the `ShardedPrefetcher` lookahead depth (gin tunable).

  Depth trades throughput for sampling lead: each buffered dispatch is
  a batch sampled BEFORE the steps ahead of it ran, so an ONLINE run
  (actors feeding replay while the learner trains) pays `depth × K`
  extra steps of staleness per buffered dispatch. The online default is
  therefore 1 — the K>1 online sampling-lead finding from round 5 —
  while offline streams (logged episodes, prefill_random), where sample
  timing is irrelevant, keep double-buffering. An explicit
  `buffer_size` (arg or gin) always wins.
  """
  if buffer_size is not None:
    if buffer_size < 1:
      raise ValueError(f"buffer_size must be >= 1, got {buffer_size}")
    return int(buffer_size)
  return int(online_default if online else offline_default)


def make_data_sharding(mesh: jax.sharding.Mesh,
                       data_axes=("data",)) -> jax.sharding.NamedSharding:
  """Batch-dim sharding over the mesh's data axes, replicated elsewhere."""
  axes = tuple(a for a in data_axes if a in mesh.axis_names)
  spec = jax.sharding.PartitionSpec(axes if axes else None)
  return jax.sharding.NamedSharding(mesh, spec)


def validate_steps_per_dispatch(k: int, **cadences: Optional[int]
                                ) -> int:
  """Checks the iterations_per_loop quantization contract.

  Every named cadence (log/checkpoint/eval/max-steps) must be a
  multiple of K — boundaries are only observable between dispatches.
  `train_loop.TrainLoop` checks it for every trainer. Returns k. None-valued cadences are skipped.
  """
  k = int(k)
  if k < 1:
    raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")
  if k > 1:
    for name, value in cadences.items():
      if value and value % k:
        raise ValueError(
            f"{name}={value} must be a multiple of "
            f"steps_per_dispatch={k} (the iterations_per_loop "
            "quantization: boundaries are only observable between "
            "dispatches).")
  return k


# Slots of a `StackedBatchStream`'s ring: one is filled while the
# transfer reads the other.
_RING_SLOTS = 2


class StackedBatchStream:
  """Groups K consecutive batches into one [K, B, ...]-stacked pytree.

  The host side of `steps_per_dispatch`: the trainer's scan consumes
  one stacked block per device program. A finite stream that runs dry
  mid-stack ends the output stream cleanly (the partial stack is
  dropped) and the drop is LOGGED: a dataset whose length isn't a
  multiple of K trains up to K-1 fewer steps than K=1 would, and that
  must not be silent.

  A class rather than a generator so `close()` works CROSS-THREAD: the
  inner stream may own real resources — a data-plane stream owns worker
  PROCESSES — and `ShardedPrefetcher.close` must be able to reach them
  from the consumer thread while the prefetch thread is still blocked
  inside `__next__` (a generator would refuse with "generator already
  executing"; closing the plane instead UNBLOCKS that thread).

  Each stack is a set of fresh arrays that belong to whoever iterates
  the stream, unless that consumer calls `reuse_buffers`: a consumer
  that copies every dispatch off the host (`ShardedPrefetcher`, onto
  devices that do not alias host memory) and says through
  `transfer_started` which arrays are being made from the dispatch it
  was last given. The stream then assembles each dispatch in a ring of
  `_RING_SLOTS` host buffers of its own, allocated per leaf from the
  shapes of the first dispatch, and writes a slot again only when the
  arrays that were reading it are ready: memory that is written a
  second time costs a copy, memory fresh from the allocator a page
  fault for every 4 KB. A dispatch whose tree, shapes or dtypes are
  not the ring's gets fresh arrays. With the ring in use a dispatch is
  handed out only once the arrays made from the one before are ready
  (`_wait_for_transfers`): one transfer out of the ring at a time.

  Whose the K batches are. Without `lend` they are never touched: they
  stay the inner stream's own, and the stack copies them. `lend` is
  how a source takes a destination, beside the iterator protocol
  (which carries nothing towards the source, so a wrapper between the
  two that forwards only `next` and `close` need not know): a callable
  that is handed, before pull `i` of a dispatch that goes into the
  ring, a batch-shaped pytree of views `slot_leaf[i]`, for that one
  pull. A source that writes its batch there and yields those very
  arrays (`ReplayBuffer.gather_next_into`) has put it in place: no
  fresh arrays, no first touch, no second copy. Such a batch is a
  window onto the slot, which is written again `_RING_SLOTS`
  dispatches later: whoever keeps it beyond its own dispatch copies
  it. Any other batch is copied into its slice as
  before, so a source may ignore the lend. The slot is then claimed,
  and its readers waited for, before the K pulls and not after. The
  first dispatch (the ring has no shapes yet) and one of another
  signature run as without `lend`.

  Spans (docs/OBSERVABILITY.md): `feed.sample` around each of the K
  pulls, `feed.buffer_wait` around each wait for arrays being made
  (a slot's readers before it is written, the dispatch before at the
  end), `feed.stack` around what assembly the pulls left (`bytes`: those
  copied, 0 when all K landed in place). `seq` counts the stacks this
  stream has yielded; the prefetcher that consumes it counts the same
  pulls, and the loop's `TimedIterator` what comes out of the one FIFO
  between them, so the three agree without being passed along.
  Counters: `feed.stack.reused_dispatches` (assembled in the ring) and
  `feed.stack.fresh_dispatches`; with a ring in use,
  `feed.gather.in_place_batches` (arrived in their slice) and
  `feed.gather.copied_batches`.
  """

  def __init__(self, stream: Iterator[Any], k: int,
               lend: Optional[Callable[[Any], None]] = None):
    self._it = iter(stream)
    self._k = int(k)
    self._lend_to_source = lend
    self._exhausted = False
    self._seq = 0
    # The ring, once a consumer asked for it: per slot None, or the
    # slot's host leaves with the arrays that were last made from
    # them. A slot yielded and not reported through
    # `transfer_started` is in neither place: it is the consumer's.
    self._ring: Optional[list] = None
    self._signature = None  # of the dispatches the ring holds
    self._slot = 0  # the slot the next dispatch is written to
    self._lent = None  # (slot, its leaves) of the dispatch yielded last

  def __iter__(self):
    return self

  def reuse_buffers(self) -> None:
    """The consumer's promise, made before it iterates: the arrays of
    a dispatch are read by nothing but the copy it reports through
    `transfer_started`, so the stream may write them again once that
    copy is ready."""
    if self._ring is None:
      self._ring = [None] * _RING_SLOTS

  def transfer_started(self, placed: Any) -> None:
    """`placed` (a pytree of arrays with `block_until_ready`) is being
    made from the dispatch this stream yielded last."""
    ring, lent = self._ring, self._lent
    self._lent = None
    if ring is not None and lent is not None:
      slot, leaves = lent
      ring[slot] = (leaves, placed)

  def _claim_slot(self) -> Optional[list]:
    """The host leaves, of the ring's signature, that the next dispatch
    is assembled in, or None without a ring. Waits until nothing reads
    them any more."""
    ring = self._ring  # `close` on another thread unbinds it
    if ring is None:
      return None
    slot, self._slot = self._slot, (self._slot + 1) % len(ring)
    # Taken out of the ring: the device arrays a slot waits on live,
    # for the ring's part, no longer than this call.
    held, ring[slot] = ring[slot], None
    if held is None:
      leaves = [np.empty((self._k,) + shape, dtype)
                for shape, dtype in self._signature[1]]
    else:
      leaves, placed = held
      with tracing.span("feed.buffer_wait", seq=self._seq):
        jax.block_until_ready(placed)
    self._lent = (slot, leaves)
    return leaves

  def _wait_for_transfers(self) -> None:
    """One transfer out of the ring at a time: a dispatch is handed
    out only when the arrays made from the one before are ready. On a
    TPU v5e a second transfer of a dispatch's size, started while the
    first was under way, left the runtime's fast path and took 6.3–7.3
    s instead of 0.3–0.5 (PERF.md §6, PR 29). The gather or stack of
    this dispatch has overlapped that transfer already."""
    for held in self._ring or ():
      if held is not None:
        with tracing.span("feed.buffer_wait", seq=self._seq):
          jax.block_until_ready(held[1])

  def _return_slot(self) -> None:
    """Puts back the slot claimed last, which nothing reads: the
    dispatch it was claimed for turned out not to be the ring's."""
    ring, lent = self._ring, self._lent
    self._lent = None
    if ring is not None and lent is not None:
      slot, leaves = lent
      ring[slot], self._slot = (leaves, ()), slot

  def __next__(self):
    if self._exhausted:
      raise StopIteration
    k = self._k
    self._lent = None  # what was yielded and not reported stays away
    # Where the source takes a destination and the ring knows its
    # shapes, the slot is claimed before the pulls.
    into = (self._claim_slot()
            if self._lend_to_source is not None
            and self._signature is not None else None)
    batches, in_place = [], [False] * k
    for i in range(k):
      try:
        with tracing.span("feed.sample", seq=self._seq, i=i):
          if into is not None:
            views = [leaf[i] for leaf in into]
            self._lend_to_source(jax.tree_util.tree_unflatten(
                self._signature[0], views))
          batches.append(next(self._it))
        if into is not None:
          got = jax.tree_util.tree_leaves(batches[-1])
          in_place[i] = len(got) == len(views) and all(
              x is view for x, view in zip(got, views))
      except StopIteration:
        self._exhausted = True
        if batches:
          import logging

          logging.getLogger(__name__).warning(
              "steps_per_dispatch=%d dropped a partial tail of %d "
              "batch(es): the finite input stream's length is not a "
              "multiple of K, so this run trains %d fewer step(s) "
              "than K=1 would.", self._k, len(batches), len(batches))
        self.close()  # the inner stream is done: release it now
        raise
    ring_in_use = self._ring is not None
    if ring_in_use:
      signature = _dispatch_signature(batches)
      if self._signature is None:
        self._signature = signature
      if signature is None or signature != self._signature:
        if into is not None:  # np.stack copies what landed there too
          self._return_slot()
          into, in_place = None, [False] * k
      elif into is None:
        into = self._claim_slot()
    copied = [b for b, placed in zip(batches, in_place) if not placed]
    with tracing.span("feed.stack", seq=self._seq,
                      bytes=tree_nbytes(copied)):
      if into is None:
        stacked = jax.tree_util.tree_map(
            lambda *xs: np.stack(xs), *batches)
      else:
        for i, batch in enumerate(batches):
          if not in_place[i]:
            for leaf, x in zip(into,
                               jax.tree_util.tree_leaves(batch)):
              leaf[i] = x
        stacked = jax.tree_util.tree_unflatten(
            self._signature[0], into)
    self._wait_for_transfers()
    tmetrics.counter("feed.stack.fresh_dispatches" if into is None
                     else "feed.stack.reused_dispatches").inc()
    if ring_in_use:
      tmetrics.counter("feed.gather.in_place_batches").inc(
          k - len(copied))
      tmetrics.counter("feed.gather.copied_batches").inc(len(copied))
    self._seq += 1
    return stacked

  def close(self) -> None:
    # The ring goes too: its host buffers, and the device arrays its
    # slots wait on.
    self._ring = self._lent = None
    closer = getattr(self._it, "close", None)
    if callable(closer):
      closer()


def _dispatch_signature(batches) -> Optional[tuple]:
  """(tree, ((shape, dtype) per leaf)) where the K batches of a
  dispatch are numpy arrays of one tree, shape and dtype leaf by leaf,
  else None: only then is `np.stack(xs, out=...)` into a buffer of
  that shape the same bytes as `np.stack(xs)`."""
  signatures = set()
  for batch in batches:
    leaves, tree = jax.tree_util.tree_flatten(batch)
    if not all(isinstance(x, np.ndarray) for x in leaves):
      return None
    signatures.add((tree, tuple((x.shape, x.dtype) for x in leaves)))
  return signatures.pop() if len(signatures) == 1 else None


def stack_batches(stream: Iterator[Any], k: int,
                  lend: Optional[Callable[[Any], None]] = None
                  ) -> StackedBatchStream:
  return StackedBatchStream(stream, k, lend)


def scan_k_steps(step_fn, state, stacked_batches, rng, step0):
  """K train steps as one traced program (the dispatch body both
  trainers jit — shared so the iterations_per_loop semantics cannot
  diverge between them, the same reason `validate_steps_per_dispatch`
  is shared).

  Args:
    step_fn: (state, *batch_parts, rng) → (state, metrics) — the
      per-step train function.
    state: the carried TrainState (donated by the caller's jit).
    stacked_batches: TUPLE of [K, B, ...]-stacked pytrees; scanned
      together, so each scan step sees the tuple's per-step slices.
    rng: the per-run step PRNG base key.
    step0: absolute step of the dispatch's first step; each scanned
      step folds `rng` by `step0 + i` — the per-step PRNG stream is
      IDENTICAL to K=1 (the equivalence both trainers' tests pin).

  Returns (state, last step's metrics) — hooks/logging observe only
  each dispatch's final step, the TPUEstimator quantization contract.
  """
  from jax import numpy as jnp

  def body(carry, xs):
    st, i = carry
    st, metrics = step_fn(*((st,) + xs),
                          jax.random.fold_in(rng, step0 + i))
    return (st, i + 1), metrics

  (state, _), metrics_seq = jax.lax.scan(
      body, (state, jnp.zeros((), jnp.int32)), stacked_batches)
  return state, jax.tree_util.tree_map(lambda m: m[-1], metrics_seq)


def stacked_sharding(sharding: jax.sharding.NamedSharding
                     ) -> jax.sharding.NamedSharding:
  """The [K, B, ...]-stacked twin of a batch sharding: the batch dim's
  spec shifts right one position (K is never sharded)."""
  return jax.sharding.NamedSharding(
      sharding.mesh, jax.sharding.PartitionSpec(None, *sharding.spec))


def tree_nbytes(tree: Any) -> int:
  """Bytes held by the array leaves of a pytree (a span's `bytes`)."""
  return sum(getattr(x, "nbytes", 0)
             for x in jax.tree_util.tree_leaves(tree))


def device_put_batch(batch: Any, sharding: jax.sharding.Sharding) -> Any:
  """Places a pytree of host numpy arrays as global sharded jax.Arrays."""

  def put(x):
    x = np.asarray(x)
    # Batch-axis sharding only applies to arrays with a batch dim; scalars
    # replicate.
    if x.ndim == 0:
      return jax.device_put(x)
    return jax.make_array_from_process_local_data(sharding, x)

  return jax.tree_util.tree_map(put, batch)


def _copies_off_host(sharding: jax.sharding.Sharding) -> bool:
  """Whether arrays placed with `sharding` own their bytes: the CPU
  client may alias an aligned numpy array instead of copying it, and
  writing that array again would change the `jax.Array`."""
  return all(d.platform != "cpu" for d in sharding.device_set)


class ShardedPrefetcher:
  """Iterator wrapper: host batches → mesh-sharded arrays, N steps ahead.

  A background thread pulls from the (possibly slow: TFRecord parse,
  image decode) host iterator and performs the H2D transfer, keeping up
  to `buffer_size` global batches resident ahead of compute. This is the
  framework's single host↔device seam; everything downstream is jitted.

  The thread's time is named by spans that share the batch's `seq`
  (docs/OBSERVABILITY.md): `feed.sample` around the pull (`feed.pull`
  around a `StackedBatchStream`'s, which names its own K pulls and the
  stack), `feed.device_put` around the placement as this thread lives it
  (the call, plus the wait for the transfer only where the zero-copy
  protocol makes one), `feed.queue_put` around the bounded put, which
  is long only while the feed is ahead of the loop.

  The thread reads each host batch once, to place it, and hands on
  only the placed arrays. So where placement copies the bytes off the
  host (any platform but `cpu`, whose client may alias a numpy array)
  it lets a `StackedBatchStream` stack into a ring of its own buffers
  (`reuse_buffers`), and tells it after each placement which arrays
  are reading the buffer it yielded last (`transfer_started`).
  """

  def __init__(self,
               iterator: Iterator[Any],
               sharding: jax.sharding.Sharding,
               buffer_size: int = 2):
    self._iterator = iterator
    self._sharding = sharding
    self._buffer_size = buffer_size
    self._queue: "queue.Queue" = queue.Queue(maxsize=buffer_size)
    self._done = object()
    self._error: Optional[BaseException] = None
    self._stop = threading.Event()
    self._thread = threading.Thread(target=self._worker, daemon=True)
    self._thread.start()

  def _worker(self):
    # Zero-copy source protocol (data-plane streams): batches are
    # views into a shared-memory ring; the slot may only recycle once
    # the device owns the bytes, so block on the transfer, then
    # release. Sources without the protocol are unaffected.
    release = None
    if getattr(self._iterator, "release_after_transfer", False):
      release = getattr(self._iterator, "release_consumed", None)
    # A `StackedBatchStream` names its own K pulls and the stack; the
    # pull around them is `feed.pull`, whose self time is what the pull
    # costs besides: the K batches go back to the allocator inside it
    # (and the previous dispatch's host copy, rebound here, where the
    # stream made it afresh).
    stacked = isinstance(self._iterator, StackedBatchStream)
    pull, pull_args = (("feed.pull", {}) if stacked
                       else ("feed.sample", {"i": 0}))
    # This thread reads a host batch once, to place it, and the loop
    # never sees it: where placing copies the bytes off the host the
    # stream may stack the next dispatches into the same memory.
    reuse = stacked and _copies_off_host(self._sharding)
    if reuse:
      self._iterator.reuse_buffers()
    try:
      source = iter(self._iterator)
      seq = 0
      while True:
        try:
          with tracing.span(pull, seq=seq, **pull_args):
            batch = next(source)
        except StopIteration:
          break
        with tracing.span("feed.device_put", seq=seq,
                          bytes=tree_nbytes(batch)):
          placed = device_put_batch(batch, self._sharding)
          if reuse:
            self._iterator.transfer_started(placed)
          if release is not None:
            jax.block_until_ready(placed)
            release()
        # Bounded put that notices close(): don't block forever holding
        # device buffers once the consumer abandoned the stream.
        with tracing.span("feed.queue_put", seq=seq):
          while not self._stop.is_set():
            try:
              self._queue.put(placed, timeout=0.1)
              break
            except queue.Full:
              continue
        if self._stop.is_set():
          return
        seq += 1
    except BaseException as e:  # surfaced on the consumer thread
      self._error = e
    finally:
      # The sentinel must reach the consumer (or close() must have been
      # called) or __next__ would block forever; bounded-put like above.
      while not self._stop.is_set():
        try:
          self._queue.put(self._done, timeout=0.1)
          break
        except queue.Full:
          continue

  def _close_source(self) -> bool:
    """Closes the input stream; True unless it must be retried.

    A plain generator refuses a cross-thread close while the prefetch
    thread is executing it (ValueError: generator already executing) —
    that is the one retryable outcome. Data-plane chains
    (`HostDataPlane` / `_PlaneStream` / `StackedBatchStream`) close
    from any thread.
    """
    closer = getattr(self._iterator, "close", None)
    if not callable(closer):
      return True
    try:
      closer()
      return True
    except ValueError:  # generator running in the prefetch thread
      return False
    except Exception:  # pragma: no cover - teardown must not raise
      import logging
      logging.getLogger(__name__).warning(
          "input stream close() failed", exc_info=True)
      return True

  def close(self, timeout_secs: float = 5.0) -> None:
    """Stops the worker and releases buffered device batches.

    Call when abandoning the stream early (e.g. bounded eval over an
    infinite generator); otherwise the worker thread would sit blocked
    holding `buffer_size` device-resident batches. Closes the source
    too: data-plane streams own worker PROCESSES and a shared-memory
    segment — abandoning the prefetcher must not leak them (pinned by
    tests/test_data_plane.py).
    """
    self._stop.set()
    while True:
      try:
        self._queue.get_nowait()
      except queue.Empty:
        break
    self._thread.join(timeout=timeout_secs)
    if self._thread.is_alive():
      # The thread is stuck inside next(source) — e.g. a starved
      # HostDataPlane polling its full queue, which no stop flag of
      # OURS interrupts. Closing the source from here UNBLOCKS it
      # (plane close terminates workers; the blocked __next__ raises),
      # so the join below reclaims the thread instead of leaking the
      # whole chain behind a 5s shrug.
      closed = self._close_source()
      self._thread.join(timeout=timeout_secs)
      if not closed and not self._thread.is_alive():
        closed = self._close_source()  # generator now suspended: retry
      if not closed:
        import logging
        logging.getLogger(__name__).warning(
            "input stream close() could not run: the prefetch thread "
            "is still executing the source generator; its resources "
            "may leak until process exit")
    else:
      self._close_source()

  def __iter__(self):
    return self

  def __next__(self):
    # Timed-slice get: a bare `get()` would strand this consumer
    # forever if `close()` ran between the empty-queue check and the
    # block — close() drains the queue and the worker's bounded
    # sentinel-put gives up once `_stop` is set, so nothing would ever
    # arrive to wake a blocked consumer (found by t2rcheck CON302).
    while True:
      if self._stop.is_set():
        raise StopIteration
      try:
        item = self._queue.get(timeout=0.1)
        break
      except queue.Empty:
        continue
    if item is self._done:
      if self._error is not None:
        raise self._error
      raise StopIteration
    return item


class TimedIterator:
  """Iterator wrapper accumulating wall time spent blocked in `next()`.

  The `input_wait_fraction` measurement of every trainer with a feed
  (`train_loop.TrainLoop.attach_feed` wraps the prefetcher in one):
  near 0 the feed keeps up (the device is the bottleneck); toward 1
  the chip starves — the benchmark's `feed_wait_share` reads it over
  a window. Raise `TFRecordInputGenerator.num_workers` (the
  process-parallel data plane, docs/DATA.md) when it climbs.
  """

  def __init__(self, iterator: Iterator[Any]):
    self._it = iter(iterator)
    self.wait_secs = 0.0
    self.seq = -1  # of the item returned last: the feed's `seq`

  def __iter__(self):
    return self

  def __next__(self):
    # One reading serves the fraction and the `loop.wait_feed` span.
    t0 = time.monotonic()
    try:
      item = next(self._it)
    finally:
      waited = time.monotonic() - t0
      self.wait_secs += waited
    self.seq += 1
    tracing.get_tracer().record("loop.wait_feed", t0, waited,
                                seq=self.seq)
    return item

  def wait_fraction(self, interval_secs: float) -> float:
    """Clamped share of `interval_secs` spent blocked; resets the
    accumulator (one call per log interval)."""
    fraction = min(max(self.wait_secs / max(interval_secs, 1e-9), 0.0),
                   1.0)
    self.wait_secs = 0.0
    # Registry publication: the telemetry-plane twin of the train
    # log's input_wait_fraction (one gauge set per log interval).
    tmetrics.gauge("input.wait_fraction").set(fraction)
    return fraction


def prefetch_to_mesh(iterator: Iterator[Any],
                     mesh: jax.sharding.Mesh,
                     data_axes=("data",),
                     buffer_size: int = 2) -> ShardedPrefetcher:
  return ShardedPrefetcher(
      iterator, make_data_sharding(mesh, data_axes), buffer_size)
