"""Process-parallel host data plane: N decode workers → shm ring → one
consumer stream.

WHY: the in-process tf.data pipeline caps out around one core's worth
of decode, and threads can't fix it (the GIL plus TF intra-op
contention). Where the feed bounds a chip is `feed_wait_share` and the
`feed_*` metrics of BENCHMARK.json's `qtopt_472.train` (PERF.md §5).
The Podracer lesson (arXiv:2104.06272) is that TPU utilization is a
host-side data-plane problem: decouple a scalable host plane from
device compute. This module is that plane's local form — the same
fan-in shape the replay service uses for actors, applied to file-backed
input:

    worker 0 ─┐ (own process: parse+decode its file shard)
    worker 1 ─┼─ shm ring (finished batches, zero-copy) ─→ assembler
    worker N ─┘                                            (consumer)

Each worker owns a DETERMINISTIC shard of the file list (files[i::N]),
runs the ordinary graph-parse tf.data pipeline over it, and memcpys
each finished batch into a free ring slot. The consumer's `__next__`
pops finished slots and returns numpy views INTO the ring — no copy on
the hot path (`copy=True` trades one memcpy for an unconditional
lifetime: see `h2d_aliases_host_memory` for when that trade is
mandatory).

Failure semantics mirror `replay.service` (same latch-and-re-raise
discipline):
  * a worker EXCEPTION ships its traceback through the full queue, is
    latched, and re-raises in the consumer on this and every later
    `__next__`;
  * a worker DEATH without a message (segfault, kill) is detected by
    exit-code polling and latched the same way;
  * `close()` always terminates workers — including workers blocked
    waiting for a free slot (they poll a stop event) — and unlinks the
    shared segment. Close is idempotent and safe to call with the
    stream mid-flight.

Ordering: batches arrive in ring-completion order. With ONE worker that
order is the worker's own pipeline order, which is why
`num_workers ∈ {0, 1}` can promise a bitwise-identical stream under a
fixed seed (pinned in tests/test_data_plane.py); with N > 1 workers
arrival order is load-dependent and only the per-worker suborder is
deterministic.
"""

from __future__ import annotations

import logging
import multiprocessing
import queue as queue_lib
import time
import traceback
from typing import Callable, Dict, Iterator, List, Optional

import numpy as np

from tensor2robot_tpu import config as gin
from tensor2robot_tpu.data.shm_ring import ShmRing, WireLayout
from tensor2robot_tpu.telemetry import metrics as tmetrics

log = logging.getLogger(__name__)

# Queue message tags (worker → consumer).
_BATCH, _DONE, _ERROR = "batch", "done", "error"


def h2d_aliases_host_memory() -> bool:
  """Does `jax.device_put` of page-aligned host memory ALIAS it?

  On the CPU backend XLA zero-copies suitably aligned numpy buffers —
  measured here: a device_put of a shared-memory-backed array tracks
  later writes to the segment. Recycling a ring slot would then mutate
  a "device" batch in flight, so consumers feeding jax on CPU must
  copy out of the ring. On TPU/GPU the H2D DMA lands in device memory;
  once the transfer completes the host view is dead weight and the
  slot can be recycled (the `release_after_transfer` protocol in
  `ShardedPrefetcher`).
  """
  try:
    import jax
    return jax.default_backend() == "cpu"
  except Exception:  # pragma: no cover - no jax in a pure host tool
    return True  # be safe: copy


def _worker_main(source: Callable[[int, int], Iterator[Dict[str, np.ndarray]]],
                 worker_index: int, num_workers: int, ring_name: str,
                 layout: WireLayout, num_slots: int, free_q, full_q,
                 stop) -> None:
  """Worker process body: stream batches from `source` into the ring.

  `source(worker_index, num_workers)` must yield flat dicts conforming
  to `layout`. Every blocking acquire polls `stop` so `close()` can
  always reclaim a worker stuck on a full ring.
  """
  ring = None
  try:
    ring = ShmRing.attach(ring_name, layout, num_slots)
    for flat in source(worker_index, num_workers):
      while True:
        if stop.is_set():
          return
        try:
          slot = free_q.get(timeout=0.1)
          break
        except queue_lib.Empty:
          continue
      ring.write(slot, flat)
      full_q.put((_BATCH, worker_index, slot))
    full_q.put((_DONE, worker_index, -1))
  except BaseException:  # latched and re-raised consumer-side
    try:
      full_q.put((_ERROR, worker_index, traceback.format_exc()))
    except Exception:  # pragma: no cover - queue already torn down
      pass
  finally:
    if ring is not None:
      ring.close()
    # Flush this process's queue feeder threads so an exit never
    # strands a message half-written into the pipe.
    for q in (free_q, full_q):
      try:
        q.close()
        q.join_thread()
      except Exception:  # pragma: no cover
        pass


@gin.configurable
class HostDataPlane:
  """N worker processes fanned into one shm-ring batch stream.

  Args:
    source: picklable callable `(worker_index, num_workers) → iterator
      of flat dict batches` conforming to `layout`. Runs INSIDE each
      worker process (spawn context: it must import everything it
      needs).
    layout: the ring's `WireLayout` (full batched shapes).
    num_workers: worker process count (>= 1; `num_workers=0` callers
      should not construct a plane at all — that's the in-process
      path).
    slots_per_worker: ring depth per worker, FLOORED AT 2 (values
      below are promoted: a worker must be able to decode one batch
      while its last waits for the consumer, or the plane serializes).
      The ring's memory footprint is `num_slots × layout.slot_bytes`
      with `num_slots = max(2, slots_per_worker) × num_workers` —
      size against the floor, not the requested value.
    copy: `views()` batches are copied out of the ring before being
      returned. `False` returns zero-copy views valid until the NEXT
      `__next__`/`close` (the consumer owns exactly one slot at a
      time). None resolves to `h2d_aliases_host_memory()` — copy
      whenever a downstream jax.device_put could alias ring memory.
    mp_context: multiprocessing start method. "spawn" (default) keeps
      workers clear of the parent's TF/JAX runtime state — forking a
      process with live TF threadpools deadlocks.
  """

  def __init__(self,
               source: Callable[[int, int],
                                Iterator[Dict[str, np.ndarray]]],
               layout: WireLayout,
               num_workers: int,
               slots_per_worker: int = 2,
               copy: Optional[bool] = None,
               mp_context: str = "spawn"):
    if num_workers < 1:
      raise ValueError(
          f"HostDataPlane needs num_workers >= 1, got {num_workers}")
    self._layout = layout
    self._copy = h2d_aliases_host_memory() if copy is None else bool(copy)
    self.num_slots = max(2, slots_per_worker) * num_workers
    self._ring = ShmRing(layout, self.num_slots)
    ctx = multiprocessing.get_context(mp_context)
    self._free_q = ctx.Queue()
    self._full_q = ctx.Queue()
    self._stop = ctx.Event()
    for slot in range(self.num_slots):
      self._free_q.put(slot)
    self._pending_slot: Optional[int] = None
    self._done: List[bool] = [False] * num_workers
    self._suspect: List[bool] = [False] * num_workers
    self._error: Optional[BaseException] = None
    self._closed = False
    self._last_death_poll = time.monotonic()
    self.batches_out = 0
    self._workers = [
        ctx.Process(
            target=_worker_main,
            args=(source, i, num_workers, self._ring.name, layout,
                  self.num_slots, self._free_q, self._full_q,
                  self._stop),
            name=f"t2r-data-plane-{i}", daemon=True)
        for i in range(num_workers)]
    for p in self._workers:
      p.start()

  # ---- consumer protocol ----

  def __iter__(self) -> "HostDataPlane":
    return self

  def release(self) -> None:
    """Returns the slot backing the last-yielded views to the free
    pool. Idempotent; called automatically on the next `__next__`
    (zero-copy mode) or immediately (copy mode)."""
    if self._pending_slot is not None and not self._closed:
      self._free_q.put(self._pending_slot)
    self._pending_slot = None

  def _latch(self, err: BaseException) -> BaseException:
    self._error = err
    tmetrics.counter("data_plane.worker_failures").inc()
    return err

  def _check_workers(self) -> None:
    """Exit-code poll: a worker that died without a message (segfault,
    external kill, silent os._exit) latches a crash error."""
    for i, p in enumerate(self._workers):
      if self._done[i] or p.is_alive():
        continue
      if p.exitcode != 0:
        raise self._latch(RuntimeError(
            f"data-plane worker {i} died (exit code {p.exitcode}) "
            "without reporting; its batch (if mid-write) is "
            "discarded"))
      # Dead with exit code 0 but no DONE marker read yet. A NORMAL
      # finisher flushes its marker into the pipe before exiting
      # (join_thread in the worker's finally), but that flush can land
      # in the instant between this poll window expiring and the
      # is_alive check — so give it exactly one more full get() window
      # to surface before declaring the death silent (e.g. a source
      # that os._exit(0)s mid-stream), which would otherwise hang the
      # consumer forever.
      if self._suspect[i]:
        raise self._latch(RuntimeError(
            f"data-plane worker {i} exited (code 0) without sending "
            "its done marker; treating as a silent death so the "
            "consumer never hangs"))
      self._suspect[i] = True

  def _poll_crashed_workers(self) -> None:
    """Nonzero-exit deaths latch even while the queue stays BUSY.

    `_check_workers` only runs on an empty-queue window, so with N > 1
    workers a crashed (OOM-killed, segfaulted) worker would otherwise
    go undetected as long as its siblings keep batches flowing — the
    stream silently drops that worker's file shard. Clean (code 0)
    exits are NOT judged here: a legitimate finisher's done marker may
    lawfully sit queued behind other workers' batches, and declaring
    it a silent death early would be a false positive; those resolve
    on the empty-queue path, where the queue has provably drained.
    """
    now = time.monotonic()
    if now - self._last_death_poll < 0.5:
      return
    self._last_death_poll = now
    for i, p in enumerate(self._workers):
      if not self._done[i] and not p.is_alive() and p.exitcode != 0:
        raise self._latch(RuntimeError(
            f"data-plane worker {i} died (exit code {p.exitcode}) "
            "without reporting; its file shard is no longer being "
            "produced"))

  def __next__(self) -> Dict[str, np.ndarray]:
    if self._error is not None:
      raise RuntimeError("data-plane worker failed") from self._error
    if self._closed:
      raise StopIteration
    self.release()
    while True:
      if all(self._done):
        # Per-producer FIFO: every worker's batches precede its done
        # marker, so once all markers are in the queue holds nothing.
        raise StopIteration
      self._poll_crashed_workers()
      try:
        tag, widx, payload = self._full_q.get(timeout=0.2)
      except queue_lib.Empty:
        self._check_workers()
        continue
      if tag == _BATCH:
        self.batches_out += 1
        tmetrics.counter("data_plane.batches").inc()
        if self._copy:
          batch = {k: np.array(v)
                   for k, v in self._ring.views(payload).items()}
          self._free_q.put(payload)
          return batch
        self._pending_slot = payload
        return self._ring.views(payload)
      if tag == _DONE:
        self._done[widx] = True
        continue
      assert tag == _ERROR
      raise self._latch(RuntimeError(
          f"data-plane worker {widx} raised:\n{payload}"))

  # ---- introspection / lifecycle ----

  @property
  def copies_batches(self) -> bool:
    return self._copy

  def require_copies(self) -> None:
    """Switches to copy-out mode (callers that retain batches past the
    next `__next__`, e.g. K-step stacking)."""
    self._copy = True

  def workers_alive(self) -> int:
    return sum(p.is_alive() for p in self._workers)

  def close(self, timeout_secs: float = 5.0) -> None:
    """Stops workers (even mid-block), reclaims the shared segment."""
    if self._closed:
      return
    self._closed = True
    self._stop.set()
    # Drain the full queue so worker feeder threads can flush and the
    # workers' final puts never wedge their interpreter shutdown.
    deadline = time.monotonic() + timeout_secs
    for p in self._workers:
      p.join(timeout=max(0.0, deadline - time.monotonic()) + 0.1)
    for p in self._workers:
      if p.is_alive():  # blocked past the grace period: force it
        p.terminate()
        p.join(timeout=1.0)
      if p.is_alive():  # pragma: no cover - terminate() ignored
        p.kill()
        p.join(timeout=1.0)
    for q in (self._full_q, self._free_q):
      try:
        while True:
          q.get_nowait()
      except queue_lib.Empty:
        pass
      q.close()
      q.join_thread()
    self._pending_slot = None
    self._ring.close()

  def __del__(self):  # best-effort: never leak processes/shm segments
    try:
      self.close(timeout_secs=1.0)
    except Exception:  # pragma: no cover
      pass
