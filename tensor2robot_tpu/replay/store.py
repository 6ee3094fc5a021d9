"""Sharded replay store: the data-plane's memory tier.

The reference's QT-Opt replay was an external Google-infra service a
fleet of actors streamed grasp episodes into while Bellman updaters
sampled (SURVEY.md §3 — never open-sourced). The single-process
`research/qtopt/replay_buffer.py` ring buffer stood in for it through
round 5; this module is the production-shaped replacement underneath
it: N independent ring-buffer SHARDS, each with its own mutex, so
concurrent actor adds and learner sampling contend on different locks
(adds route whole batches round-robin across shards; a sample gathers
each shard's slice as one contiguous block under that shard's lock
only, so writers on other shards never wait on the sampler — and
concurrent samplers overlap their gathers. Within one gather the row
memcpys are already striped across cores by `native/gather.cc`).

Sampling modes (one seeded `numpy` Generator, deterministic given the
call sequence):

  * ``uniform`` — one `rng.integers` over the LIVE total, split to
    shards by cumulative size. With `num_shards=1` this performs the
    exact rng call and row gather the legacy `ReplayBuffer` performed,
    which is what keeps the thin adapter bit-identical to the old
    in-process path (pinned by tests/test_replay.py).
  * ``fifo`` — globally oldest-first by add sequence (offline replay of
    logged episodes in order); the read cursor wraps when it catches
    the writer, so the stream is infinite like the others.
  * ``prioritized`` — proportional to per-row priority (set at add
    time, e.g. per-episode TD error or success weight).

Eviction is capacity-bounded ring overwrite per shard; evicted rows can
optionally SPILL to disk as `.npz` chunks (`spill_dir`) so an online
run's overwritten history remains auditable/re-trainable instead of
vanishing. Every row carries the learner step at which it was added
(`set_learner_step`), which is what turns sampling staleness from a
docstring caveat into the measured per-batch age the sampler reports.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from tensor2robot_tpu import config as gin
from tensor2robot_tpu import specs as specs_lib
from tensor2robot_tpu.specs import TensorSpecStruct
from tensor2robot_tpu.telemetry import core as tracing
from tensor2robot_tpu.telemetry import metrics as tmetrics
from tensor2robot_tpu.utils import native

SAMPLING_MODES = ("uniform", "fifo", "prioritized")


def to_flat_arrays(transitions: Any) -> Dict[str, np.ndarray]:
  """Transition batch (TensorSpecStruct or mapping) → flat numpy dict.

  The one normalization every ingestion path shares (direct store.add,
  service.put, session staging), so the coercion semantics cannot
  drift between them.
  """
  if isinstance(transitions, TensorSpecStruct):
    flat = transitions.to_flat_dict()
  else:
    flat = dict(transitions)
  return {k: np.asarray(v) for k, v in flat.items()}


def _record_event(name: str) -> None:
  """Best-effort jax.monitoring tap (same channel as CompileWatch)."""
  try:
    import jax.monitoring as monitoring
    monitoring.record_event(name)
  except Exception:  # noqa: BLE001 — instrumentation must never raise
    pass


class _Shard:
  """One ring buffer: storage + per-row metadata under one mutex."""

  __slots__ = ("storage", "add_step", "add_seq", "priority", "lock",
               "insert", "size", "cursor")

  def __init__(self, flat_spec: Dict[str, Any], capacity: int):
    self.storage: Dict[str, np.ndarray] = {}
    for key, spec in flat_spec.items():
      self.storage[key] = np.zeros(
          (capacity,) + tuple(spec.shape), dtype=spec.dtype)
    self.add_step = np.zeros((capacity,), np.int64)   # learner step at add
    self.add_seq = np.zeros((capacity,), np.int64)    # global add order
    self.priority = np.zeros((capacity,), np.float64)
    self.lock = threading.Lock()
    self.insert = 0
    self.size = 0
    self.cursor = 0  # FIFO read position (rows consumed mod size)


@gin.configurable
class ReplayStore:
  """Sharded, capacity-bounded transition store with seeded sampling."""

  def __init__(self,
               transition_spec: TensorSpecStruct,
               capacity: int = 100_000,
               num_shards: int = 1,
               seed: int = 0,
               sampling: str = "uniform",
               spill_dir: Optional[str] = None):
    """Args:
      transition_spec: flat(-tenable) spec of one transition row.
      capacity: TOTAL row capacity; each shard holds capacity//num_shards
        (the remainder is dropped — capacity must be >= num_shards).
      num_shards: independent ring buffers (per-shard locks).
      seed: sampler determinism (one Generator for the whole store).
      sampling: "uniform" | "fifo" | "prioritized".
      spill_dir: when set, rows evicted by ring overwrite are saved as
        npz chunks here instead of being silently lost.
    """
    if sampling not in SAMPLING_MODES:
      raise ValueError(
          f"sampling must be one of {SAMPLING_MODES}, got {sampling!r}")
    if num_shards < 1:
      raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if capacity < num_shards:
      raise ValueError(
          f"capacity {capacity} < num_shards {num_shards}: every shard "
          "needs at least one row.")
    self._spec = specs_lib.flatten_spec_structure(transition_spec)
    self._flat_spec = dict(self._spec.to_flat_dict())
    self._num_shards = int(num_shards)
    self._shard_capacity = int(capacity) // self._num_shards
    self._capacity = self._shard_capacity * self._num_shards
    self._sampling = sampling
    self._spill_dir = spill_dir
    self._shards = [_Shard(self._flat_spec, self._shard_capacity)
                    for _ in range(self._num_shards)]
    self._row_bytes = sum(
        store.nbytes // self._shard_capacity
        for store in self._shards[0].storage.values())
    self._rng = np.random.default_rng(seed)
    # One lock for the sampler state (rng + cross-shard bookkeeping);
    # it is never held while a shard gather runs, so adds into other
    # shards proceed concurrently with sampling.
    self._sample_lock = threading.Lock()
    self._route = 0          # round-robin add target
    self._add_seq = 0        # global monotonically increasing add order
    self._learner_step = 0
    self._spill_chunks = 0
    # Counter increments happen from many threads (actors on different
    # shards); a dedicated stats mutex keeps them exact — `+=` on an
    # int is a read-modify-write that drops updates under contention.
    self._stats_lock = threading.Lock()
    # ---- instrumentation (read via metrics_snapshot) ----
    self.adds_total = 0          # transitions
    self.add_calls = 0
    self.samples_total = 0       # transitions
    self.sample_calls = 0
    self.evictions_total = 0
    self.spilled_total = 0
    self._created = time.monotonic()
    self._last_snapshot = (time.monotonic(), 0, 0)
    # Telemetry handles cached once: the add/sample hot paths call
    # .inc()/.set() directly instead of re-resolving names through
    # the registry lock per call.
    self._tm_adds = tmetrics.counter("replay.adds")
    self._tm_samples = tmetrics.counter("replay.samples")
    self._tm_evictions = tmetrics.counter("replay.evictions")
    self._tm_fill = tmetrics.gauge("replay.fill")
    self._tm_learner_step = tmetrics.gauge("replay.learner_step")

  # ---- shape / introspection ----

  @property
  def capacity(self) -> int:
    return self._capacity

  @property
  def num_shards(self) -> int:
    return self._num_shards

  @property
  def shard_capacity(self) -> int:
    return self._shard_capacity

  @property
  def transition_spec(self) -> TensorSpecStruct:
    return self._spec

  @property
  def sampling(self) -> str:
    return self._sampling

  def __len__(self) -> int:
    return sum(s.size for s in self._shards)

  def shard_sizes(self) -> Tuple[int, ...]:
    return tuple(s.size for s in self._shards)

  # ---- learner-step plumbing (staleness source) ----

  def set_learner_step(self, step: int) -> None:
    """Tags subsequent adds with the learner's current step (an int
    assignment — safe to call every loop iteration from the trainer
    while actor threads add concurrently)."""
    self._learner_step = int(step)
    self._tm_learner_step.set(self._learner_step)

  @property
  def learner_step(self) -> int:
    return self._learner_step

  # ---- add path ----

  def add(self, transitions: Any,
          priority: Optional[float] = None) -> int:
    """Appends a BATCH of transitions ([N, ...] per key); returns N.

    The whole batch lands on ONE shard (round-robin per call), so an
    add takes exactly one shard lock — concurrent actors adding and the
    learner sampling other shards never serialize on it.
    """
    flat = to_flat_arrays(transitions)
    for key in self._flat_spec:
      if key not in flat:
        raise KeyError(f"Transition batch missing key {key!r}.")
    if priority is not None and priority < 0:
      raise ValueError(
          f"priority must be >= 0 (got {priority}): negative weights "
          "break the prioritized sampler's cumulative draw.")
    n = int(next(iter(flat.values())).shape[0])
    if n == 0:
      return 0
    if n > self._capacity:
      # Legacy total-capacity semantics: only the last `capacity` rows
      # can survive anyway.
      flat = {k: v[-self._capacity:] for k, v in flat.items()}
      n = self._capacity
    if n > self._shard_capacity and self._num_shards > 1:
      # A batch bigger than one shard SPLITS across shards instead of
      # silently truncating rows the total capacity could hold.
      for lo in range(0, n, self._shard_capacity):
        self.add({k: v[lo:lo + self._shard_capacity]
                  for k, v in flat.items()}, priority=priority)
      return n
    if n > self._shard_capacity:
      flat = {k: v[-self._shard_capacity:] for k, v in flat.items()}
      n = self._shard_capacity
    with self._sample_lock:
      shard = self._shards[self._route]
      self._route = (self._route + 1) % self._num_shards
      seq0 = self._add_seq
      self._add_seq += n
    step = self._learner_step
    prio = 1.0 if priority is None else float(priority)
    spill_payload = None
    with shard.lock:
      start = shard.insert
      idx = (start + np.arange(n)) % self._shard_capacity
      evicted = max(0, n - (self._shard_capacity - shard.size))
      if evicted and self._spill_dir:
        # Copy the doomed rows under the lock; the disk write happens
        # AFTER release — a multi-MB np.savez under the shard mutex
        # would stall every sampler/writer on this shard behind
        # filesystem latency.
        spill_idx = idx[n - evicted:]
        spill_payload = {key: native.gather_rows(store, spill_idx)
                         for key, store in shard.storage.items()}
        spill_payload["__add_step"] = shard.add_step[spill_idx].copy()
      for key, store in shard.storage.items():
        native.scatter_rows(store, idx, np.ascontiguousarray(flat[key]))
      shard.add_step[idx] = step
      shard.add_seq[idx] = seq0 + np.arange(n)
      shard.priority[idx] = prio
      shard.insert = int((start + n) % self._shard_capacity)
      shard.size = int(min(shard.size + n, self._shard_capacity))
    if spill_payload is not None:
      self._write_spill(spill_payload)
    with self._stats_lock:
      self.adds_total += n
      self.add_calls += 1
      self.evictions_total += evicted
    # Registry publication (telemetry plane): the same counters the
    # snapshot reports, visible process-wide without a store handle.
    self._tm_adds.inc(n)
    self._tm_fill.set(len(self) / max(self._capacity, 1))
    if evicted:
      self._tm_evictions.inc(evicted)
      _record_event("/t2r/replay/evict")
    return n

  def _write_spill(self, arrays: Dict[str, np.ndarray]) -> None:
    """Persists one batch of evicted rows (no locks held)."""
    os.makedirs(self._spill_dir, exist_ok=True)
    with self._stats_lock:
      chunk = self._spill_chunks
      self._spill_chunks += 1
    path = os.path.join(self._spill_dir, f"spill-{chunk:08d}.npz")
    np.savez(path + ".tmp", **arrays)
    os.replace(path + ".tmp.npz", path)
    with self._stats_lock:
      self.spilled_total += int(arrays["__add_step"].size)

  # ---- sample path ----

  def sample(self, batch_size: int) -> TensorSpecStruct:
    """A batch in the wire spec (metadata dropped)."""
    batch, _, _ = self.sample_with_ages(batch_size)
    return batch

  def sample_with_ages(self, batch_size: int,
                       out: Optional[Dict[str, np.ndarray]] = None
                       ) -> Tuple[TensorSpecStruct, np.ndarray,
                                  np.ndarray]:
    """(batch, ages_in_learner_steps [B], global_row_ids [B]).

    `ages` is the staleness measurement: learner step NOW minus the
    learner step each sampled row was added at. `global_row_ids`
    (shard * shard_capacity + slot) exist so reproducibility tests can
    digest the exact sample schedule.

    Multi-shard batches are emitted SHARD-MAJOR (rows grouped by
    shard, deterministic given the draw): each shard's slice is one
    contiguous gather under that shard's lock only, so concurrent
    adds/samples on other shards never wait — the whole point of
    sharding. Row order within a uniform/prioritized batch is
    statistically irrelevant; FIFO mode restores global oldest-first
    order (its contract) at the cost of one permutation. The gather
    itself is already striped across cores inside `native.gather_rows`,
    which is why there is no per-shard thread fan-out here.

    Without `out` the batch is a set of fresh arrays that belong to
    the caller. With `out`, a flat dict with every key of the wire
    spec → a C-contiguous `[batch_size, ...]` array of the store's
    dtype, the rows are gathered into those arrays and the batch's
    leaves ARE them: same draw, same rows, same order, no allocation
    (memory the caller has written before costs a copy; memory fresh
    from the allocator a page fault for every 4 KB). An `out` that does
    not fit raises before the draw and before any write. Multi-shard
    FIFO, which ends on a permutation, returns its own arrays whatever
    it is given: the caller tells by identity.
    """
    if out is not None:
      self._check_out(batch_size, out)
    with tracing.span("replay.draw", rows=batch_size):
      shard_ids, local = self._draw(batch_size)
    # `native`: the library is loaded; a store's arrays are contiguous,
    # so it then serves every gather here (`native.gather_rows` counts
    # rows by path for the cases where it cannot).
    with tracing.span("replay.gather", rows=batch_size,
                      bytes=batch_size * self._row_bytes,
                      native=native.native_available()):
      out, ages, row_ids = self._gather(batch_size, shard_ids, local,
                                        out)
    with self._stats_lock:
      self.samples_total += batch_size
      self.sample_calls += 1
    self._tm_samples.inc(batch_size)
    np.maximum(ages, 0, out=ages)  # adds race the step tag by design
    return TensorSpecStruct.from_flat_dict(out), ages, row_ids

  def _check_out(self, batch_size: int,
                 out: Dict[str, np.ndarray]) -> None:
    """Raises unless `out` can take a batch as it is: checked for every
    key before the first row is written to any."""
    if set(out) != set(self._flat_spec):
      raise ValueError(
          f"sample_with_ages: out has keys {sorted(out)}, the store "
          f"{sorted(self._flat_spec)}.")
    for key, store in self._shards[0].storage.items():
      arr = out[key]
      want = (batch_size,) + store.shape[1:]
      if (not isinstance(arr, np.ndarray) or arr.shape != want
          or arr.dtype != store.dtype or not arr.flags.c_contiguous
          or not arr.flags.writeable):
        raise ValueError(
            f"sample_with_ages: out[{key!r}] must be a writable "
            f"C-contiguous array of {want}/{store.dtype}, got "
            f"{getattr(arr, 'shape', None)}/"
            f"{getattr(arr, 'dtype', type(arr).__name__)}.")

  def _draw(self, batch_size: int) -> Tuple[np.ndarray, np.ndarray]:
    """(shard ids, slots in the shard) of one batch, under the
    sampler's lock."""
    with self._sample_lock:
      sizes = [s.size for s in self._shards]
      total = sum(sizes)
      if total == 0:
        raise ValueError("Cannot sample from an empty replay store.")
      if self._sampling == "uniform":
        shard_ids, local = self._draw_uniform(batch_size, sizes, total)
      elif self._sampling == "prioritized":
        shard_ids, local = self._draw_prioritized(batch_size, sizes)
      else:
        # FIFO's oldest-first contract needs a CONSISTENT view of every
        # shard's insert/add_seq while the draw walks them: take all
        # shard locks in index order (no other path holds one shard
        # lock while acquiring another, so the order cannot deadlock)
        # and re-snapshot sizes under them. FIFO is the offline-replay
        # mode; this is not the online hot path.
        for sh in self._shards:
          sh.lock.acquire()
        try:
          sizes = [s.size for s in self._shards]
          shard_ids, local = self._draw_fifo(batch_size, sizes)
        finally:
          for sh in self._shards:
            sh.lock.release()
    return shard_ids, local

  def _gather(self, batch_size: int, shard_ids: np.ndarray,
              local: np.ndarray,
              out: Optional[Dict[str, np.ndarray]] = None):
    """(rows by key, ages, global row ids) of the drawn slots, each
    shard's slice under that shard's lock only; the rows in `out`'s
    arrays where it is given (`_check_out` has passed it)."""
    now = self._learner_step
    if self._num_shards == 1:
      # The legacy-exact path: one gather, draw order preserved.
      shard = self._shards[0]
      with shard.lock:
        out = {key: native.gather_rows(
            store, local, out=None if out is None else out[key])
               for key, store in shard.storage.items()}
        ages = now - shard.add_step[local]
        row_ids = local.copy()
    else:
      order = np.argsort(shard_ids, kind="stable")
      sorted_local = local[order]
      if out is None or self._sampling == "fifo":
        out = {key: np.empty((batch_size,) + store.shape[1:],
                             dtype=store.dtype)
               for key, store in self._shards[0].storage.items()}
      else:  # in the store's key order, as without `out`
        out = {key: out[key] for key in self._shards[0].storage}
      ages = np.empty((batch_size,), np.int64)
      row_ids = np.empty((batch_size,), np.int64)
      counts = np.bincount(shard_ids, minlength=self._num_shards)
      lo = 0
      for s in range(self._num_shards):
        hi = lo + int(counts[s])
        if hi == lo:
          continue
        idx = sorted_local[lo:hi]
        shard = self._shards[s]
        with shard.lock:
          for key, store in shard.storage.items():
            # Slice gathers run single-threaded BY DESIGN: a sharded
            # store's parallelism comes from concurrent callers and
            # writers on other shards (that is why you shard) — letting
            # every slice also fan out native threads oversubscribes
            # the cores the concurrent callers are using (measured
            # slower under load). The 1-shard path above keeps the
            # intra-gather striping.
            native.gather_rows(store, idx, out=out[key][lo:hi],
                               num_threads=1)
          ages[lo:hi] = now - shard.add_step[idx]
        row_ids[lo:hi] = s * self._shard_capacity + idx
        lo = hi
      if self._sampling == "fifo":
        # FIFO's contract is global oldest-first: undo the shard-major
        # grouping back to the draw order.
        inverse = np.empty_like(order)
        inverse[order] = np.arange(batch_size)
        out = {key: arr[inverse] for key, arr in out.items()}
        ages = ages[inverse]
        row_ids = row_ids[inverse]
    return out, ages, row_ids

  def _draw_uniform(self, batch: int, sizes: List[int], total: int):
    """One rng call over the live total (the legacy-exact draw)."""
    flat = self._rng.integers(0, total, size=batch)
    if self._num_shards == 1:
      return np.zeros(batch, np.int64), flat
    cum = np.cumsum(sizes)
    shard_ids = np.searchsorted(cum, flat, side="right")
    offsets = cum - np.asarray(sizes)
    return shard_ids, flat - offsets[shard_ids]

  def _draw_prioritized(self, batch: int, sizes: List[int]):
    """Proportional to per-row priority across every live row."""
    parts = []
    for s, shard in enumerate(self._shards):
      if sizes[s]:
        parts.append(shard.priority[:sizes[s]])
    weights = np.concatenate(parts) if parts else np.zeros(0)
    cum = np.cumsum(weights)
    if cum[-1] <= 0:
      flat = self._rng.integers(0, int(sum(sizes)), size=batch)
    else:
      flat = np.searchsorted(cum,
                             self._rng.random(batch) * cum[-1],
                             side="right")
      flat = np.minimum(flat, len(weights) - 1)
    cumsize = np.cumsum(sizes)
    shard_ids = np.searchsorted(cumsize, flat, side="right")
    offsets = cumsize - np.asarray(sizes)
    return shard_ids, flat - offsets[shard_ids]

  def _draw_fifo(self, batch: int, sizes: List[int]):
    """Globally oldest-first by add sequence; wraps when exhausted.

    Per-shard: the oldest live row sits at insert-size (mod cap);
    `cursor` counts rows consumed since then. Each draw takes the
    smallest next add_seq among shards with UNREAD rows; only when
    every live shard is fully read do all cursors reset together — a
    per-shard reset would let a wrapped shard's old rows jump ahead
    of another shard's unread ones.
    """
    shard_ids = np.empty(batch, np.int64)
    local = np.empty(batch, np.int64)
    for i in range(batch):
      if all(self._shards[s].cursor >= sizes[s]
             for s in range(self._num_shards) if sizes[s]):
        for shard in self._shards:
          shard.cursor = 0  # full pass done: restart from the oldest
      best, best_seq = -1, None
      for s, shard in enumerate(self._shards):
        if sizes[s] == 0 or shard.cursor >= sizes[s]:
          continue
        pos = (shard.insert - sizes[s] + shard.cursor) \
            % self._shard_capacity
        seq = shard.add_seq[pos]
        if best_seq is None or seq < best_seq:
          best, best_seq = s, seq
      shard = self._shards[best]
      pos = (shard.insert - sizes[best] + shard.cursor) \
          % self._shard_capacity
      shard_ids[i] = best
      local[i] = pos
      shard.cursor += 1
    return shard_ids, local

  # ---- warmup / metrics ----

  def wait_until_size(self, min_size: int,
                      timeout_secs: Optional[float] = None) -> bool:
    """Blocks until `min_size` transitions are live (actor warmup)."""
    deadline = (time.monotonic() + timeout_secs
                if timeout_secs is not None else None)
    while len(self) < min_size:
      if deadline is not None and time.monotonic() > deadline:
        return False
      time.sleep(0.01)
    return True

  def metrics_snapshot(self) -> Dict[str, float]:
    """Cumulative counters + instantaneous fill; cheap, lock-free."""
    size = len(self)
    return {
        "size": float(size),
        "capacity": float(self._capacity),
        "fill": size / max(self._capacity, 1),
        "num_shards": float(self._num_shards),
        "adds_total": float(self.adds_total),
        "samples_total": float(self.samples_total),
        "evictions_total": float(self.evictions_total),
        "spilled_total": float(self.spilled_total),
        "learner_step": float(self._learner_step),
    }

  def metrics_scalars(self, prefix: str = "replay_") -> Dict[str, float]:
    """Windowed rates since the previous call (the train-log shape:
    one call per log interval alongside `stall_fraction`)."""
    now = time.monotonic()
    t0, adds0, samples0 = self._last_snapshot
    dt = max(now - t0, 1e-9)
    adds, samples = self.adds_total, self.samples_total
    self._last_snapshot = (now, adds, samples)
    size = len(self)
    return {
        f"{prefix}fill": size / max(self._capacity, 1),
        f"{prefix}size": float(size),
        f"{prefix}adds_per_sec": (adds - adds0) / dt,
        f"{prefix}samples_per_sec": (samples - samples0) / dt,
        f"{prefix}evictions_total": float(self.evictions_total),
        f"{prefix}spilled_total": float(self.spilled_total),
    }
