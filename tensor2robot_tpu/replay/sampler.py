"""Streaming sampler: store → fixed-wire-spec batches → prefetcher.

The learner-facing edge of the data plane. `ReplayBatchSampler` is an
infinite iterator of `TensorSpecStruct` batches in the store's wire
spec — exactly what `data.prefetch.ShardedPrefetcher` consumes — and it
is where sampling STALENESS becomes a measured quantity: every batch's
per-row age (learner step at sample minus learner step at add, via the
store's `set_learner_step` tag) lands in a fixed-bucket histogram the
trainer logs alongside `stall_fraction`.

Round-5 context: the K>1 online caveat in `train_qtopt` said the last
step of a dispatch can train on samples up to ~3K parameter updates
old, and could only say it in prose. With the trainer tagging the store
each iteration, `staleness_snapshot()` reports the real distribution —
and the dispatch-depth / K trade-off becomes tunable against data
instead of a docstring.

The sampler can also record a SCHEDULE DIGEST — a running SHA-256 over
the exact global row ids drawn — which is what the seeded
success-protocol reproducibility check compares across runs (two runs
with the same seeds must produce identical digests).
"""

from __future__ import annotations

import hashlib
import threading
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

from tensor2robot_tpu import config as gin
from tensor2robot_tpu.replay.store import ReplayStore, to_flat_arrays
from tensor2robot_tpu.specs import TensorSpecStruct
from tensor2robot_tpu.telemetry import metrics as tmetrics

# Fixed bucket EDGES (upper bounds, in learner steps) so histograms are
# comparable across runs and JSON-stable; the last bucket is open.
STALENESS_BUCKETS: Tuple[int, ...] = (
    0, 1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 4096, 16384)


@gin.configurable
class ReplayBatchSampler:
  """Infinite fixed-batch sampling stream with staleness accounting."""

  def __init__(self,
               store: ReplayStore,
               batch_size: int,
               record_schedule: bool = False):
    self._store = store
    self._batch_size = int(batch_size)
    self._record_schedule = record_schedule
    self._digest = hashlib.sha256()
    self._lock = threading.Lock()
    self._counts = np.zeros(len(STALENESS_BUCKETS) + 1, np.int64)
    self._age_sum = 0
    self._age_max = 0
    self._rows = 0
    self._batches = 0
    # Per-batch mean ages in a fixed RING (not an append-capped list:
    # that would freeze the "recent" p95 on the run's first window
    # forever) — 65536 batches of history bounds memory while the p95
    # tracks the live distribution on long runs.
    self._recent_means = np.zeros(65536, np.float64)
    self._recent_count = 0
    self._lent: Optional[Dict[str, np.ndarray]] = None
    self._tm_staleness = tmetrics.histogram(
        "replay.staleness_steps", tmetrics.DEFAULT_STEP_BOUNDS)

  @property
  def batch_size(self) -> int:
    return self._batch_size

  @property
  def store(self) -> ReplayStore:
    return self._store

  @property
  def wire_spec(self) -> TensorSpecStruct:
    """The fixed wire spec every emitted batch conforms to."""
    return self._store.transition_spec

  def gather_next_into(self, batch) -> None:
    """Lends the arrays of `batch` (a batch as `sample` returns one:
    the wire spec's keys → `[batch_size, ...]` arrays) to the next
    `sample`, which gathers its rows into them and returns them
    (`ReplayStore.sample_with_ages(out=)`). For that one sample only,
    whether it succeeds or raises. It passes beside the iterator
    protocol, which carries nothing towards the source, so a wrapper
    that forwards only `next` need not know; the caller is the thread
    that pulls the stream."""
    self._lent = to_flat_arrays(batch)

  def sample(self) -> TensorSpecStruct:
    """One batch; staleness and (optionally) the schedule recorded."""
    out, self._lent = self._lent, None
    batch, ages, row_ids = self._store.sample_with_ages(
        self._batch_size, out=out)
    with self._lock:
      self._counts += np.bincount(
          np.searchsorted(STALENESS_BUCKETS, ages, side="left"),
          minlength=len(self._counts))[:len(self._counts)]
      self._age_sum += int(ages.sum())
      self._age_max = max(self._age_max, int(ages.max()))
      self._rows += ages.size
      self._batches += 1
      self._recent_means[
          self._recent_count % self._recent_means.size] = ages.mean()
      self._recent_count += 1
      if self._record_schedule:
        self._digest.update(row_ids.tobytes())
    # Registry publication: per-batch mean age into the step-bucket
    # histogram (the telemetry-plane view of the same distribution).
    self._tm_staleness.observe(float(ages.mean()))
    return batch

  def __iter__(self) -> Iterator[TensorSpecStruct]:
    while True:
      yield self.sample()

  # Alias so the adapter's legacy `as_stream` shape reads naturally.
  def as_stream(self) -> Iterator[TensorSpecStruct]:
    return iter(self)

  # ---- reproducibility ----

  def schedule_digest(self) -> str:
    """SHA-256 over every (shard, slot) drawn so far, in order."""
    if not self._record_schedule:
      raise RuntimeError(
          "schedule recording is off; construct with "
          "record_schedule=True")
    with self._lock:
      return self._digest.hexdigest()

  # ---- staleness reporting ----

  def staleness_snapshot(self) -> Dict[str, object]:
    """The measured staleness distribution since construction.

    `histogram` maps bucket upper-bound labels ("<=8", ..., ">16384")
    to sampled-row counts; ages are in LEARNER STEPS (sample-time step
    minus add-time step), so an offline buffer reads as all-zero ages
    until training begins and grows linearly after — the online regime
    is the signal this exists for.
    """
    with self._lock:
      labels = [f"<={b}" for b in STALENESS_BUCKETS] + [
          f">{STALENESS_BUCKETS[-1]}"]
      hist = {label: int(c) for label, c in zip(labels, self._counts)}
      mean = self._age_sum / self._rows if self._rows else 0.0
      live = self._recent_means[
          :min(self._recent_count, self._recent_means.size)]
      p95 = float(np.percentile(live, 95)) if live.size else 0.0
      return {
          "histogram": hist,
          "mean_age_steps": mean,
          "max_age_steps": self._age_max,
          "batch_mean_age_p95_steps": p95,
          "rows": self._rows,
          "batches": self._batches,
      }

  def metrics_scalars(self, prefix: str = "replay_") -> Dict[str, float]:
    """The scalar cut of the snapshot, shaped for the train log."""
    snap = self.staleness_snapshot()
    return {
        f"{prefix}staleness_mean_steps": float(snap["mean_age_steps"]),
        f"{prefix}staleness_max_steps": float(snap["max_age_steps"]),
        f"{prefix}staleness_batch_p95_steps": float(
            snap["batch_mean_age_p95_steps"]),
        f"{prefix}sampled_batches": float(snap["batches"]),
    }


def make_stream(store: ReplayStore, batch_size: int,
                record_schedule: bool = False
                ) -> Tuple[Iterator[TensorSpecStruct],
                           ReplayBatchSampler]:
  """(iterator, sampler) — the iterator feeds `ShardedPrefetcher`, the
  sampler handle stays with the trainer for staleness/metrics reads."""
  sampler = ReplayBatchSampler(store, batch_size,
                               record_schedule=record_schedule)
  return iter(sampler), sampler


# ---- cross-shard fan-out (ISSUE 16: the sharded replay plane) ----
#
# With one shard per replay HOST, a learner batch is assembled from
# per-shard sample RPCs instead of one store gather. These two pure
# helpers define that assembly; `fleet.learner.RemoteReplay` applies
# them over its shard clients. The result obeys the PR-3 gather
# contract — rows grouped by shard, shards in index order (SHARD-MAJOR)
# — so a cross-host batch has the same layout an in-process
# multi-shard `sample_with_ages` gather produces.


def shard_fanout_counts(batch_size: int,
                        shard_sizes: Tuple[int, ...]) -> Tuple[int, ...]:
  """Per-shard sample counts, proportional to shard fill.

  Mirrors the in-store multi-shard draw (uniform over the TOTAL
  population → expected counts proportional to shard sizes) with a
  deterministic largest-remainder rounding: quotas floor, and the
  leftover rows go to the largest fractional remainders (ties to the
  lower shard index). Empty shards draw zero — a fleet whose actors
  all hash to one shard still samples correctly.
  """
  sizes = [max(0, int(s)) for s in shard_sizes]
  total = sum(sizes)
  if batch_size < 0:
    raise ValueError(f"batch_size must be >= 0, got {batch_size}")
  if total == 0:
    raise ValueError("cannot allocate a sample batch: every shard "
                     "is empty")
  quotas = [batch_size * s / total for s in sizes]
  counts = [int(q) for q in quotas]
  remainders = sorted(
      range(len(sizes)), key=lambda i: (counts[i] - quotas[i], i))
  for i in remainders[:batch_size - sum(counts)]:
    counts[i] += 1
  return tuple(counts)


def concat_shard_major(
    parts: "list[Dict[str, np.ndarray]]") -> Dict[str, np.ndarray]:
  """Concatenates per-shard flat sample dicts in shard-index order."""
  if not parts:
    raise ValueError("no shard produced rows for this batch")
  if len(parts) == 1:
    return dict(parts[0])
  return {key: np.concatenate([part[key] for part in parts], axis=0)
          for key in parts[0]}


# ---- rendezvous hashing (ISSUE 17: the shared HRW seam) ----
#
# The replay plane homes actors on shards with highest-random-weight
# hashing (`fleet.actor.home_shard`); the replicated serving tier
# places tenants on front replicas with the SAME rule. These helpers
# are the canonical form, generalized to an arbitrary bucket-id set so
# a router can rank over the SURVIVORS after a replica death. The salt
# is byte-identical to `home_shard`'s (`"{key}|shard-{i}"`), and
# tests/test_serving_router.py pins `rendezvous_choose(k, range(n)) ==
# home_shard(k, n)` so the two modules (actor.py must stay jax-free
# and cannot import this one) can never drift.


def rendezvous_weight(key: str, bucket: int) -> int:
  """The deterministic pseudo-random weight of (key, bucket)."""
  digest = hashlib.sha256(f"{key}|shard-{bucket}".encode()).digest()
  return int.from_bytes(digest[:8], "big")


def rendezvous_rank(key: str,
                    buckets: "Iterable[int]") -> "list[int]":
  """Buckets sorted by descending HRW weight for `key`.

  The operational property (pinned): removing a bucket deletes its
  entry from every key's ranking and changes NOTHING else — so only
  keys whose top choice was the removed bucket remap, and each key's
  fallback order is stable under further membership changes.
  """
  members = sorted(set(int(b) for b in buckets))
  if not members:
    raise ValueError("rendezvous_rank needs at least one bucket")
  return sorted(members,
                key=lambda b: rendezvous_weight(key, b),
                reverse=True)


def rendezvous_choose(key: str, buckets: "Iterable[int]") -> int:
  """The HRW winner — `home_shard` over an arbitrary bucket set."""
  return rendezvous_rank(key, buckets)[0]


def rendezvous_spread(key: str, buckets: "Iterable[int]",
                      k: int) -> "list[int]":
  """The top-`k` buckets for `key` — a hot tenant spread over k
  replicas. `k` is clamped to the membership size; order is the
  failover order (index 0 is the HRW home)."""
  if k < 1:
    raise ValueError(f"k must be >= 1, got {k}")
  return rendezvous_rank(key, buckets)[:k]
