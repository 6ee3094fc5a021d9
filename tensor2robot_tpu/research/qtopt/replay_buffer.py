"""ReplayBuffer: thin API-compatible adapter over the replay data plane.

Through round 5 this module WAS the replay system — a single-process
numpy ring buffer. The sharded store / ingestion service / streaming
sampler now live in `tensor2robot_tpu/replay/`; this class keeps the
old call surface (`add` / `sample` / `as_stream` / `wait_until_size`)
so every existing caller and gin config keeps working, delegating to a
`ReplayStore` underneath.

Compatibility contract (pinned by tests/test_replay.py): with the
defaults (one shard, uniform sampling) the adapter is BIT-IDENTICAL to
the legacy buffer — same seeded rng call per sample, same physical row
layout, same gather — so a training run through it reproduces the old
in-process path exactly. The new capabilities (shards, prioritized/FIFO
sampling, eviction spill, staleness metrics) are opt-in constructor
args and passthroughs.
"""

from __future__ import annotations

from typing import Dict, Iterator, Optional

from tensor2robot_tpu import config as gin
from tensor2robot_tpu.replay import ReplayBatchSampler, ReplayStore
from tensor2robot_tpu.specs import TensorSpecStruct


@gin.configurable
class ReplayBuffer:
  """Uniform-sampling ring buffer API over the sharded `ReplayStore`."""

  def __init__(self, transition_spec: TensorSpecStruct,
               capacity: int = 100_000, seed: int = 0,
               num_shards: int = 1, sampling: str = "uniform",
               spill_dir: Optional[str] = None):
    self._store = ReplayStore(
        transition_spec, capacity=capacity, num_shards=num_shards,
        seed=seed, sampling=sampling, spill_dir=spill_dir)
    self._stream_sampler: Optional[ReplayBatchSampler] = None

  def __len__(self) -> int:
    return len(self._store)

  @property
  def capacity(self) -> int:
    return self._store.capacity

  @property
  def store(self) -> ReplayStore:
    """The underlying data-plane store (service attachment point)."""
    return self._store

  def add(self, transitions: TensorSpecStruct,
          priority: Optional[float] = None) -> None:
    """Appends a BATCH of transitions (dict/struct of [N, ...] arrays)."""
    self._store.add(transitions, priority=priority)

  def sample(self, batch_size: int) -> TensorSpecStruct:
    """Seeded random batch (empty buffer raises, as before)."""
    try:
      return self._store.sample(batch_size)
    except ValueError as e:
      # Legacy message said "replay buffer"; keep tests/callers happy.
      raise ValueError(
          "Cannot sample from an empty replay buffer.") from e

  def as_stream(self, batch_size: int) -> Iterator[TensorSpecStruct]:
    """Infinite sampling stream (feeds ShardedPrefetcher).

    The stream's sampler handle is kept so `metrics_scalars` /
    `staleness_snapshot` report the live training stream's staleness.
    """
    self._stream_sampler = ReplayBatchSampler(self._store, batch_size)
    return iter(self._stream_sampler)

  def gather_next_into(self, batch: TensorSpecStruct) -> None:
    """Lends `batch`'s arrays to the next sample of the stream that
    `as_stream` made last (`ReplayBatchSampler.gather_next_into`):
    how `data.prefetch.StackedBatchStream` has a batch gathered
    straight into its slice of a dispatch."""
    if self._stream_sampler is not None:
      self._stream_sampler.gather_next_into(batch)

  def wait_until_size(self, min_size: int,
                      timeout_secs: Optional[float] = None) -> bool:
    """Blocks until `min_size` transitions are buffered (actor warmup)."""
    return self._store.wait_until_size(min_size, timeout_secs)

  # ---- data-plane passthroughs (new capability, optional to use) ----

  def set_learner_step(self, step: int) -> None:
    """Tags subsequent adds with the learner step (staleness source)."""
    self._store.set_learner_step(step)

  def metrics_scalars(self, prefix: str = "replay_") -> Dict[str, float]:
    """Store fill/throughput + stream staleness, for the train log."""
    out = self._store.metrics_scalars(prefix=prefix)
    if self._stream_sampler is not None:
      out.update(self._stream_sampler.metrics_scalars(prefix=prefix))
    return out

  def staleness_snapshot(self) -> Optional[Dict[str, object]]:
    if self._stream_sampler is None:
      return None
    return self._stream_sampler.staleness_snapshot()
