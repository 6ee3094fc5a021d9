"""Distributed replay data plane (actors → service → store → learner).

The QT-Opt workload is online RL: a fleet of actors streams transitions
into replay while the learner samples from it (SURVEY.md §3; Podracer,
arXiv:2104.06272). This package is that layer, host-side and
production-shaped:

  * `store`    — sharded ring-buffer memory tier (per-shard locks,
                 uniform/FIFO/prioritized seeded sampling, bounded
                 eviction with optional disk spill, per-row add-step
                 tags for staleness).
  * `service`  — multi-producer ingestion front (bounded queue with
                 explicit backpressure or drop-and-count overflow,
                 per-actor sessions whose episodes commit atomically,
                 crash/restart survival).
  * `sampler`  — fixed-wire-spec streaming sampler feeding
                 `data.prefetch.ShardedPrefetcher`, with the measured
                 per-batch staleness histogram and a schedule digest
                 for reproducibility checks.

`research/qtopt/replay_buffer.ReplayBuffer` remains the thin
API-compatible adapter over a 1-shard store; its gather feeds both
QT-Opt cells of BENCHMARK.json (`feed_*`); the plane under actor load
has no measurement on the chip (ROADMAP W4). See docs/REPLAY.md.
"""

from tensor2robot_tpu.replay.sampler import (
    STALENESS_BUCKETS,
    ReplayBatchSampler,
    make_stream,
)
from tensor2robot_tpu.replay.service import (
    ActorIngestSession,
    ReplayWriteService,
)
from tensor2robot_tpu.replay.store import ReplayStore
