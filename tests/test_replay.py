"""Tests for the replay data plane: store, service, sampler, rewiring.

The contracts under pin:
  * the sharded `ReplayStore`'s 1-shard uniform mode is BIT-IDENTICAL
    to the legacy in-process ring buffer (an inline copy of the
    retired 106-line implementation is the oracle), and a full QT-Opt
    training run through the new plane reproduces the legacy path's
    parameters exactly;
  * failure paths: an actor crash mid-episode leaves the store
    consistent (no partial episode), queue overflow increments drop
    counters and never blocks the learner, and a crashed actor's
    restart resumes ingestion;
  * the staleness metric measures what it claims (known-age fixtures);
  * the prefetch lookahead depth defaults to 1 in the online regime
    (the round-5 K>1 sampling-lead finding) and is configurable;
  * under concurrent actor commits and sampler threads, at one and at
    two shards, every sampled row is whole and every commit lands.
"""

import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from tensor2robot_tpu.replay import (
    STALENESS_BUCKETS,
    ReplayBatchSampler,
    ReplayStore,
    ReplayWriteService,
    make_stream,
)
from tensor2robot_tpu.research.qtopt import (
    GraspActor,
    GraspingQModel,
    QTOptLearner,
    ReplayBuffer,
    ToyGraspEnv,
    train_qtopt,
)
from tensor2robot_tpu.specs import TensorSpecStruct, make_random_tensors
from tensor2robot_tpu.telemetry.records import read_records

RNG = jax.random.PRNGKey(0)


def _tiny_learner(**kwargs):
  model = GraspingQModel(
      image_size=16, torso_filters=(8,), head_filters=(8,),
      dense_sizes=(16,), action_dim=2, **kwargs)
  return QTOptLearner(model, cem_population=8, cem_iterations=1,
                      cem_elites=2)


def _spec():
  return _tiny_learner().transition_specification()


class _LegacyReplayBuffer:
  """The retired single-process ring buffer, verbatim semantics — the
  oracle the adapter/store must match bit-for-bit at one shard."""

  def __init__(self, transition_spec, capacity=100_000, seed=0):
    from tensor2robot_tpu import specs as specs_lib

    self._spec = specs_lib.flatten_spec_structure(transition_spec)
    self._capacity = int(capacity)
    self._storage = {}
    for key, spec in self._spec.to_flat_dict().items():
      self._storage[key] = np.zeros(
          (self._capacity,) + tuple(spec.shape), dtype=spec.dtype)
    self._rng = np.random.default_rng(seed)
    self._insert_index = 0
    self._size = 0

  def __len__(self):
    return self._size

  @property
  def capacity(self):
    return self._capacity

  def add(self, transitions):
    flat = (transitions.to_flat_dict()
            if isinstance(transitions, TensorSpecStruct)
            else dict(transitions))
    n = next(iter(flat.values())).shape[0]
    if n > self._capacity:
      flat = {k: v[-self._capacity:] for k, v in flat.items()}
      n = self._capacity
    start = self._insert_index
    idx = (start + np.arange(n)) % self._capacity
    for key, store in self._storage.items():
      store[idx] = np.ascontiguousarray(flat[key])
    self._insert_index = int((start + n) % self._capacity)
    self._size = int(min(self._size + n, self._capacity))

  def sample(self, batch_size):
    idx = self._rng.integers(0, self._size, size=batch_size)
    return TensorSpecStruct.from_flat_dict(
        {key: store[idx] for key, store in self._storage.items()})

  def as_stream(self, batch_size):
    while True:
      yield self.sample(batch_size)

  def wait_until_size(self, min_size, timeout_secs=None):
    return self._size >= min_size


class TestReplayStore:

  def test_add_sample_round_trip_wire_dtypes(self):
    store = ReplayStore(_spec(), capacity=64, num_shards=2)
    store.add(make_random_tensors(_spec(), batch_size=32, seed=0))
    assert len(store) == 32
    flat = store.sample(16).to_flat_dict()
    assert flat["image"].shape == (16, 16, 16, 3)
    assert flat["image"].dtype == np.uint8  # stored in wire dtype

  def test_shard_routing_balances(self):
    store = ReplayStore(_spec(), capacity=256, num_shards=4)
    for i in range(4):
      store.add(make_random_tensors(_spec(), batch_size=16, seed=i))
    assert store.shard_sizes() == (16, 16, 16, 16)

  def test_eviction_counted_on_wraparound(self):
    store = ReplayStore(_spec(), capacity=16, num_shards=1)
    for seed in range(3):
      store.add(make_random_tensors(_spec(), batch_size=10, seed=seed))
    assert len(store) == 16
    assert store.evictions_total == 14  # 30 added, 16 live

  def test_batch_larger_than_shard_keeps_tail(self):
    store = ReplayStore(_spec(), capacity=8, num_shards=1)
    batch = make_random_tensors(_spec(), batch_size=20, seed=0)
    store.add(batch)
    assert len(store) == 8
    sampled = store.sample(4).to_flat_dict()["image"]
    # Every sampled row must come from the LAST 8 rows of the batch.
    tail = batch.to_flat_dict()["image"][-8:]
    for row in sampled:
      assert any(np.array_equal(row, t) for t in tail)

  def test_oversized_batch_splits_across_shards(self):
    """A batch bigger than one shard must use the TOTAL capacity
    (split round-robin), not silently truncate to shard capacity."""
    store = ReplayStore(_spec(), capacity=64, num_shards=2, seed=0)
    store.add(make_random_tensors(_spec(), batch_size=48, seed=0))
    assert len(store) == 48
    assert store.evictions_total == 0
    assert set(store.shard_sizes()) == {32, 16}

  def test_negative_priority_raises(self):
    store = ReplayStore(_spec(), capacity=32, sampling="prioritized")
    with pytest.raises(ValueError, match="priority"):
      store.add(make_random_tensors(_spec(), batch_size=4, seed=0),
                priority=-2.0)

  def test_missing_key_and_empty_raise(self):
    store = ReplayStore(_spec(), capacity=8)
    with pytest.raises(KeyError):
      store.add({"image": np.zeros((2, 16, 16, 3), np.uint8)})
    with pytest.raises(ValueError, match="empty"):
      store.sample(2)

  def test_one_shard_uniform_bitwise_matches_legacy(self):
    """The adapter's compatibility contract: same seeded rng call,
    same physical layout, same rows — across interleaved adds and
    wraparound."""
    legacy = _LegacyReplayBuffer(_spec(), capacity=48, seed=7)
    store = ReplayStore(_spec(), capacity=48, num_shards=1, seed=7)
    for seed in range(4):
      batch = make_random_tensors(_spec(), batch_size=20, seed=seed)
      legacy.add(batch)
      store.add(batch)
      a = legacy.sample(16).to_flat_dict()
      b = store.sample(16).to_flat_dict()
      assert set(a) == set(b)
      for key in a:
        np.testing.assert_array_equal(a[key], b[key], err_msg=key)

  def test_fifo_returns_oldest_first(self):
    store = ReplayStore(_spec(), capacity=64, num_shards=2,
                        sampling="fifo")
    flat0 = make_random_tensors(_spec(), batch_size=8, seed=0)
    flat1 = make_random_tensors(_spec(), batch_size=8, seed=1)
    store.add(flat0)  # shard 0, add_seq 0..7
    store.add(flat1)  # shard 1, add_seq 8..15
    batch = store.sample(8).to_flat_dict()
    np.testing.assert_array_equal(batch["image"],
                                  flat0.to_flat_dict()["image"])
    batch2 = store.sample(8).to_flat_dict()
    np.testing.assert_array_equal(batch2["image"],
                                  flat1.to_flat_dict()["image"])
    # Exhausted: wraps back to the oldest live rows.
    batch3 = store.sample(8).to_flat_dict()
    np.testing.assert_array_equal(batch3["image"],
                                  flat0.to_flat_dict()["image"])

  def test_prioritized_sampling_biases_toward_priority(self):
    store = ReplayStore(_spec(), capacity=128, num_shards=2, seed=0,
                        sampling="prioritized")
    store.add(make_random_tensors(_spec(), batch_size=32, seed=0),
              priority=1.0)   # shard 0
    store.add(make_random_tensors(_spec(), batch_size=32, seed=1),
              priority=9.0)   # shard 1
    _, _, row_ids = store.sample_with_ages(512)
    high = np.mean(row_ids >= store.shard_capacity)
    assert 0.8 < high < 1.0  # ~0.9 expected

  def test_spill_preserves_evicted_rows(self, tmp_path):
    spill = str(tmp_path / "spill")
    store = ReplayStore(_spec(), capacity=8, num_shards=1, seed=0,
                        spill_dir=spill)
    first = make_random_tensors(_spec(), batch_size=8, seed=0)
    store.add(first)
    store.add(make_random_tensors(_spec(), batch_size=4, seed=1))
    assert store.evictions_total == 4
    assert store.spilled_total == 4
    files = sorted(os.listdir(spill))
    assert len(files) == 1 and files[0].endswith(".npz")
    arrays = np.load(os.path.join(spill, files[0]))
    # The evicted rows are the OLDEST four (ring head).
    np.testing.assert_array_equal(
        arrays["image"], first.to_flat_dict()["image"][:4])

  def test_staleness_ages_from_learner_step(self):
    store = ReplayStore(_spec(), capacity=64, num_shards=1)
    store.set_learner_step(10)
    store.add(make_random_tensors(_spec(), batch_size=8, seed=0))
    store.set_learner_step(25)
    _, ages, _ = store.sample_with_ages(8)
    np.testing.assert_array_equal(ages, np.full(8, 15))

  def test_multi_shard_sampling_deterministic_given_seed(self):
    def draw(seed):
      store = ReplayStore(_spec(), capacity=64, num_shards=4,
                          seed=seed)
      for i in range(4):
        store.add(make_random_tensors(_spec(), batch_size=16, seed=i))
      _, _, ids = store.sample_with_ages(32)
      return ids

    np.testing.assert_array_equal(draw(3), draw(3))
    assert not np.array_equal(draw(3), draw(4))


def _filled_store(num_shards, sampling, seed=11):
  store = ReplayStore(_spec(), capacity=96, num_shards=num_shards,
                      seed=seed, sampling=sampling)
  for i in range(6):
    store.add(make_random_tensors(_spec(), batch_size=16, seed=i),
              priority=float(1 + i))
    store.set_learner_step(10 * (i + 1))
  return store


def _out_arrays(store, batch_size, fill=0):
  return {key: np.full((batch_size,) + tuple(leaf.shape), fill,
                       dtype=leaf.dtype)
          for key, leaf in store.transition_spec.to_flat_dict().items()}


class TestSampleIntoGivenArrays:
  """`sample_with_ages(out=)` (ISSUE 29): the same draw and the same
  rows, in arrays the caller brought."""

  @pytest.mark.parametrize("sampling",
                           ["uniform", "prioritized", "fifo"])
  @pytest.mark.parametrize("num_shards", [1, 3])
  def test_same_bytes_ages_row_ids_and_schedule(self, num_shards,
                                                sampling):
    plain = _filled_store(num_shards, sampling)
    given = _filled_store(num_shards, sampling)
    keeps_out = not (sampling == "fifo" and num_shards > 1)
    # The same arrays every call, full of the previous call's rows.
    out = _out_arrays(given, 24)
    for call in range(4):
      want, ages, ids = plain.sample_with_ages(24)
      got, ages_out, ids_out = given.sample_with_ages(24, out=out)
      assert list(got.to_flat_dict()) == list(want.to_flat_dict())
      for key, x in want.to_flat_dict().items():
        assert got[key].dtype == x.dtype and got[key].shape == x.shape
        assert got[key].tobytes() == x.tobytes(), (call, key)
        assert (got[key] is out[key]) == keeps_out
      np.testing.assert_array_equal(ages_out, ages)
      np.testing.assert_array_equal(ids_out, ids)
    # Through the sampler's one-shot lend: the schedule it digests and
    # the staleness it accounts are those of the plain stream.
    plain, given = (
        ReplayBatchSampler(_filled_store(num_shards, sampling), 24,
                           record_schedule=True) for _ in range(2))
    for call in range(4):
      if call != 2:  # a sample without a lend in between
        given.gather_next_into(TensorSpecStruct.from_flat_dict(out))
      want, got = plain.sample(), given.sample()
      assert (got["image"] is out["image"]) == (keeps_out and call != 2)
      assert got["image"].tobytes() == want["image"].tobytes()
    assert given.schedule_digest() == plain.schedule_digest()
    assert given.staleness_snapshot() == plain.staleness_snapshot()

  @pytest.mark.parametrize(
      "wrong", ["shape", "dtype", "missing_key", "extra_key",
                "not_contiguous", "read_only"])
  @pytest.mark.parametrize("num_shards", [1, 3])
  def test_an_out_that_does_not_fit_raises_before_any_write(
      self, num_shards, wrong):
    store = _filled_store(num_shards, "uniform")
    twin = _filled_store(num_shards, "uniform")
    out = _out_arrays(store, 24, fill=7)
    last = list(out)[-1]  # the keys before it would be written first
    if wrong == "shape":
      out[last] = out[last][:23]
    elif wrong == "dtype":
      out[last] = out[last].astype(np.float64)
    elif wrong == "missing_key":
      del out[last]
    elif wrong == "extra_key":
      out["extra"] = np.zeros((24,), np.float32)
    elif wrong == "not_contiguous":
      out["image"] = np.full((24, 16, 16, 6), 7, np.uint8)[..., ::2]
    else:
      out["image"].flags.writeable = False
    with pytest.raises(ValueError, match="sample_with_ages: out"):
      store.sample_with_ages(24, out=out)
    for key, arr in out.items():
      if key != "extra":
        assert (arr == 7).all(), key
    # Nothing was drawn either: the next sample is the twin's first.
    got, want = store.sample_with_ages(24), twin.sample_with_ages(24)
    np.testing.assert_array_equal(got[2], want[2])
    assert got[0]["image"].tobytes() == want[0]["image"].tobytes()


class TestReplayWriteService:

  def test_put_flush_commits(self):
    store = ReplayStore(_spec(), capacity=128)
    service = ReplayWriteService(store, queue_batches=4)
    assert service.put(make_random_tensors(_spec(), batch_size=16,
                                           seed=0))
    assert service.flush(timeout_secs=10)
    assert len(store) == 16
    assert service.committed_transitions == 16
    service.close()

  def test_overflow_drop_counts_and_never_blocks(self, monkeypatch):
    """Queue overflow under the drop policy: producers get False +
    counters, and the LEARNER's sample path stays un-blocked even
    with the writer wedged mid-add."""
    store = ReplayStore(_spec(), capacity=128)
    store.add(make_random_tensors(_spec(), batch_size=32, seed=9))
    gate = threading.Event()
    real_add = store.add

    def wedged_add(*args, **kwargs):
      gate.wait(timeout=30)
      return real_add(*args, **kwargs)

    monkeypatch.setattr(store, "add", wedged_add)
    service = ReplayWriteService(store, queue_batches=2,
                                 overflow="drop")
    batch = make_random_tensors(_spec(), batch_size=8, seed=0)
    # Fill: one batch wedges in the writer, two sit in the queue.
    results = [service.put(batch) for _ in range(5)]
    t0 = time.perf_counter()
    dropped = [service.put(batch) for _ in range(3)]
    put_secs = time.perf_counter() - t0
    assert put_secs < 1.0  # drop policy never blocks a producer
    assert not all(dropped)
    assert service.dropped_batches >= 3
    assert service.dropped_transitions >= 24
    # The learner samples the store directly: wedged ingestion is
    # invisible to it.
    t0 = time.perf_counter()
    store.sample(16)
    assert time.perf_counter() - t0 < 1.0
    gate.set()
    service.close()
    assert results[0] is True

  def test_overflow_block_applies_backpressure(self, monkeypatch):
    store = ReplayStore(_spec(), capacity=128)
    gate = threading.Event()
    real_add = store.add
    monkeypatch.setattr(
        store, "add",
        lambda *a, **k: (gate.wait(timeout=30), real_add(*a, **k)))
    service = ReplayWriteService(store, queue_batches=1,
                                 overflow="block",
                                 block_timeout_secs=0.2)
    batch = make_random_tensors(_spec(), batch_size=4, seed=0)
    service.put(batch)  # will wedge in the writer
    deadline = time.monotonic() + 10
    while service.queue_depth > 0 and time.monotonic() < deadline:
      time.sleep(0.005)  # writer must HOLD batch 1 before we fill
    service.put(batch)  # fills the queue
    t0 = time.perf_counter()
    accepted = service.put(batch)  # must WAIT ~block_timeout, then drop
    waited = time.perf_counter() - t0
    assert not accepted
    assert waited >= 0.15
    gate.set()
    service.close()

  def test_session_commits_whole_episodes(self):
    store = ReplayStore(_spec(), capacity=128)
    service = ReplayWriteService(store, queue_batches=4)
    session = service.session("actor-a")
    session.begin_episode()
    session.append(make_random_tensors(_spec(), batch_size=4, seed=0))
    session.append(make_random_tensors(_spec(), batch_size=4, seed=1))
    assert len(store) == 0  # staged only — nothing visible mid-episode
    assert session.end_episode()
    service.flush()
    assert len(store) == 8
    service.close()

  def test_crash_mid_episode_leaves_store_consistent(self):
    store = ReplayStore(_spec(), capacity=128)
    service = ReplayWriteService(store, queue_batches=4)
    session = service.session("actor-a")
    session.add(make_random_tensors(_spec(), batch_size=8, seed=0))
    session.begin_episode()
    session.append(make_random_tensors(_spec(), batch_size=4, seed=1))
    # Crash: the episode never ends; abort is what the actor's crash
    # handler (and a restart's session reopen) performs.
    session.abort()
    service.flush()
    assert len(store) == 8  # the committed episode only, no partial
    assert service.aborted_episodes == 1
    service.close()

  def test_restart_resumes_ingestion(self):
    store = ReplayStore(_spec(), capacity=128)
    service = ReplayWriteService(store, queue_batches=4)
    dead = service.session("actor-a")
    dead.begin_episode()
    dead.append(make_random_tensors(_spec(), batch_size=4, seed=0))
    # Restart: reopening the id aborts the dead incarnation's staged
    # rows and returns a working session.
    fresh = service.session("actor-a")
    assert service.restarts == 1
    assert service.aborted_episodes == 1
    with pytest.raises(RuntimeError, match="closed"):
      dead.append(make_random_tensors(_spec(), batch_size=4, seed=1))
    assert fresh.add(make_random_tensors(_spec(), batch_size=8, seed=2))
    service.flush()
    assert len(store) == 8
    service.close()


class TestActorOnThePlane:
  """GraspActor wired through the ingestion service."""

  def test_actor_crash_discards_partial_and_restart_resumes(self):
    learner = _tiny_learner()
    store = ReplayStore(learner.transition_specification(),
                        capacity=2048)
    service = ReplayWriteService(store, queue_batches=8)
    env = ToyGraspEnv(image_size=16, action_dim=2, seed=3)
    actor = GraspActor(learner, service, env=env, batch_episodes=16,
                       epsilon=0.0, seed=3)
    # Sabotage the env after one good batch: the collection thread
    # must crash cleanly (partial episode discarded, flag set).
    actor.collect_once()
    service.flush()
    committed = len(store)
    assert committed == 16

    real_grade = env.grade
    calls = {"n": 0}

    def failing_grade(actions, positions):
      calls["n"] += 1
      raise RuntimeError("sim died mid-episode")

    env.grade = failing_grade
    actor.start()
    deadline = time.monotonic() + 30
    while not actor.crashed and time.monotonic() < deadline:
      time.sleep(0.01)
    assert actor.crashed
    assert calls["n"] >= 1
    service.flush()
    assert len(store) == committed  # nothing partial landed

    # Restart: same actor object, env healed; ingestion resumes.
    env.grade = real_grade
    actor.start()
    assert not actor.crashed
    deadline = time.monotonic() + 30
    while len(store) <= committed and time.monotonic() < deadline:
      time.sleep(0.01)
    actor.stop()
    service.flush()
    assert len(store) > committed
    assert service.restarts == 1
    service.close()


class TestReplayBatchSampler:

  def test_stream_feeds_prefetcher_wire_spec(self):
    from tensor2robot_tpu.data.prefetch import (
        ShardedPrefetcher,
        make_data_sharding,
    )
    from tensor2robot_tpu.parallel import create_mesh

    store = ReplayStore(_spec(), capacity=128, num_shards=2)
    store.add(make_random_tensors(_spec(), batch_size=64, seed=0))
    stream, sampler = make_stream(store, batch_size=16)
    mesh = create_mesh({"data": 1}, devices=jax.devices()[:1])
    prefetcher = ShardedPrefetcher(stream, make_data_sharding(mesh),
                                   buffer_size=1)
    try:
      placed = next(prefetcher)
      flat = placed.to_flat_dict()
      assert flat["image"].shape == (16, 16, 16, 3)
      assert sampler.staleness_snapshot()["batches"] >= 1
    finally:
      prefetcher.close()

  def test_staleness_histogram_buckets(self):
    store = ReplayStore(_spec(), capacity=64)
    store.set_learner_step(0)
    store.add(make_random_tensors(_spec(), batch_size=32, seed=0))
    sampler = ReplayBatchSampler(store, batch_size=8)
    store.set_learner_step(3)   # ages 3 → "<=4" bucket
    sampler.sample()
    store.set_learner_step(100)  # ages 100 → "<=128" bucket
    sampler.sample()
    snap = sampler.staleness_snapshot()
    assert snap["histogram"]["<=4"] == 8
    assert snap["histogram"]["<=128"] == 8
    assert snap["rows"] == 16
    assert snap["max_age_steps"] == 100
    labels = list(snap["histogram"])
    assert labels[0] == "<=0"
    assert labels[-1] == f">{STALENESS_BUCKETS[-1]}"

  def test_schedule_digest_reproducible(self):
    def digest(seed):
      store = ReplayStore(_spec(), capacity=128, num_shards=2,
                          seed=seed)
      store.add(make_random_tensors(_spec(), batch_size=64, seed=0))
      sampler = ReplayBatchSampler(store, batch_size=16,
                                   record_schedule=True)
      for _ in range(4):
        sampler.sample()
      return sampler.schedule_digest()

    assert digest(5) == digest(5)
    assert digest(5) != digest(6)

  def test_metrics_scalars_shape(self):
    store = ReplayStore(_spec(), capacity=64)
    store.add(make_random_tensors(_spec(), batch_size=32, seed=0))
    sampler = ReplayBatchSampler(store, batch_size=8)
    sampler.sample()
    scalars = sampler.metrics_scalars()
    assert set(scalars) == {
        "replay_staleness_mean_steps", "replay_staleness_max_steps",
        "replay_staleness_batch_p95_steps", "replay_sampled_batches"}


class TestAdapterAndTrainerEquivalence:
  """The acceptance pin: QT-Opt through the new data plane reproduces
  the legacy in-process ReplayBuffer path exactly."""

  def _train(self, replay, tmp_path, name):
    learner = _tiny_learner()
    return train_qtopt(
        learner=learner,
        model_dir=str(tmp_path / name),
        replay_buffer=replay,
        max_train_steps=6,
        batch_size=8,
        save_checkpoints_steps=6,
        log_every_steps=3,
    )

  def test_offline_training_bitwise_matches_legacy(self, tmp_path):
    batch = make_random_tensors(_spec(), batch_size=64, seed=3)
    legacy = _LegacyReplayBuffer(_spec(), capacity=64, seed=7)
    legacy.add(batch)
    plane = ReplayBuffer(_spec(), capacity=64, seed=7)
    plane.add(batch)
    base = self._train(legacy, tmp_path, "legacy")
    new = self._train(plane, tmp_path, "plane")
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(
            jax.device_get(base.train_state.params)),
        jax.tree_util.tree_leaves(
            jax.device_get(new.train_state.params))):
      np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                    err_msg=str(path))

  def test_single_actor_online_ingestion_matches_direct_add(self):
    """Single-actor online collection through the SERVICE (sessioned,
    queued, writer-thread committed) must land the store in exactly
    the state the legacy direct-add path lands it in — same rows, same
    slots, same sample schedule — so a training run over either is
    identical (plane→params equality is pinned by the offline bitwise
    test above; this one pins the ingestion leg without paying two
    more XLA compiles)."""
    def run(via_service):
      learner = _tiny_learner()
      spec = learner.transition_specification()
      buf = ReplayBuffer(spec, capacity=1024, seed=7)
      env = ToyGraspEnv(image_size=16, action_dim=2, seed=5)
      if via_service:
        service = ReplayWriteService(buf.store, queue_batches=8)
        sink = service
      else:
        service, sink = None, buf
      actor = GraspActor(learner, sink, env=env, batch_episodes=32,
                         epsilon=0.2, seed=5)
      for _ in range(4):
        actor.collect_once()
      if service is not None:
        assert service.flush(timeout_secs=30)
        service.close()
      return buf

    base = run(False)
    new = run(True)
    assert len(base) == len(new) == 128
    # Identically-seeded samplers over identically-ingested stores
    # must draw identical rows from identical slots.
    a = base.sample(64).to_flat_dict()
    b = new.sample(64).to_flat_dict()
    assert set(a) == set(b)
    for key in a:
      np.testing.assert_array_equal(a[key], b[key], err_msg=key)

  def test_adapter_keeps_legacy_surface(self):
    buf = ReplayBuffer(_spec(), capacity=32, seed=0)
    with pytest.raises(ValueError, match="empty replay buffer"):
      buf.sample(2)
    buf.add(make_random_tensors(_spec(), batch_size=8, seed=0))
    assert len(buf) == 8
    assert buf.capacity == 32
    assert buf.wait_until_size(8, timeout_secs=1)
    stream = buf.as_stream(4)
    batch = next(stream)
    assert batch.to_flat_dict()["image"].shape == (4, 16, 16, 3)
    assert "replay_fill" in buf.metrics_scalars()


class TestPrefetchDepth:

  def test_resolver_defaults_and_override(self):
    from tensor2robot_tpu.data.prefetch import prefetch_buffer_size

    assert prefetch_buffer_size(None, online=False) == 2
    assert prefetch_buffer_size(None, online=True) == 1
    assert prefetch_buffer_size(5, online=True) == 5
    with pytest.raises(ValueError):
      prefetch_buffer_size(0)

  def test_resolver_gin_configurable(self):
    from tensor2robot_tpu import config as gin
    from tensor2robot_tpu.data.prefetch import prefetch_buffer_size

    gin.bind_parameter("prefetch_buffer_size.online_default", 3)
    try:
      assert prefetch_buffer_size(None, online=True) == 3
    finally:
      gin.clear_config()
    # The binding train_qtopt's docstring advertises: it must apply
    # through the trainer's call shape (buffer_size NOT forwarded when
    # unset — a positional None would shadow the binding in ginlite).
    gin.bind_parameter("prefetch_buffer_size.buffer_size", 7)
    try:
      assert prefetch_buffer_size(online=True) == 7
    finally:
      gin.clear_config()

  def test_train_qtopt_online_uses_depth_1_and_logs_replay_metrics(
      self, tmp_path, monkeypatch):
    """An online run (a hook drives collection) must construct the
    prefetcher at depth 1 — the K>1 sampling-lead default — and the
    train log must carry the data-plane scalars next to the loop's
    own (one shared train run keeps the suite's compile bill down)."""
    from tensor2robot_tpu.data import prefetch as prefetch_lib
    from tensor2robot_tpu.hooks import Hook

    seen = {}
    real = prefetch_lib.ShardedPrefetcher

    class Recording(real):

      def __init__(self, iterator, sharding, buffer_size=2):
        seen["buffer_size"] = buffer_size
        super().__init__(iterator, sharding, buffer_size=buffer_size)

    monkeypatch.setattr(prefetch_lib, "ShardedPrefetcher", Recording)

    class OnlineMarker(Hook):
      drives_online_collection = True

    learner = _tiny_learner()
    buf = ReplayBuffer(learner.transition_specification(),
                       capacity=64, seed=1)
    buf.add(make_random_tensors(
        learner.transition_specification(), batch_size=64, seed=0))
    train_qtopt(
        learner=learner,
        model_dir=str(tmp_path / "depth"),
        replay_buffer=buf,
        max_train_steps=4,
        batch_size=8,
        save_checkpoints_steps=4,
        log_every_steps=2,
        hooks=[OnlineMarker()],
    )
    assert seen["buffer_size"] == 1
    records = read_records(os.path.join(str(tmp_path / "depth"),
                                         "metrics_train.jsonl"))
    last = records[-1]
    assert "replay_fill" in last
    assert "replay_staleness_mean_steps" in last
    assert "replay_samples_per_sec" in last
    assert last["replay_fill"] == 1.0
    # Ages are non-negative and the sampler saw every consumed batch
    # (positivity under a controlled clock is pinned in
    # TestReplayBatchSampler — here prefetch timing makes the exact
    # mean scheduling-dependent).
    assert last["replay_staleness_mean_steps"] >= 0
    assert last["replay_sampled_batches"] >= 4


def _tagged_chunk(tag, rows=16):
  """A [rows, ...] chunk whose every field of every row carries `tag`."""
  flat = make_random_tensors(_spec(), batch_size=rows,
                             seed=0).to_flat_dict()
  return {key: np.full_like(value, tag % 251)
          for key, value in flat.items()}


class TestStoreUnderConcurrentLoad:
  """The online shape: actor sessions commit through the service while
  sampler threads draw, rows wrapping the ring under both."""

  @pytest.mark.parametrize("num_shards", [1, 2])
  def test_samples_are_whole_rows_and_every_commit_lands(
      self, num_shards):
    store = ReplayStore(_spec(), capacity=128, num_shards=num_shards,
                        seed=0)
    store.add(_tagged_chunk(0, rows=64))
    service = ReplayWriteService(store, queue_batches=4,
                                 overflow="block")
    sampler = ReplayBatchSampler(store, batch_size=32)
    chunks, torn, done = 40, [], threading.Event()

    def produce(actor):
      session = service.session(f"actor-{actor}")
      for i in range(chunks):
        assert session.add(_tagged_chunk(1 + actor + 2 * i))

    def consume():
      step = 0
      while not done.is_set() or step < 8:
        store.set_learner_step(step)
        flat = sampler.sample().to_flat_dict()
        tags = flat["reward"][:, 0]
        for key, value in flat.items():
          rows = value.reshape(len(tags), -1)
          if not (rows == tags[:, None].astype(value.dtype)).all():
            torn.append(key)
        step += 1

    producers = [threading.Thread(target=produce, args=(a,))
                 for a in range(2)]
    consumers = [threading.Thread(target=consume) for _ in range(2)]
    for thread in producers + consumers:
      thread.start()
    for thread in producers:
      thread.join(timeout=120)
    assert service.flush(timeout_secs=60)
    done.set()
    for thread in consumers:
      thread.join(timeout=120)
    service.close()
    assert not any(t.is_alive() for t in producers + consumers)
    # A row overwritten while a sampler gathered it would mix two tags.
    assert not torn, sorted(set(torn))
    assert service.committed_transitions == 2 * chunks * 16
    assert service.dropped_batches == 0
    assert store.adds_total == 64 + 2 * chunks * 16
    assert len(store) == 128
    assert store.shard_sizes() == (128 // num_shards,) * num_shards
    snap = sampler.staleness_snapshot()
    assert snap["rows"] >= 2 * 8 * 32
    assert sum(snap["histogram"].values()) == snap["rows"]
