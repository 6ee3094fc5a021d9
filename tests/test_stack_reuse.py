"""`StackedBatchStream`'s ring of reused host buffers (ISSUE 25;
data/prefetch.py): what is stacked into a slot is what `np.stack`
gives, a slot is written again only when the arrays made from it are
ready, and a consumer that did not ask gets independent arrays.

Also the check of the acceptance criteria that needs the chip: run as
a program (the suite's conftest pins the CPU),

  python tests/test_stack_reuse.py --cell qtopt_64 --dispatches 24

it drives a `ShardedPrefetcher` over dispatches of a benchmark cell's
shapes and compares a checksum of every placed array with that of its
host buffer taken before the yield; then a real `ReplayBuffer` of the
cell's shapes through the in-place path (ISSUE 29), each placed array
also against the checksums of the rows that were drawn for it.
"""

import gc
import itertools
import logging
import os
import sys
import threading
import time
import weakref

import numpy as np
import pytest

K = 3
RING = 2


def _prefetch():
  from tensor2robot_tpu.data import prefetch
  return prefetch


def _batch(n, rows=4, dtype=np.float32):
  """Batch number `n` of a stream: every element says where it is."""
  image = (np.arange(rows * 6).reshape(rows, 2, 3) + 100 * n)
  return {"image": image.astype(dtype),
          "reward": np.full((rows,), n, np.float32)}


def _batches(count, **kwargs):
  return (_batch(n, **kwargs) for n in range(count))


def _expected(seq, k=K, **kwargs):
  return {key: np.stack([_batch(n, **kwargs)[key]
                         for n in range(seq * k, (seq + 1) * k)])
          for key in ("image", "reward")}


def _assert_dispatch(stacked, seq, **kwargs):
  want = _expected(seq, **kwargs)
  assert sorted(stacked) == sorted(want)
  for key in want:
    got = np.asarray(stacked[key])
    assert got.dtype == want[key].dtype and got.shape == want[key].shape
    assert got.tobytes() == want[key].tobytes(), (seq, key)


def _counts():
  from tensor2robot_tpu import telemetry
  got = telemetry.registry().scalars("feed.stack.")
  return (got.get("feed.stack.reused_dispatches", 0.0),
          got.get("feed.stack.fresh_dispatches", 0.0))


class Placed:
  """Stands in for the device arrays made from a dispatch: a copy of
  the bytes, ready when the test says so."""

  def __init__(self, stacked, ready=True):
    self.copy = {key: np.array(x) for key, x in stacked.items()}
    self.ready = threading.Event()
    self.waited = 0
    if ready:
      self.ready.set()

  def block_until_ready(self):
    self.waited += 1
    assert self.ready.wait(timeout=30)
    return self


@pytest.fixture(autouse=True)
def clean_registry():
  from tensor2robot_tpu.telemetry import core as tcore
  from tensor2robot_tpu.telemetry import metrics as tmetrics
  tcore.reset_for_tests()
  tmetrics.reset_for_tests()
  yield
  tcore.reset_for_tests()
  tmetrics.reset_for_tests()


def _pull_until_it_waits_for(stream, held):
  """Starts a pull of `stream` on a thread of its own and returns
  (the thread, the list its dispatch will land in) once the pull waits
  for `held`; it is still waiting 0.2 s later."""
  result = []
  puller = threading.Thread(target=lambda: result.append(next(stream)),
                            daemon=True)
  puller.start()
  deadline = time.monotonic() + 30
  while not held.waited and time.monotonic() < deadline:
    time.sleep(0.01)
  assert held.waited == 1
  time.sleep(0.2)
  assert puller.is_alive() and not result
  return puller, result


def _release(held, puller):
  held.ready.set()
  puller.join(timeout=30)
  assert not puller.is_alive()


def _reusing(count, k=K, **kwargs):
  stream = _prefetch().stack_batches(_batches(count, **kwargs), k)
  stream.reuse_buffers()
  return stream


class TestRing:

  def test_reused_stacks_are_np_stack_of_the_same_batches(self):
    dispatches = 4 * RING
    stream = _reusing(dispatches * K)
    buffers, placed = [], []
    for seq in range(dispatches):
      stacked = next(stream)
      _assert_dispatch(stacked, seq)
      buffers.append(stacked["image"])
      placed.append(Placed(stacked))
      stream.transfer_started(placed[-1])
    with pytest.raises(StopIteration):
      next(stream)
    # Two buffers took every dispatch in turn. The arrays made from a
    # dispatch were waited for before the next one was handed out, and
    # again (at once) before the dispatch that wrote over its buffer.
    assert {id(b) for b in buffers} == {id(b) for b in buffers[:RING]}
    assert buffers[0] is not buffers[1]
    assert all(buffers[i] is buffers[i % RING]
               for i in range(dispatches))
    assert [p.waited for p in placed] == \
        [2] * (dispatches - RING) + [1, 0]
    # What the consumer copied is still each dispatch's own.
    for seq, p in enumerate(placed):
      _assert_dispatch(p.copy, seq)
    assert _counts() == (dispatches, 0)

  def test_one_transfer_out_of_the_ring_at_a_time(self):
    stream = _reusing(3 * K)
    first = next(stream)
    held = Placed(first, ready=False)
    stream.transfer_started(held)
    # The second dispatch is stacked, in the other buffer, and is not
    # handed out while the first one's arrays are being made: its
    # transfer would run beside theirs. The first buffer, which the
    # third dispatch will want, has not been written.
    puller, result = _pull_until_it_waits_for(stream, held)
    _assert_dispatch(first, 0)
    _release(held, puller)
    assert result[0]["image"] is not first["image"]
    _assert_dispatch(result[0], 1)
    stream.transfer_started(Placed(result[0]))
    assert next(stream)["image"] is first["image"]
    _assert_dispatch(first, 2)
    _assert_dispatch(held.copy, 0)

  def test_the_ring_lets_go_of_the_arrays_it_waited_for(self):
    stream = _reusing(3 * K)
    placed = Placed(next(stream))
    gone = weakref.ref(placed)
    stream.transfer_started(placed)
    del placed
    stream.transfer_started(Placed(next(stream)))
    gc.collect()
    assert gone() is not None  # the slot has not come round yet
    next(stream)
    gc.collect()
    assert gone() is None

  def test_a_dispatch_nobody_reported_is_never_written_again(self):
    stream = _reusing(4 * K)
    kept = [next(stream) for _ in range(4)]
    for seq, stacked in enumerate(kept):
      _assert_dispatch(stacked, seq)
    assert len({id(s["image"]) for s in kept}) == 4
    assert _counts() == (4, 0)

  @pytest.mark.parametrize("odd", ["shape", "dtype", "tree", "leaf"])
  def test_a_dispatch_of_another_kind_gets_fresh_arrays(self, odd):
    def batches():
      for n in range(4 * K):
        batch = _batch(n)
        if 2 * K <= n < 3 * K:  # the third dispatch
          if odd == "shape":
            batch = _batch(n, rows=2)
          elif odd == "dtype":
            batch = _batch(n, dtype=np.float64)
          elif odd == "tree":
            batch["extra"] = np.zeros((4,), np.float32)
          else:  # the first batch alone differs: no `out=` fits
            batch = _batch(n, dtype=np.float64 if n == 2 * K
                           else np.float32)
        yield batch

    stream = _prefetch().stack_batches(batches(), K)
    stream.reuse_buffers()
    seen = []
    for seq in range(4):
      stacked = next(stream)
      if seq != 2:
        _assert_dispatch(stacked, seq)
      seen.append(stacked)
      stream.transfer_started(Placed(stacked))
    third = {key: np.stack([_batch(n)[key] for n in range(2 * K, 3 * K)])
             for key in ("image", "reward")}
    if odd == "shape":
      assert seen[2]["image"].shape == (K, 2, 2, 3)
    elif odd == "tree":
      assert sorted(seen[2]) == ["extra", "image", "reward"]
    else:  # promoted as `np.stack` promotes, not cast into a slot
      assert seen[2]["image"].dtype == np.float64
      np.testing.assert_array_equal(seen[2]["image"], third["image"])
    # The odd one touched no slot; the fourth went on round the ring.
    assert seen[0]["image"] is not seen[1]["image"]
    assert not any(seen[2]["image"] is s["image"] for s in seen[:2])
    assert seen[3]["image"] is seen[0]["image"]
    _assert_dispatch(seen[1], 1)
    assert _counts() == (3, 1)

  def test_the_partial_tail_is_still_dropped_and_logged(self, caplog):
    stream = _reusing(2 * K + 2)
    with caplog.at_level(logging.WARNING):
      for seq in range(2):
        stacked = next(stream)
        _assert_dispatch(stacked, seq)
        stream.transfer_started(Placed(stacked))
      with pytest.raises(StopIteration):
        next(stream)
    assert "dropped a partial tail of 2" in caplog.text
    assert _counts() == (2, 0)

  def test_close_releases_the_ring(self):
    closed = []

    class Inner:
      def __iter__(self):
        return self

      def __next__(self, _n=iter(range(100))):
        return _batch(next(_n))

      def close(self):
        closed.append(True)

    stream = _prefetch().stack_batches(Inner(), K)
    stream.reuse_buffers()
    stacked = next(stream)
    placed = Placed(stacked)
    stream.transfer_started(placed)
    buffer_gone = weakref.ref(stacked["image"])
    placed_gone = weakref.ref(placed)
    del stacked, placed
    gc.collect()
    assert buffer_gone() is not None and placed_gone() is not None
    stream.close()
    gc.collect()
    assert closed and buffer_gone() is None and placed_gone() is None
    # A stream pulled after its close stacks afresh.
    assert next(stream)["image"].shape == (K, 4, 2, 3)
    assert _counts() == (1, 1)


class TestWhoGetsTheRing:

  def test_a_plain_consumer_gets_independent_arrays(self):
    stacks = list(_prefetch().stack_batches(_batches(5 * K), K))
    assert len(stacks) == 5
    for seq, stacked in enumerate(stacks):
      _assert_dispatch(stacked, seq)
    assert len({id(s["image"]) for s in stacks}) == 5
    assert not any(np.shares_memory(a["image"], b["image"])
                   for i, a in enumerate(stacks) for b in stacks[:i])
    assert _counts() == (0, 5)

  def test_cpu_devices_do_not_copy_off_the_host(self):
    import jax
    prefetch = _prefetch()
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    sharding = prefetch.stacked_sharding(
        prefetch.make_data_sharding(mesh))
    assert not prefetch._copies_off_host(sharding)
    assert not prefetch._copies_off_host(
        jax.sharding.SingleDeviceSharding(jax.devices()[0]))

  def test_a_late_consumer_on_cpu_finds_every_dispatch_its_own(self):
    """On the CPU backend a placed array may BE the host array: the
    prefetcher must not lend the stream's buffers there."""
    import jax
    prefetch = _prefetch()
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    dispatches = 4 * RING
    prefetcher = prefetch.ShardedPrefetcher(
        prefetch.stack_batches(_batches(dispatches * K, rows=8), K),
        prefetch.stacked_sharding(prefetch.make_data_sharding(mesh)),
        buffer_size=2)
    time.sleep(0.5)  # the queue is full, the worker holds one more
    consumed = []
    for stacked in prefetcher:
      consumed.append(stacked)
      time.sleep(0.02)
    assert len(consumed) == dispatches
    for seq, stacked in enumerate(consumed):
      assert isinstance(stacked["image"], jax.Array)
      _assert_dispatch(stacked, seq, rows=8)
    assert _counts() == (0, dispatches)

  def test_the_prefetcher_lends_where_placement_copies(self,
                                                      monkeypatch):
    """The engaged path end to end, on devices made to copy: the
    check the chip run makes at the cells' sizes."""
    prefetch = _prefetch()
    _make_placement_copy(monkeypatch, prefetch)
    result = check_placed_against_host(
        lambda: _batches(6 * RING * K, rows=8), K, 6 * RING,
        consumer_sleep=0.01)
    assert result["dispatches"] == 6 * RING
    assert result["mismatches"] == 0
    assert result["buffers"] == RING
    assert _counts() == (6 * RING, 0)


class LendingSource:
  """A source that takes a destination as the replay sampler does: one
  lent batch of arrays, for the next pull alone, is written and yielded
  as it is; without one the batch is a set of fresh arrays."""

  def __init__(self, count, honour=True, make=_batch):
    self._count, self._honour, self._make = count, honour, make
    self._lent = None
    self.pulled = 0
    self.lends = 0
    self.fail_at = None  # a pull that raises once, after taking the lend

  def lend(self, views):
    self.lends += 1
    self._lent = views

  def __iter__(self):
    return self

  def __next__(self):
    lent, self._lent = self._lent, None
    n = self.pulled
    if n >= self._count:
      raise StopIteration
    if n == self.fail_at:
      self.fail_at = None
      raise RuntimeError("the source failed")
    self.pulled += 1
    batch = self._make(n)
    if lent is None or not self._honour or any(
        lent[key].shape != x.shape for key, x in batch.items()):
      return batch
    for key, x in batch.items():
      lent[key][...] = x
    return lent


def _gather_counts():
  from tensor2robot_tpu import telemetry
  got = telemetry.registry().scalars("feed.gather.")
  return (got.get("feed.gather.in_place_batches", 0.0),
          got.get("feed.gather.copied_batches", 0.0))


def _lending(source, k=K, wrap=lambda s: s):
  stream = _prefetch().stack_batches(wrap(source), k, lend=source.lend)
  stream.reuse_buffers()
  return stream


def _stack_bytes():
  from tensor2robot_tpu import telemetry
  return [s["args"]["bytes"]
          for s in telemetry.get_tracer().snapshot_spans()
          if s["name"] == "feed.stack"]


class TestLend:
  """A source that takes a destination gathers each batch straight
  into its slice of the ring slot (ISSUE 29)."""

  @pytest.fixture(autouse=True)
  def spans(self):
    from tensor2robot_tpu import telemetry
    telemetry.configure("lend_test")

  def test_in_place_dispatches_are_np_stack_of_the_same_batches(self):
    dispatches = 1 + 3 * RING  # three laps after the one that copies
    source = LendingSource(dispatches * K)
    stream = _lending(source)
    buffers, placed = [], []
    for seq in range(dispatches):
      stacked = next(stream)
      _assert_dispatch(stacked, seq)
      buffers.append(stacked["image"])
      placed.append(Placed(stacked))
      stream.transfer_started(placed[-1])
    with pytest.raises(StopIteration):
      next(stream)
    assert all(buffers[i] is buffers[i % RING]
               for i in range(dispatches))
    # As without a lend, but the claim comes before the pulls: the
    # pull that found the source dry had claimed one slot more.
    assert [p.waited for p in placed] == [2] * (dispatches - 1) + [0]
    for seq, p in enumerate(placed):
      _assert_dispatch(p.copy, seq)
    assert _counts() == (dispatches, 0)
    # The first dispatch gives the ring its shapes and is copied; every
    # later batch arrived in its slice, and its stack copied nothing.
    assert source.lends == (dispatches - 1) * K + 1  # the dry pull's
    assert _gather_counts() == ((dispatches - 1) * K, K)
    first = sum(x.nbytes for x in _expected(0).values())
    assert _stack_bytes() == [first] + [0] * (dispatches - 1)

  def test_a_slot_is_not_gathered_into_before_its_arrays_are_ready(
      self):
    """The claim's own wait, which the one before a dispatch is handed
    out leaves nothing to do for unless a pull failed in between."""
    source = LendingSource(4 * K)
    stream = _lending(source)
    first = next(stream)
    held = Placed(first, ready=False)
    stream.transfer_started(held)
    source.fail_at = K + 1  # the second dispatch dies in its pulls
    with pytest.raises(RuntimeError, match="the source failed"):
      next(stream)
    assert held.waited == 0
    # The next dispatch wants the first one's slot (the failed one had
    # taken the other), whose reader is still at it: no batch has been
    # asked for, let alone written.
    puller, result = _pull_until_it_waits_for(stream, held)
    assert source.pulled == K + 1
    _assert_dispatch(first, 0)
    _release(held, puller)
    assert result[0]["image"] is first["image"]
    _assert_dispatch(held.copy, 0)

  def test_one_transfer_out_of_the_ring_at_a_time(self):
    source = LendingSource(3 * K)
    stream = _lending(source)
    first = next(stream)
    held = Placed(first, ready=False)
    stream.transfer_started(held)
    # The second dispatch has been gathered, beside the transfer and
    # into the other slot, and waits to be handed out.
    puller, result = _pull_until_it_waits_for(stream, held)
    assert source.pulled == 2 * K
    _assert_dispatch(first, 0)
    _release(held, puller)
    _assert_dispatch(result[0], 1)
    assert _gather_counts() == (K, K)

  def test_a_wrapper_that_forwards_only_next_still_lands_in_place(self):
    """The benchmark's `KeepFirst` stands between the stream and the
    sampler; what it keeps of a batch that is a view of a slot has to
    outlive the slot's next fill."""
    from benchmark.harness.window import KeepFirst
    dispatches = 1 + 2 * RING
    source = LendingSource(dispatches * K)
    kept = []
    stream = _lending(source, wrap=lambda s: KeepFirst(
        s, kept, 2 * K, lambda batch: dict(batch)))
    assert not hasattr(stream._it, "lend")
    for seq in range(dispatches):
      stacked = next(stream)
      _assert_dispatch(stacked, seq)
      stream.transfer_started(Placed(stacked))
    assert _gather_counts() == ((dispatches - 1) * K, K)
    # Both slots have been written again since; the second dispatch's
    # kept batches were views of one of them.
    assert len(kept) == 2 * K
    for n, batch in enumerate(kept):
      for key, x in _batch(n).items():
        assert batch[key].tobytes() == x.tobytes(), (n, key)
        assert batch[key].flags.owndata

  @pytest.mark.parametrize(
      "case", ["source_ignores_the_lend", "another_signature",
               "first_dispatch", "no_ring"])
  def test_what_does_not_land_in_place_is_copied_and_counted(self, case):
    def make(n):
      odd = case == "another_signature" and 2 * K <= n < 3 * K
      return _batch(n, rows=2 if odd else 4)

    source = LendingSource(
        4 * K, honour=case != "source_ignores_the_lend", make=make)
    stream = _prefetch().stack_batches(source, K, lend=source.lend)
    if case != "no_ring":
      stream.reuse_buffers()
    seen = []
    for seq in range(1 if case == "first_dispatch" else 4):
      stacked = next(stream)
      if not (case == "another_signature" and seq == 2):
        _assert_dispatch(stacked, seq)
      seen.append(stacked)
      stream.transfer_started(Placed(stacked))
    if case == "source_ignores_the_lend":
      assert source.lends == 3 * K
      assert _counts() == (4, 0) and _gather_counts() == (0, 4 * K)
      assert seen[2]["image"] is seen[0]["image"]
    elif case == "another_signature":
      # Claimed ahead, found not to fit, stacked afresh: the slot went
      # back, and the next dispatch of the ring's kind took it.
      want = np.stack([_batch(n, rows=2)["image"]
                       for n in range(2 * K, 3 * K)])
      assert seen[2]["image"].tobytes() == want.tobytes()
      assert not any(np.shares_memory(seen[2]["image"], s["image"])
                     for s in seen[:2])
      assert seen[3]["image"] is seen[0]["image"]
      _assert_dispatch(seen[1], 1)
      assert _counts() == (3, 1)
      assert _gather_counts() == (2 * K, 2 * K)
    elif case == "first_dispatch":
      assert source.lends == 0
      assert _counts() == (1, 0) and _gather_counts() == (0, K)
    else:  # a consumer that did not ask: nothing lent, nothing counted
      assert source.lends == 0
      assert len({id(s["image"]) for s in seen}) == 4
      assert _counts() == (0, 4) and _gather_counts() == (0, 0)

  def test_a_stale_lend_does_not_survive_a_failed_pull(self):
    source = LendingSource(4 * K)
    stream = _lending(source)
    for seq in range(2):
      stacked = next(stream)
      stream.transfer_started(Placed(stacked))
    source.fail_at = 2 * K + 1  # the third dispatch's second pull
    with pytest.raises(RuntimeError, match="the source failed"):
      next(stream)
    # The source took the lend with the pull that failed: the batches
    # that follow are gathered where the stream says now, not there.
    assert source._lent is None
    stacked = next(stream)
    want = {key: np.stack([_batch(n)[key]
                           for n in range(2 * K + 1, 3 * K + 1)])
            for key in ("image", "reward")}
    for key in want:
      assert stacked[key].tobytes() == want[key].tobytes()


class TestReplayTakesTheLend:
  """The real source: `ReplayBuffer.gather_next_into` reaches the live
  stream's sampler, for one sample."""

  def _buffer(self, rows=64, seed=7):
    from tensor2robot_tpu.research.qtopt.replay_buffer import ReplayBuffer
    from tensor2robot_tpu.specs import ExtendedTensorSpec, TensorSpecStruct
    spec = TensorSpecStruct({
        "image": ExtendedTensorSpec((2, 3), np.uint8, name="image"),
        "reward": ExtendedTensorSpec((), np.float32, name="reward")})
    buffer = ReplayBuffer(spec, capacity=rows, seed=seed)
    rng = np.random.default_rng(0)
    buffer.add({"image": rng.integers(0, 256, (rows, 2, 3), np.uint8),
                "reward": rng.random(rows).astype(np.float32)})
    return buffer

  def test_the_stream_gathers_into_its_slices_the_same_rows(self):
    from benchmark.harness.window import KeepFirst
    plain = _prefetch().stack_batches(self._buffer().as_stream(8), K)
    buffer = self._buffer()
    kept = []
    stream = _prefetch().stack_batches(
        KeepFirst(buffer.as_stream(8), kept, K,
                  lambda batch: dict(batch.to_flat_dict())),
        K, lend=buffer.gather_next_into)
    stream.reuse_buffers()
    for _ in range(1 + 3 * RING):
      want, got = next(plain), next(stream)
      assert sorted(got.to_flat_dict()) == sorted(want.to_flat_dict())
      for key, x in want.to_flat_dict().items():
        assert got[key].dtype == x.dtype
        assert got[key].tobytes() == x.tobytes()
      stream.transfer_started(Placed(got.to_flat_dict()))
    assert _gather_counts() == (3 * RING * K, K)
    assert _counts() == (1 + 3 * RING, 1 + 3 * RING)  # and the plain

  def test_the_lend_reaches_the_live_sampler_for_one_sample(self):
    buffer = self._buffer()
    views = {"image": np.zeros((8, 2, 3), np.uint8),
             "reward": np.zeros((8,), np.float32)}
    buffer.gather_next_into(views)  # no stream yet: nothing to reach
    first = buffer.as_stream(8)
    stream = buffer.as_stream(8)  # each call makes a new sampler
    buffer.gather_next_into(views)
    assert next(first)["image"] is not views["image"]
    batch = next(stream)
    assert batch["image"] is views["image"]
    assert batch["reward"] is views["reward"]
    assert next(stream)["image"] is not views["image"]

  def test_a_lend_that_does_not_fit_raises_once_and_writes_nothing(self):
    buffer, twin = self._buffer(), self._buffer()
    stream, want = buffer.as_stream(8), twin.as_stream(8)
    views = {"image": np.full((8, 2, 3), 9, np.uint8),
             "reward": np.full((4,), 9, np.float32)}  # four rows short
    buffer.gather_next_into(views)
    with pytest.raises(ValueError, match="reward"):
      next(buffer._stream_sampler.as_stream())
    assert (views["image"] == 9).all() and (views["reward"] == 9).all()
    # Nothing was drawn, and the lend went with the failure.
    batch = next(stream)
    assert batch["image"] is not views["image"]
    assert batch["image"].tobytes() == next(want)["image"].tobytes()


def test_the_prefetcher_feeds_in_place_gathers_of_the_rows_drawn(
    monkeypatch):
  """The in-place path end to end through a `ShardedPrefetcher`, on
  devices made to copy: the check the chip run makes at the cells'
  sizes, against the rows drawn and not only the buffer's bytes."""
  from tensor2robot_tpu.specs import ExtendedTensorSpec, TensorSpecStruct
  prefetch = _prefetch()
  _make_placement_copy(monkeypatch, prefetch)
  spec = TensorSpecStruct({
      "image": ExtendedTensorSpec((4, 4, 3), np.uint8, name="image"),
      "action": ExtendedTensorSpec((5,), np.float32, name="action")})
  dispatches = 6 * RING
  buffer, want_sums = replay_with_row_sums(
      spec, rows=64, batch=8, k=K, seed=5, block_rows=16)
  result = check_placed_against_host(
      lambda: buffer.as_stream(8), K, dispatches, consumer_sleep=0.01,
      lend=buffer.gather_next_into, want_sums=want_sums)
  assert result["dispatches"] == dispatches
  assert result["mismatches"] == 0
  assert result["buffers"] == RING
  # The stream is endless: the feed ran some dispatches ahead.
  assert result["reused"] >= dispatches and result["fresh"] == 0
  assert result["copied_batches"] == K  # the first dispatch's
  assert result["in_place_batches"] >= (dispatches - 1) * K


def _make_placement_copy(monkeypatch, prefetch):
  """CPU devices that behave like an accelerator's: placement reads a
  private copy of the host bytes."""
  real = prefetch.device_put_batch
  monkeypatch.setattr(prefetch, "_copies_off_host", lambda s: True)
  monkeypatch.setattr(
      prefetch, "device_put_batch",
      lambda batch, sharding: real(
          {key: np.array(x) for key, x in _flat(batch).items()},
          sharding))


# ---- the check on placed arrays, shared with the chip run ----


def _flat(tree):
  """A dispatch as a flat dict, be it one or a `TensorSpecStruct`."""
  return tree.to_flat_dict() if hasattr(tree, "to_flat_dict") else tree


def _host_row_sums(x):
  """uint32 sums, one per row of a stacked [K, B, ...] host array, of
  the row's 32-bit words (4-byte dtypes) or of its bytes."""
  rows = x.reshape(x.shape[0], x.shape[1], -1)
  words = rows.view(np.uint32 if x.dtype.itemsize == 4 else np.uint8)
  return words.sum(axis=2, dtype=np.uint32)


def _device_row_sums(x):
  """The same sums from the placed array, computed where it lives
  (no reshape: on the TPU a narrow minor dimension is padded out)."""
  import jax
  from jax import numpy as jnp
  if x.dtype.itemsize == 4:
    words = jax.lax.bitcast_convert_type(x, jnp.uint32)
  elif x.dtype.itemsize == 1:
    words = jax.lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
  else:
    raise ValueError(f"no row sums for {x.dtype}")
  return jnp.sum(words, axis=tuple(range(2, x.ndim)), dtype=jnp.uint32)


def check_placed_against_host(make_batches, k, dispatches,
                              consumer_sleep=0.0, devices=None,
                              lend=None, want_sums=None):
  """Drives `ShardedPrefetcher(stack_batches(..., lend))` for
  `dispatches` dispatches; returns how many placed leaves' row sums
  differ from those of their host buffer taken before the yield, or
  from `want_sums(seq)` (asked for in order) where the caller knows
  what dispatch `seq` has to hold whatever its buffer held."""
  import jax
  from tensor2robot_tpu import telemetry
  prefetch = _prefetch()
  host_sums, host_ids = [], set()

  class Checked(prefetch.StackedBatchStream):

    def __next__(self):
      stacked = super().__next__()
      host_sums.append({key: _host_row_sums(x)
                        for key, x in _flat(stacked).items()})
      host_ids.add(id(stacked["image"]))
      return stacked

  telemetry.configure("stack_reuse_check")
  mesh = jax.sharding.Mesh(
      np.array(devices if devices is not None else jax.devices()),
      ("data",))
  prefetcher = prefetch.ShardedPrefetcher(
      Checked(make_batches(), k, lend),
      prefetch.stacked_sharding(prefetch.make_data_sharding(mesh)),
      buffer_size=2)  # as the trainers: four dispatches may be live
  sums = jax.jit(lambda tree: {key: _device_row_sums(x)
                               for key, x in _flat(tree).items()})
  mismatches = seen = 0
  t0 = time.monotonic()
  try:
    for seq, placed in enumerate(prefetcher):
      got = jax.device_get(sums(placed))
      del placed
      for want in [host_sums[seq]] + (
          [want_sums(seq)] if want_sums is not None else []):
        for key, row_sums in want.items():
          mismatches += int(not np.array_equal(got[key], row_sums))
      seen += 1
      if seen == dispatches:
        break
      time.sleep(consumer_sleep)
  finally:
    prefetcher.close()
  seconds = time.monotonic() - t0
  reused, fresh = _counts()

  def durations(name):
    return [s["dur"] for s in telemetry.get_tracer().snapshot_spans()
            if s["name"] == name]

  waits = durations("feed.buffer_wait")
  in_place, copied = _gather_counts()
  return {
      "dispatches": seen, "mismatches": mismatches,
      "buffers": len(host_ids), "reused": reused, "fresh": fresh,
      "in_place_batches": in_place, "copied_batches": copied,
      "seconds": seconds,
      "buffer_wait_s": {"count": len(waits), "total": sum(waits),
                        "max": max(waits, default=0.0)},
      "stack_s_median": float(np.median(durations("feed.stack"))),
      "sample_s_median": float(np.median(durations("feed.sample"))),
      "device_put_s_median": float(
          np.median(durations("feed.device_put"))),
  }


def replay_with_row_sums(spec, rows, batch, k, seed, block_rows):
  """A `ReplayBuffer` (one shard, uniform) holding `rows` distinct
  rows of `spec`, and `want_sums(seq)`: the row sums dispatch `seq` of
  its stream has to hold, from the sums of the rows as they were added
  and the store's own seeded draw (`rng.integers(0, rows, batch)` a
  batch, the legacy-exact draw tests/test_replay.py pins), so it never
  looks at the memory the stream gathers into."""
  from tensor2robot_tpu.research.qtopt.replay_buffer import ReplayBuffer
  buffer = ReplayBuffer(spec, capacity=rows, seed=seed)
  rng = np.random.default_rng(seed + 1)
  flat = buffer.store.transition_spec.to_flat_dict()
  base = {}
  for key, leaf in flat.items():
    shape = (block_rows,) + tuple(leaf.shape)
    base[key] = (rng.integers(0, 256, shape, dtype=np.uint8)
                 if np.dtype(leaf.dtype) == np.uint8
                 else rng.uniform(-1, 1, shape).astype(leaf.dtype))
  row_sums = {key: [] for key in flat}
  for n in range(rows // block_rows):
    block = {key: (x ^ np.uint8(n) if x.dtype == np.uint8
                   else x + np.asarray(n, x.dtype))
             for key, x in base.items()}
    buffer.add(block)
    for key, x in block.items():
      row_sums[key].append(_host_row_sums(x[None])[0])
  row_sums = {key: np.concatenate(v) for key, v in row_sums.items()}
  assert len(buffer) == rows
  draws = np.random.default_rng(seed)

  def want_sums(seq):
    ids = [draws.integers(0, rows, size=batch) for _ in range(k)]
    return {key: np.stack([sums[i] for i in ids])
            for key, sums in row_sums.items()}

  return buffer, want_sums


def test_row_sums_agree_between_host_and_device():
  import jax
  rng = np.random.default_rng(0)
  for x in (rng.integers(0, 256, (2, 3, 4, 4), dtype=np.uint8),
            rng.integers(0, 256, (2, 3, 5), dtype=np.uint8),
            rng.standard_normal((2, 3, 7)).astype(np.float32),
            rng.standard_normal((2, 3)).astype(np.float32)):
    np.testing.assert_array_equal(
        jax.device_get(_device_row_sums(jax.numpy.asarray(x))),
        _host_row_sums(x))


# ---- the chip run ----


def _cell_config(cell, root):
  """(a benchmark cell's configuration, its transition spec)."""
  import json
  with open(os.path.join(root, "benchmark", "configs",
                         f"{cell}.json")) as f:
    config = json.load(f)
  from benchmark.harness import program
  return config, program.build_learner(config).transition_specification()


def _cell_batches(config, spec, seed):
  """A maker of an endless stream of batches of a benchmark cell's
  transition spec at its batch size. The stream cycles through a pool
  of 2K + 1 distinct batches: a fresh 200 MB batch a pull would cost
  the page faults this check is not about, and with a pool that long
  consecutive dispatches, and the two that share a slot, all differ."""
  k = config["train"]["steps_per_dispatch"]
  rows = config["train"]["batch_size_per_chip"]
  rng = np.random.default_rng(seed)
  pool = [{} for _ in range(2 * k + 1)]
  for key, leaf in spec.to_flat_dict().items():
    shape = (rows,) + tuple(leaf.shape)
    if np.dtype(leaf.dtype) == np.uint8:
      base = rng.integers(0, 256, shape, dtype=np.uint8)
    else:
      base = rng.uniform(-1, 1, shape).astype(leaf.dtype)
    for n, batch in enumerate(pool):
      batch[key] = (base ^ np.uint8(n) if base.dtype == np.uint8
                    else base + np.asarray(n, base.dtype))

  return lambda: itertools.cycle(pool)


def main(argv):
  import argparse
  import json
  parser = argparse.ArgumentParser()
  parser.add_argument("--cell", required=True)
  parser.add_argument("--dispatches", type=int, default=24)
  parser.add_argument("--seed", type=int, default=2147480101)
  args = parser.parse_args(argv)
  # Run as a program, the repo's root is not on the path.
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  sys.path.insert(0, root)
  import jax
  from tensor2robot_tpu.telemetry import core as tcore
  from tensor2robot_tpu.telemetry import metrics as tmetrics
  device = jax.devices()[0]
  config, spec = _cell_config(args.cell, root)
  k = config["train"]["steps_per_dispatch"]
  batch = config["train"]["batch_size_per_chip"]
  ok = True
  for path in ("stacked", "in_place"):
    tcore.reset_for_tests()
    tmetrics.reset_for_tests()
    if path == "stacked":  # a source that takes no destination
      result = check_placed_against_host(
          _cell_batches(config, spec, args.seed), k, args.dispatches,
          devices=[device])
    else:  # the replay buffer, two batches of rows, gathering in place
      buffer, want_sums = replay_with_row_sums(
          spec, rows=2 * batch, batch=batch, k=k, seed=args.seed,
          block_rows=batch // 4)
      result = check_placed_against_host(
          lambda: buffer.as_stream(batch), k, args.dispatches,
          devices=[device], lend=buffer.gather_next_into,
          want_sums=want_sums)
      del buffer
    result.update(cell=args.cell, path=path, platform=device.platform,
                  device_kind=device.device_kind)
    print(json.dumps(result))
    ok = ok and (result["mismatches"] == 0
                 and result["dispatches"] == args.dispatches)
    if device.platform != "cpu":  # there the ring must have engaged
      ok = ok and result["fresh"] == 0 and result["buffers"] == RING
      if path == "in_place":  # and every batch but the first K landed
        ok = ok and result["copied_batches"] == k
  return 0 if ok else 1


if __name__ == "__main__":
  sys.exit(main(sys.argv[1:]))
