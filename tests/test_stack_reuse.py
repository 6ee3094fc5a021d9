"""`StackedBatchStream`'s ring of reused host buffers (ISSUE 25;
data/prefetch.py): what is stacked into a slot is what `np.stack`
gives, a slot is written again only when the arrays made from it are
ready, and a consumer that did not ask gets independent arrays.

Also the check of the acceptance criteria that needs the chip: run as
a program (the suite's conftest pins the CPU),

  python tests/test_stack_reuse.py --cell qtopt_64 --dispatches 24

it drives a `ShardedPrefetcher` over dispatches of a benchmark cell's
shapes and compares a checksum of every placed array with that of its
host buffer taken before the yield.
"""

import gc
import itertools
import logging
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
    # Two buffers took every dispatch in turn, and each was waited for
    # once, before the dispatch that wrote over it.
    assert {id(b) for b in buffers} == {id(b) for b in buffers[:RING]}
    assert buffers[0] is not buffers[1]
    assert all(buffers[i] is buffers[i % RING]
               for i in range(dispatches))
    assert [p.waited for p in placed] == \
        [1] * (dispatches - RING) + [0] * RING
    # What the consumer copied is still each dispatch's own.
    for seq, p in enumerate(placed):
      _assert_dispatch(p.copy, seq)
    assert _counts() == (dispatches, 0)

  def test_a_slot_is_not_written_before_its_arrays_are_ready(self):
    stream = _reusing(3 * K)
    first = next(stream)
    held = Placed(first, ready=False)
    stream.transfer_started(held)
    stream.transfer_started(Placed(next(stream)))
    result = []
    puller = threading.Thread(target=lambda: result.append(next(stream)),
                              daemon=True)
    puller.start()
    deadline = time.monotonic() + 30
    while not held.waited and time.monotonic() < deadline:
      time.sleep(0.01)
    assert held.waited == 1
    time.sleep(0.2)
    # The third dispatch wants the first one's slot, whose reader is
    # still at it: nothing has been written.
    assert puller.is_alive() and not result
    _assert_dispatch(first, 0)
    held.ready.set()
    puller.join(timeout=30)
    assert not puller.is_alive()
    assert result[0]["image"] is first["image"]
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


def _make_placement_copy(monkeypatch, prefetch):
  """CPU devices that behave like an accelerator's: placement reads a
  private copy of the host bytes."""
  real = prefetch.device_put_batch
  monkeypatch.setattr(prefetch, "_copies_off_host", lambda s: True)
  monkeypatch.setattr(
      prefetch, "device_put_batch",
      lambda batch, sharding: real(
          {key: np.array(x) for key, x in batch.items()}, sharding))


# ---- the check on placed arrays, shared with the chip run ----


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
                              consumer_sleep=0.0, devices=None):
  """Drives `ShardedPrefetcher(stack_batches(...))` for `dispatches`
  dispatches; returns how many placed leaves' row sums differ from
  those of their host buffer taken before the yield."""
  import jax
  from tensor2robot_tpu import telemetry
  prefetch = _prefetch()
  host_sums, host_ids = [], set()

  class Checked(prefetch.StackedBatchStream):

    def __next__(self):
      stacked = super().__next__()
      host_sums.append({key: _host_row_sums(x)
                        for key, x in stacked.items()})
      host_ids.add(id(stacked["image"]))
      return stacked

  telemetry.configure("stack_reuse_check")
  mesh = jax.sharding.Mesh(
      np.array(devices if devices is not None else jax.devices()),
      ("data",))
  prefetcher = prefetch.ShardedPrefetcher(
      Checked(make_batches(), k),
      prefetch.stacked_sharding(prefetch.make_data_sharding(mesh)),
      buffer_size=2)  # as the trainers: four dispatches may be live
  sums = jax.jit(lambda tree: {key: _device_row_sums(x)
                               for key, x in tree.items()})
  mismatches = seen = 0
  t0 = time.monotonic()
  try:
    for seq, placed in enumerate(prefetcher):
      got = jax.device_get(sums(placed))
      del placed
      for key, want in host_sums[seq].items():
        mismatches += int(not np.array_equal(got[key], want))
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
  return {
      "dispatches": seen, "mismatches": mismatches,
      "buffers": len(host_ids), "reused": reused, "fresh": fresh,
      "seconds": seconds,
      "buffer_wait_s": {"count": len(waits), "total": sum(waits),
                        "max": max(waits, default=0.0)},
      "stack_s_median": float(np.median(durations("feed.stack"))),
      "device_put_s_median": float(
          np.median(durations("feed.device_put"))),
  }


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


def _cell_batches(cell, seed):
  """(K, a maker of an endless stream of batches) of a benchmark
  cell's transition spec at its batch size. The stream cycles through
  a pool of 2K + 1 distinct batches: a fresh 200 MB batch a pull would
  cost the page faults this check is not about, and with a pool that
  long consecutive dispatches, and the two that share a slot, all
  differ."""
  import json
  import os
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  sys.path.insert(0, root)
  with open(os.path.join(root, "benchmark", "configs",
                         f"{cell}.json")) as f:
    config = json.load(f)
  from benchmark.harness import program
  spec = program.build_learner(
      config).transition_specification().to_flat_dict()
  k = config["train"]["steps_per_dispatch"]
  rows = config["train"]["batch_size_per_chip"]
  rng = np.random.default_rng(seed)
  pool = [{} for _ in range(2 * k + 1)]
  for key, leaf in spec.items():
    shape = (rows,) + tuple(leaf.shape)
    if np.dtype(leaf.dtype) == np.uint8:
      base = rng.integers(0, 256, shape, dtype=np.uint8)
    else:
      base = rng.uniform(-1, 1, shape).astype(leaf.dtype)
    for n, batch in enumerate(pool):
      batch[key] = (base ^ np.uint8(n) if base.dtype == np.uint8
                    else base + np.asarray(n, base.dtype))

  return k, lambda: itertools.cycle(pool)


def main(argv):
  import argparse
  import json
  parser = argparse.ArgumentParser()
  parser.add_argument("--cell", required=True)
  parser.add_argument("--dispatches", type=int, default=24)
  parser.add_argument("--seed", type=int, default=2147480101)
  args = parser.parse_args(argv)
  import jax
  device = jax.devices()[0]
  k, batches = _cell_batches(args.cell, args.seed)
  result = check_placed_against_host(batches, k, args.dispatches,
                                     devices=[device])
  result.update(cell=args.cell, platform=device.platform,
                device_kind=device.device_kind)
  print(json.dumps(result))
  ok = (result["mismatches"] == 0
        and result["dispatches"] == args.dispatches)
  if device.platform != "cpu":  # there the ring must have engaged
    ok = ok and result["fresh"] == 0 and result["buffers"] == RING
  return 0 if ok else 1


if __name__ == "__main__":
  sys.exit(main(sys.argv[1:]))
