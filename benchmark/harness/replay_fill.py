"""Replay rows made from `--seed` and a `ReplayBuffer` that keeps the
first batches its stream hands out, so that the outputs check can give
the reference the very rows the first dispatch trained on."""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmark.harness import window
from tensor2robot_tpu.research.qtopt.replay_buffer import ReplayBuffer


def fill(buffer: ReplayBuffer, rows: int, seed: int,
         block_rows: int) -> None:
  """Adds `rows` distinct transitions through the buffer's own `add`.

  Random bytes for every row would be gigabytes of generator work that
  each run pays as set-up; instead one seeded block of `block_rows`
  rows is drawn, and each further block is that one with every image
  byte XOR-ed by the block's number: all rows differ, and the working
  set is the full `rows`, far beyond any CPU cache.
  """
  if rows % block_rows or rows // block_rows > 255:
    raise ValueError(f"{rows} rows do not split into at most 255 "
                     f"blocks of {block_rows}")
  rng = np.random.default_rng(seed)
  spec = buffer.store.transition_spec.to_flat_dict()
  base: Dict[str, np.ndarray] = {}
  for key, leaf in spec.items():
    if np.dtype(leaf.dtype) == np.uint8:
      base[key] = rng.integers(
          0, 256, (block_rows,) + tuple(leaf.shape), dtype=np.uint8)
  for block in range(rows // block_rows):
    chunk = {}
    for key, leaf in spec.items():
      shape = (block_rows,) + tuple(leaf.shape)
      if key in base:
        chunk[key] = base[key] ^ np.uint8(block)
      elif key == "reward":
        chunk[key] = (rng.random(shape) < 0.3).astype(leaf.dtype)
      elif key == "done":
        chunk[key] = (rng.random(shape) < 0.2).astype(leaf.dtype)
      else:  # actions and any further state vector
        chunk[key] = rng.uniform(-1.0, 1.0, shape).astype(leaf.dtype)
    buffer.add(chunk)


class RecordingReplay(ReplayBuffer):
  """The shipped buffer; its stream also keeps the first `keep` batches
  it yields (the host arrays the sampler just gathered; a leaf that is
  a view of memory the stream owns is copied: `window.KeepFirst`)."""

  def __init__(self, *args, keep: int = 0, **kwargs):
    super().__init__(*args, **kwargs)
    self._keep = keep
    self.kept: List[Dict[str, np.ndarray]] = []

  def as_stream(self, batch_size: int):
    return window.KeepFirst(super().as_stream(batch_size), self.kept,
                            self._keep,
                            lambda batch: dict(batch.to_flat_dict()))
