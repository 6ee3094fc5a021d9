"""The benchmark's rows for a `train_eval` cell: an input generator of
the program's own kind (`AbstractInputGenerator`) over a host table of
rows made from `--seed` to the model's TRAIN feature and label specs,
sampled per batch. The trainer's `stack_batches`, `ShardedPrefetcher`
and H2D take it from there, inside the window. The stream keeps its
first batches for the outputs check."""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmark.harness import window
from tensor2robot_tpu.data.abstract_input_generator import (
    AbstractInputGenerator)
from tensor2robot_tpu.specs import TensorSpecStruct


def make_table(spec: TensorSpecStruct, rows: int, int_below: Dict[str, int],
               rng: np.random.Generator) -> Dict[str, np.ndarray]:
  """`rows` rows to a flat spec: bytes uniform over 0..255, any other
  integer leaf uniform below the bound the configuration states for it
  (a sliced vocabulary draws its ids from the slice), floats uniform
  in [-1, 1)."""
  table = {}
  for key, leaf in spec.to_flat_dict().items():
    shape = (rows,) + tuple(leaf.shape)
    dtype = np.dtype(leaf.dtype)
    if dtype == np.uint8:
      table[key] = rng.integers(0, 256, shape, dtype=np.uint8)
    elif np.issubdtype(dtype, np.integer):
      if key not in int_below:
        raise ValueError(
            f"integer leaf {key!r}: the configuration's "
            f"train.int_below states no bound for it")
      table[key] = rng.integers(0, int_below[key], shape, dtype=dtype)
    else:
      table[key] = rng.uniform(-1.0, 1.0, shape).astype(dtype)
  return table


class _Batches:
  """The endless stream of sampled batches. A class, not a generator:
  the prefetcher closes its source from another thread."""

  def __init__(self, features, labels, rows: int, batch_size: int,
               rng: np.random.Generator):
    self._features, self._labels = features, labels
    self._rows, self._batch_size, self._rng = rows, batch_size, rng

  def __iter__(self):
    return self

  def __next__(self):
    rows = self._rng.integers(0, self._rows, self._batch_size)
    return tuple(
        TensorSpecStruct.from_flat_dict(
            {k: v[rows] for k, v in table.items()})
        for table in (self._features, self._labels))


class SeededRows(AbstractInputGenerator):
  """Batches of `batch_size` rows drawn, with the seed's generator,
  from a table of `rows` rows made once; `kept` holds the first `keep`
  batches as `{"features": {...}, "labels": {...}}`."""

  def __init__(self, rows: int, seed: int, keep: int,
               int_below: Dict[str, int], batch_size: int):
    super().__init__(batch_size=batch_size)
    self._rows, self._seed, self._keep = rows, seed, keep
    self._int_below = int_below
    self.kept: List[dict] = []

  def _create_dataset(self, mode, batch_size: int):
    rng = np.random.default_rng(self._seed)
    features = make_table(self.feature_spec, self._rows,
                          self._int_below, rng)
    labels = make_table(self.label_spec, self._rows, self._int_below,
                        rng)
    return window.KeepFirst(
        _Batches(features, labels, self._rows, batch_size, rng),
        self.kept, self._keep,
        lambda batch: {"features": dict(batch[0].to_flat_dict()),
                       "labels": dict(batch[1].to_flat_dict())})
