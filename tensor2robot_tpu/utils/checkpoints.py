"""Orbax-backed checkpointing: save/restore/poll, warm-start, resharding.

Reference parity: Estimator auto-checkpointing + `maybe_init_from_checkpoint`
warm start + predictors polling `model_dir` for new checkpoints
(SURVEY.md §6 "Checkpoint/resume"). TPU-native: orbax with async save
(device→host copy happens immediately, serialization overlaps training)
and restore-with-resharding (restored arrays adopt whatever sharding the
target abstract pytree carries — checkpoints move freely between mesh
shapes).

Layout: `<model_dir>/ckpt/<step>/{state,params}` — `state` is the full
TrainState pytree; `params` duplicates the (small, CNN-scale) inference
variables `{"params": ..., "batch_stats": ...}` so warm-start and
predictors can restore serving weights — INCLUDING batch-norm moving
averages, which the reference's full-checkpoint restore also carried —
without knowing the optimizer. A `<step>` directory is only visible
once finalized (orbax writes atomically), so pollers never see partial
checkpoints.
"""

from __future__ import annotations

import functools
import importlib.metadata
import os
import re
import time
from typing import Any, List, Optional

import jax
import numpy as np

# orbax's cloud logger imports google.cloud packages; each asks this for
# a version warning as it is imported, a stat of every installed file
# (17,447): the second call took 25-35 s of the trainer's 35-44 s import
# on the v5e's machine (PERF.md §6, PR 30). One answer serves them all.
importlib.metadata.packages_distributions = functools.cache(
    importlib.metadata.packages_distributions)
import orbax.checkpoint as ocp  # noqa: E402

from tensor2robot_tpu import telemetry  # noqa: E402

CKPT_SUBDIR = "ckpt"


def _ckpt_root(model_dir: str) -> str:
  return os.path.join(model_dir, CKPT_SUBDIR)


def list_steps(model_dir: str, subdir: str = "state") -> List[int]:
  """Lists steps whose `subdir` payload has been finalized.

  state/ and params/ are written by independent async checkpointers
  (each with its own atomic rename), so a step only counts once the
  SPECIFIC payload the caller intends to restore exists — otherwise a
  poller could pick up a step whose other half finalized first.
  """
  root = _ckpt_root(model_dir)
  if not os.path.isdir(root):
    return []
  steps = []
  for entry in os.listdir(root):
    if re.fullmatch(r"\d+", entry) and not entry.endswith(".tmp"):
      if os.path.isdir(os.path.join(root, entry, subdir)):
        steps.append(int(entry))
  return sorted(steps)


def latest_step(model_dir: str, subdir: str = "state") -> Optional[int]:
  steps = list_steps(model_dir, subdir)
  return steps[-1] if steps else None


class CheckpointWriter:
  """Async orbax writer with retention.

  `save()` returns as soon as device arrays are copied to host; disk
  serialization overlaps subsequent training steps (the reference's
  checkpointing blocked the Estimator loop).
  """

  def __init__(self, model_dir: str, max_to_keep: Optional[int] = 5):
    self._root = _ckpt_root(model_dir)
    os.makedirs(self._root, exist_ok=True)
    self._checkpointer = ocp.AsyncCheckpointer(
        ocp.StandardCheckpointHandler())
    self._params_checkpointer = ocp.AsyncCheckpointer(
        ocp.StandardCheckpointHandler())
    self._max_to_keep = max_to_keep
    # step → payload subdirs still being serialized. Pruned by
    # completion (orbax's atomic rename makes the payload dir visible
    # exactly when its async save finishes), NOT only by wait():
    # otherwise, once the retention window fills, every save() finds
    # its GC victim "pending" and degrades to a full synchronous wait.
    self._pending_steps: dict = {}

  def save(self, step: int, state: Any, params: Optional[Any] = None,
           batch_stats: Optional[Any] = None, force: bool = False) -> None:
    payloads = ["state"]
    self._save(self._checkpointer, step, "state", state, force)
    if params is None:
      params = getattr(state, "params", None)
    if batch_stats is None:
      # Callers that pass params explicitly still get their BN stats
      # saved — losing them silently is the bug this payload fixes.
      batch_stats = getattr(state, "batch_stats", None)
    if params is not None:
      # Inference payload: params AND batch-norm statistics. Serving a
      # BN model with fresh-init stats silently degrades predictions,
      # so the stats ride with the weights.
      variables = {"params": params, "batch_stats": batch_stats or {}}
      self._save(self._params_checkpointer, step, "params", variables,
                 force)
      payloads.append("params")
    self._pending_steps[int(step)] = payloads
    with telemetry.span("ckpt.gc", step=step):
      self._gc()

  def _save(self, checkpointer, step: int, payload: str, tree: Any,
            force: bool) -> None:
    """One payload's save as two spans. A checkpointer's `save` first
    waits for its own last save to commit: the same wait, made just
    before, is a span of its own, and `ckpt.save_<payload>` is the
    copy to the host and the hand-over to the writing thread."""
    with telemetry.span("ckpt.wait_previous", step=step,
                        payload=payload):
      checkpointer.wait_until_finished()
    nbytes = sum(getattr(leaf, "nbytes", 0)
                 for leaf in jax.tree_util.tree_leaves(tree))
    with telemetry.span(f"ckpt.save_{payload}", step=step,
                        bytes=nbytes):
      checkpointer.save(
          os.path.join(self._root, str(int(step)), payload),
          args=ocp.args.StandardSave(tree), force=force)

  def wait(self) -> None:
    self._checkpointer.wait_until_finished()
    self._params_checkpointer.wait_until_finished()
    self._pending_steps.clear()

  def close(self) -> None:
    self.wait()
    self._checkpointer.close()
    self._params_checkpointer.close()

  def _step_is_finished(self, step: int) -> bool:
    """Have all of `step`'s async payloads been finalized on disk?

    Orbax serializes into a tmpdir and atomically renames it to the
    payload path on commit, so the payload dir existing under its
    final name IS the completion signal (the same invariant
    `list_steps` pollers rely on).
    """
    step_dir = os.path.join(self._root, str(step))
    return all(os.path.isdir(os.path.join(step_dir, payload))
               for payload in self._pending_steps.get(step, ()))

  def _prune_finished(self) -> None:
    for step in list(self._pending_steps):
      if self._step_is_finished(step):
        del self._pending_steps[step]

  def _gc(self) -> None:
    if self._max_to_keep is None:
      return
    import shutil
    self._prune_finished()
    steps = sorted(
        int(e) for e in os.listdir(self._root)
        if re.fullmatch(r"\d+", e))
    excess = len(steps) - self._max_to_keep
    for step in steps[:max(excess, 0)]:
      # Steady-state deletions target old, long-finished saves (pruned
      # above); only block when the victim is genuinely still in
      # flight (pathological max_to_keep < save cadence), so async
      # overlap is preserved across an arbitrarily long run.
      if step in self._pending_steps:
        self.wait()
      shutil.rmtree(os.path.join(self._root, str(step)),
                    ignore_errors=True)


def _abstract_like(tree: Any) -> Any:
  """Target pytree of ShapeDtypeStructs carrying shardings for restore."""

  def leaf(x):
    if isinstance(x, jax.Array):
      return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    if isinstance(x, (np.ndarray, np.generic)):
      return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype)
    return x

  return jax.tree_util.tree_map(leaf, tree)


def reshard_like(like: Any, mesh, rules, *,
                 min_size_to_shard: int = 2 ** 10) -> Any:
  """Abstract twin of `like` carrying rules-table target shardings.

  The restore half of the rules seam (`parallel/rules.py`,
  docs/SHARDING.md): a checkpoint saved under ANY mesh layout restores
  directly onto ANY other — pass the result as `restore_state`'s
  ``like`` and every array lands placed per the table. `rules` is an
  ordered (regex, placement) table (e.g. `parallel.family_rules(
  "qtopt")` or a strategy table); ``mesh`` is the TARGET mesh.
  """
  from tensor2robot_tpu.parallel import rules as rules_lib

  shardings = rules_lib.specs_to_shardings(
      mesh, rules_lib.match_partition_rules(
          rules, like, mesh, min_size_to_shard=min_size_to_shard))

  def leaf(x, sharding):
    shape = np.shape(x) if not hasattr(x, "shape") else x.shape
    dtype = getattr(x, "dtype", None)
    if dtype is None:
      return x
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

  return jax.tree_util.tree_map(leaf, like, shardings)


def restore_state_on_mesh(model_dir: str, like: Any, mesh, rules,
                          step: Optional[int] = None,
                          min_size_to_shard: int = 2 ** 10) -> Any:
  """`restore_state` with the target layout derived from a rules
  table instead of `like`'s current placement — the reshard-on-restore
  entry point (pod checkpoint → serving mesh, relayout after a
  topology change)."""
  return restore_state(
      model_dir,
      reshard_like(like, mesh, rules,
                   min_size_to_shard=min_size_to_shard),
      step=step)


def restore_state(model_dir: str, like: Any,
                  step: Optional[int] = None) -> Any:
  """Restores a full TrainState; arrays adopt `like`'s shardings."""
  if step is None:
    step = latest_step(model_dir)
    if step is None:
      raise FileNotFoundError(
          f"No checkpoints found under {_ckpt_root(model_dir)}")
  path = os.path.join(_ckpt_root(model_dir), str(int(step)), "state")
  with ocp.StandardCheckpointer() as checkpointer:
    return checkpointer.restore(path, _abstract_like(like))


def _find_params_path(path_or_model_dir: str,
                      step: Optional[int] = None) -> str:
  candidates = []
  if step is not None:
    candidates.append(os.path.join(
        _ckpt_root(path_or_model_dir), str(int(step)), "params"))
  else:
    found = latest_step(path_or_model_dir, subdir="params")
    if found is not None:
      candidates.append(os.path.join(
          _ckpt_root(path_or_model_dir), str(found), "params"))
    candidates.append(os.path.join(path_or_model_dir, "params"))
    candidates.append(path_or_model_dir)
  for path in candidates:
    if os.path.isdir(path):
      return path
  raise FileNotFoundError(
      f"No params checkpoint found at any of: {candidates}")


def _is_variables_payload(tree: Any) -> bool:
  return (isinstance(tree, dict)
          and "params" in tree
          and set(tree) <= {"params", "batch_stats"})


def _adopt_like(like: Any, restored: Any) -> Any:
  """Host-restored leaves adopt `like`'s dtypes and shardings."""

  def leaf(l, x):
    if isinstance(l, jax.Array):
      return jax.device_put(jax.numpy.asarray(x, l.dtype), l.sharding)
    return np.asarray(x)

  return jax.tree_util.tree_map(leaf, like, restored)


def restore_variables(path_or_model_dir: str, like: Any,
                      step: Optional[int] = None) -> Any:
  """Restores the inference payload `{"params", "batch_stats"}`.

  `like` must be a dict with "params" and "batch_stats" entries (the
  latter may be an empty dict); restored arrays adopt its shardings.
  Predictors use this so BN moving averages survive the
  trainer→predictor handoff — the reference restored full checkpoints,
  moving averages included. Payloads written before batch_stats rode
  along (bare params trees) still restore; their stats fall back to
  `like`'s (the old, stale-stats behavior, with a warning).
  """
  path = _find_params_path(path_or_model_dir, step)
  with ocp.StandardCheckpointer() as checkpointer:
    restored = checkpointer.restore(path)
  if not _is_variables_payload(restored):
    import logging
    logging.getLogger(__name__).warning(
        "Params payload at %s predates batch_stats bundling; BN stats "
        "keep their current (init) values.", path)
    restored = {"params": restored, "batch_stats": None}
  out = {"params": _adopt_like(like["params"], restored["params"])}
  like_stats = like.get("batch_stats", {})
  restored_stats = restored.get("batch_stats")
  if restored_stats:
    out["batch_stats"] = _adopt_like(like_stats, restored_stats)
  else:
    out["batch_stats"] = like_stats
  return out


def restore_params(path_or_model_dir: str, like: Any,
                   step: Optional[int] = None) -> Any:
  """Restores just params — for warm starts.

  `like` is the params subtree alone. The payload also carries
  batch_stats, whose structure the caller may not know, so the payload
  is read target-free and the params subtree extracted; leaves then
  adopt `like`'s shardings. Accepts a model_dir (picks latest step), a
  step dir, or a direct params checkpoint path.
  """
  path = _find_params_path(path_or_model_dir, step)
  with ocp.StandardCheckpointer() as checkpointer:
    restored = checkpointer.restore(path)
  if _is_variables_payload(restored):
    restored = restored["params"]
  return _adopt_like(like, restored)


def wait_for_new_checkpoint(
    model_dir: str,
    last_step: Optional[int] = None,
    timeout_secs: Optional[float] = None,
    poll_interval_secs: float = 1.0,
    subdir: str = "state",
) -> Optional[int]:
  """Blocks until a checkpoint newer than `last_step` appears.

  Reference parity: predictors' poll/wait for new checkpoints
  (SURVEY.md §4.4). Returns the new step, or None on timeout.
  `subdir` selects which payload must be finalized ("params" for
  predictors that only restore parameters).
  """
  deadline = (time.time() + timeout_secs) if timeout_secs is not None \
      else None
  while True:
    step = latest_step(model_dir, subdir=subdir)
    if step is not None and (last_step is None or step > last_step):
      return step
    if deadline is not None and time.time() > deadline:
      return None
    time.sleep(poll_interval_secs)
