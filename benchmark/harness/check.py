"""The comparison that decides `correct`: what the timed path produced
against the plain reference, number by number, each under a limit of
its own (`benchmark/limits/<cell>.json`; PERF.md gives the readings
each limit was set from)."""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

import jax
import numpy as np

from benchmark.harness import weights


def load_limits(data_dir: str, cell_name: str) -> Dict[str, float]:
  """`<data_dir>/limits/<cell>.json` without its notes (keys that
  start with `_`)."""
  with open(os.path.join(data_dir, "limits",
                         f"{cell_name}.json")) as f:
    return {k: v for k, v in json.load(f).items()
            if not k.startswith("_")}


def verdict(numbers: Dict[str, float], limits: Dict[str, float],
            out=print) -> bool:
  """Prints each number beside its limit; True when all hold."""
  ok = True
  for name, limit in limits.items():
    value = numbers.get(name)
    held = value is not None and np.isfinite(value) and value <= limit
    ok = ok and bool(held)
    out(f"check {name}: {value!r} limit {limit!r} "
        f"{'ok' if held else 'FAILED'}")
  return ok


def _leaf_terms(program: Dict[str, np.ndarray],
                reference: Dict[str, np.ndarray],
                start: Optional[Dict[str, np.ndarray]] = None):
  """(‖p‖, ‖r‖, Σ(p − r)², Σr²) for every leaf of `reference`, in its
  order: p and r are the program's leaf and the reference's in
  float64, each less `start`'s leaf where `start` is given (a
  parameter's change over the steps). A leaf at a time, in two buffers
  of the largest leaf's size that every leaf is read into and worked
  on in place: a state of any size costs the host 16 bytes for each
  element of its largest leaf, and those pages fault in once. Two
  whole trees of changes in float64, and a fresh array for every
  intermediate, cost 16 bytes a parameter and, on the chip machine's
  host (4 KB pages), 50 s at 369 M parameters against 8 s at 721 M
  here (PERF.md §6, PR 33). The arithmetic is that of the whole-tree
  form, operation for operation, so every number is the same to the
  last digit."""
  largest = max((np.size(leaf) for leaf in reference.values()),
                default=0)
  buffers = np.empty((2, max(largest, 1)), np.float64)
  for key, leaf in reference.items():
    p, r = (buffer[:np.size(leaf)].reshape(np.shape(leaf))
            for buffer in buffers)
    np.copyto(p, program[key])
    np.copyto(r, leaf)
    if start is not None:
      np.subtract(p, start[key], out=p)
      np.subtract(r, start[key], out=r)
    p_norm, r_norm = float(np.linalg.norm(p)), float(np.linalg.norm(r))
    np.subtract(p, r, out=p)
    diff = float(np.sum(np.square(p, out=p)))
    size = float(np.sum(np.square(r, out=r)))
    yield p_norm, r_norm, diff, size


def _gap_and_err(program, reference, start=None):
  """(`worst_leaf_gap`, `rel_err`) of two trees in one pass over their
  leaves; with `start`, of their changes from it."""
  terms = list(_leaf_terms(program, reference, start))
  floor = float(np.median([r_norm for _, r_norm, _, _ in terms]))
  gap = max(abs(p_norm - r_norm) / max(r_norm, floor, 1e-30)
            for p_norm, r_norm, _, _ in terms)
  diff = size = 0.0
  for _, _, leaf_diff, leaf_size in terms:
    diff += leaf_diff
    size += leaf_size
  return gap, float(np.sqrt(diff / max(size, 1e-300)))


def worst_leaf_gap(program: Dict[str, np.ndarray],
                   reference: Dict[str, np.ndarray]) -> float:
  """max over leaves of |‖p‖ - ‖r‖| / max(‖r‖, median leaf ‖r‖): the
  gap between the norms, not the norm of the difference, on the scale
  of the leaf or of the median leaf, whichever is larger (some leaves
  are all but zero)."""
  return _gap_and_err(program, reference)[0]


def rel_err(program: Dict[str, np.ndarray],
            reference: Dict[str, np.ndarray]) -> float:
  """sqrt(Σ_leaves ‖p − r‖²) / sqrt(Σ_leaves ‖r‖²) in float64: the
  norm of the difference over all elements of the tree, not the gap
  between two norms. A sum of squares, one for every element, cannot
  cancel: it is 0 only where every element agrees, and the rounding
  of an element can only raise it, whatever the seed. A gap
  between norms (`worst_leaf_gap`) or between means is the absolute
  value of a signed difference, which passes through 0 as the seed
  varies, so its smallest reading shrinks with the number of seeds
  read and nothing can be held against it (PERF.md §2)."""
  return _gap_and_err(program, reference)[1]


def _adam_mu(opt_state) -> Dict[str, np.ndarray]:
  for part in jax.tree_util.tree_leaves(
      opt_state, is_leaf=lambda x: hasattr(x, "mu")):
    if hasattr(part, "mu"):
      return weights.flatten(part.mu)
  raise ValueError("no Adam moments in the optimizer state")


def numbers_between(got_state: dict, got_metrics: Dict[str, float],
                    start: Dict[str, np.ndarray], ref_state: dict,
                    ref_metrics: Dict[str, float]) -> Dict[str, float]:
  """The numbers compared for a train cell. `got_*` is what stands in
  the program's place (flat dicts: params, mu, stats), `ref_*` the
  reference after the same steps, `start` the weights both began at.
  `<name>_rel_gap` for every scalar of the reference's metrics
  (`loss`, `grad_norm` and what a family's reference adds),
  `q_next_mean_gap` only where the reference has a CEM target, the
  two `bn_stats_*` only where it has running statistics. The
  `*_rel_err` are norms of differences (`rel_err`): what a precision
  limit can be held on; the others are gaps between scalars or norms,
  held against gross faults."""
  # Every scalar both sides report, loss and gradient norm first.
  numbers = {
      f"{name}_rel_gap":
          abs(got_metrics[name] - ref) / max(abs(ref), 1e-30)
      for name, ref in ref_metrics.items() if name in got_metrics}
  if "q_next_mean" in ref_metrics:  # a mean of probabilities
    numbers["q_next_mean_gap"] = abs(
        got_metrics["q_next_mean"] - ref_metrics["q_next_mean"])
  mu_gap, mu_err = _gap_and_err(got_state["mu"], ref_state["mu"])
  # Of the parameters' change from `start`, not of the parameters.
  change_gap, change_err = _gap_and_err(
      got_state["params"],
      {key: ref_state["params"][key] for key in start}, start)
  numbers["adam_mu_worst_leaf_gap"] = mu_gap
  numbers["param_change_worst_leaf_gap"] = change_gap
  numbers["adam_mu_rel_err"] = mu_err
  numbers["param_change_rel_err"] = change_err
  if ref_state["stats"]:
    (numbers["bn_stats_worst_leaf_gap"],
     numbers["bn_stats_rel_err"]) = _gap_and_err(got_state["stats"],
                                                 ref_state["stats"])
  return numbers


def program_state(state) -> dict:
  """The train state a loop checkpointed after its first dispatch, as
  the flat dicts `numbers_between` takes."""
  return {"params": weights.flatten(state.params),
          "mu": _adam_mu(state.opt_state),
          "stats": weights.flatten(state.batch_stats)}


def numbers_of(follow, config: dict, run: dict,
               control=False) -> Dict[str, float]:
  """`numbers_between` for the loop's first dispatch (its last step's
  metrics and the state it checkpointed) against the reference after
  the same K steps, or with `control` (True, or the name of one of a
  kind's partial controls) for that control in the program's place
  against that same reference. `follow(config, inputs, seed32,
  control)` is a kind's own: (state, metrics) of the reference or of
  the control after the K steps."""
  inputs = run["check_inputs"]
  if "reference" not in run:  # the control is held against the same
    run["reference"] = follow(config, inputs, run["seed32"], False)
  if control:
    got_state, got_metrics = follow(config, inputs, run["seed32"],
                                    control)
  else:
    got_state, got_metrics = (program_state(inputs["first_state"]),
                              inputs["first_metrics"])
  return numbers_between(got_state, got_metrics, inputs["params"],
                         *run["reference"])


def decide(numbers, config: dict, run: dict,
           limits: Dict[str, float], out=print) -> bool:
  """`correct` of a run of a train loop: `numbers(config, run)` (a
  driver's) under `limits`, once the loop gave what they are read
  from. Leaves each number beside its limit under `run["compared"]`,
  for the result's line. No limit is held against the control: a run
  follows the reference alone (the control lives in
  `tools/read_limits.py` and the tests)."""
  inputs = run["check_inputs"]
  if inputs["first_state"] is None or len(inputs["batches"]) != run["k"]:
    out("check: the loop gave no first checkpoint or too few batches")
    return False
  read = numbers(config, run)
  # A number that is not finite goes as text: the result's line has to
  # stay JSON that any reader takes.
  run["compared"] = {
      name: {"value": value if value is None or np.isfinite(value)
             else repr(value), "limit": limit}
      for name, limit in limits.items()
      for value in (read.get(name),)}
  return verdict(read, limits, out)
