"""The comparison that decides `correct`: what the timed path produced
against the plain reference, number by number, each under a limit of
its own (`benchmark/limits/<cell>.json`; PERF.md gives the readings
each limit was set from)."""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import weights
from benchmark.reference import qnet

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_limits(cell_name: str) -> Dict[str, float]:
  with open(os.path.join(HERE, "limits", f"{cell_name}.json")) as f:
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


def _leaf_norms(tree: Dict[str, np.ndarray]) -> Dict[str, float]:
  return {k: float(np.linalg.norm(np.asarray(v, np.float64)))
          for k, v in tree.items()}


def worst_leaf_gap(program: Dict[str, np.ndarray],
                   reference: Dict[str, np.ndarray]) -> float:
  """max over leaves of |‖p‖ - ‖r‖| / max(‖r‖, median leaf ‖r‖): the
  gap between the norms, not the norm of the difference, on the scale
  of the leaf or of the median leaf, whichever is larger (some leaves
  are all but zero)."""
  p, r = _leaf_norms(program), _leaf_norms(reference)
  floor = float(np.median(list(r.values())))
  return max(abs(p[k] - r[k]) / max(r[k], floor, 1e-30) for k in r)


def follow_reference(config: dict, inputs: dict, seed32: int,
                     quant: qnet.Quant = qnet.REFERENCE):
  """The reference through the first dispatch: K Bellman steps on the
  K batches the loop's stream yielded first, from the benchmark's
  weights. Returns (state after K steps, last step's metrics)."""
  cfg = qnet.NetConfig.from_config(config)
  rows = config["reference"]["cem_rows_per_block"]
  step_fn = jax.jit(
      lambda state, batch, rng: qnet.bellman_step(
          cfg, state, batch, rng, quant, rows))
  with jax.default_matmul_precision("highest"):
    k = len(inputs["batches"])
    step0 = inputs["first_step"] - k
    state = qnet.init_state(
        {k: jnp.asarray(v) for k, v in inputs["params"].items()},
        {k: jnp.asarray(v) for k, v in inputs["stats"].items()},
        step0, weights.ADAM_NU0)
    # The loop keys step s with fold_in(PRNGKey(seed + 1), s).
    step_rng = jax.random.PRNGKey(seed32 + 1)
    metrics = None
    for i, batch in enumerate(inputs["batches"]):
      state, metrics = step_fn(
          state, {key: jnp.asarray(v) for key, v in batch.items()},
          jax.random.fold_in(step_rng, step0 + i))
    state = jax.device_get(state)
    metrics = {key: float(v) for key, v in metrics.items()}
  return state, metrics


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
  reference after the same steps, `start` the weights both began at."""
  delta = {k: np.asarray(got_state["params"][k], np.float64) - start[k]
           for k in start}
  ref_delta = {k: np.asarray(ref_state["params"][k], np.float64)
               - start[k] for k in start}
  return {
      "loss_rel_gap": abs(got_metrics["loss"] - ref_metrics["loss"])
      / abs(ref_metrics["loss"]),
      "grad_norm_rel_gap":
          abs(got_metrics["grad_norm"] - ref_metrics["grad_norm"])
          / ref_metrics["grad_norm"],
      "q_next_mean_gap":
          abs(got_metrics["q_next_mean"] - ref_metrics["q_next_mean"]),
      "adam_mu_worst_leaf_gap": worst_leaf_gap(got_state["mu"],
                                               ref_state["mu"]),
      "param_change_worst_leaf_gap": worst_leaf_gap(delta, ref_delta),
      "bn_stats_worst_leaf_gap": worst_leaf_gap(got_state["stats"],
                                                ref_state["stats"]),
  }


def train_numbers(inputs: dict, ref_state: dict,
                  ref_metrics: Dict[str, float]) -> Dict[str, float]:
  """`numbers_between` for the loop's first dispatch: its last step's
  metrics and the state it checkpointed."""
  state = inputs["first_state"]
  got = {"params": weights.flatten(state.params),
         "mu": _adam_mu(state.opt_state),
         "stats": weights.flatten(state.batch_stats)}
  return numbers_between(got, inputs["first_metrics"],
                         inputs["params"], ref_state, ref_metrics)


def check_train(cell_name: str, config: dict, run: dict,
                limits: Optional[Dict[str, float]] = None,
                out=print) -> bool:
  inputs = run["check_inputs"]
  if inputs["first_state"] is None or len(inputs["batches"]) != run["k"]:
    out("check: the loop gave no first checkpoint or too few batches")
    return False
  ref_state, ref_metrics = follow_reference(config, inputs,
                                            run["seed32"])
  numbers = train_numbers(inputs, ref_state, ref_metrics)
  return verdict(numbers, limits or load_limits(cell_name), out)
