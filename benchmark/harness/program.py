"""Builds the system under test from a configuration file: the shipped
gin file plus the configuration's bindings, nothing else. Everything
here is a call into the program; the benchmark's own arithmetic lives
in the other modules."""

from __future__ import annotations

import importlib
import os
from typing import Dict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build_learner(config: dict, fix_scales: bool = True):
  """The `QTOptLearner` the configuration describes.
  `fix_scales=False` leaves the int8 scales to be calibrated (the tool
  that makes the configuration's scales needs that)."""
  from tensor2robot_tpu import config as gin
  from tensor2robot_tpu.bin import run_t2r_trainer
  for module in run_t2r_trainer._DEFAULT_MODULES:
    importlib.import_module(module)
  gin.clear_config()
  gin.parse_config_files_and_bindings(
      [os.path.join(ROOT, config["gin_file"])],
      list(config["gin_bindings"]))
  from tensor2robot_tpu.research.qtopt.qtopt_learner import QTOptLearner
  learner = QTOptLearner()
  check_sizes(learner, config)
  # The activation scales of the int8 tower are constants of the
  # compiled programs. The configuration fixes them, so that every
  # seed runs the same programs and only a cell's first run compiles.
  if fix_scales and config["int8_act_scales"]:
    learner.set_activation_scales(config["int8_act_scales"])
  return learner


def check_sizes(learner, config: dict) -> None:
  """The sizes the configuration file states are the sizes the program
  was built with: the reference and the FLOP count read the file."""
  model, net = learner.model, learner.model.network
  stated, cem = config["model"], config["cem"]
  built = {
      "image_size": model.image_size,
      "action_dim": model.action_dim,
      "space_to_depth": net.space_to_depth,
      "torso_filters": list(net.torso_filters),
      "head_filters": list(net.head_filters),
      "dense_sizes": list(net.dense_sizes),
      "action_embedding_size": net.action_embedding_size,
  }
  for key, value in built.items():
    if stated[key] != value:
      raise ValueError(
          f"{config['name']}: file states {key}={stated[key]}, the "
          f"program built {value}")
  built_cem = {"iterations": learner.cem_iterations,
               "population": learner.cem_population}
  for key, value in built_cem.items():
    if cem[key] != value:
      raise ValueError(
          f"{config['name']}: file states cem.{key}={cem[key]}, the "
          f"program built {value}")
  if learner.cem_inference != config["precision"]["cem_tower"]:
    raise ValueError(
        f"{config['name']}: file states the CEM tower in "
        f"{config['precision']['cem_tower']}, the program runs "
        f"{learner.cem_inference}")


def seeded_state(learner, params: Dict, stats: Dict, step: int):
  """The program's learner state holding the benchmark's weights: the
  tree comes from the program, every value from the benchmark."""
  import jax
  import jax.numpy as jnp

  from benchmark.harness import weights

  # Only the tree's shape is the program's: nothing is initialised.
  state = jax.eval_shape(
      lambda: learner.create_state(jax.random.PRNGKey(0), batch_size=2))
  ts = state.train_state
  placed = weights.place(ts.params, params)
  # Adam as a run `step` steps old holds it: its count at `step` and a
  # second moment that has long warmed up. From all-zero moments Adam's
  # first updates are the gradients' signs, and the rounding of a
  # near-zero gradient then moves a weight by a whole learning rate:
  # the K steps of a dispatch would amplify what the check measures.
  opt_state = tuple(
      part._replace(
          count=jnp.asarray(step, part.count.dtype),
          nu=jax.tree_util.tree_map(
              lambda x: jnp.full_like(x, weights.ADAM_NU0), part.nu))
      if hasattr(part, "nu") else part
      for part in learner.model.tx.init(placed))
  ts = ts.replace(
      step=jnp.asarray(step, ts.step.dtype),
      params=placed,
      batch_stats=weights.place(ts.batch_stats, stats),
      opt_state=opt_state)
  return state.replace(
      train_state=ts,
      target_params=jax.tree_util.tree_map(jnp.copy, placed))
