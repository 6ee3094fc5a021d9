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


def parse_gin(config: dict):
  """The configuration's gin file and bindings, parsed as
  `bin/run_t2r_trainer` parses them; returns the program's gin."""
  from tensor2robot_tpu import config as gin
  from tensor2robot_tpu.bin import run_t2r_trainer
  for module in run_t2r_trainer._DEFAULT_MODULES:
    importlib.import_module(module)
  gin.clear_config()
  gin.parse_config_files_and_bindings(
      [os.path.join(ROOT, config["gin_file"])],
      list(config["gin_bindings"]))
  return gin


def build_learner(config: dict, fix_scales: bool = True):
  """The `QTOptLearner` the configuration describes.
  `fix_scales=False` leaves the int8 scales to be calibrated (the tool
  that makes the configuration's scales needs that)."""
  parse_gin(config)
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


def build_model(config: dict):
  """The T2R model that the configuration's gin file binds to
  `train_eval_model.model`, checked against the file's `model` and
  `learner` blocks."""
  gin = parse_gin(config)
  # The binding is a parsed `@Model()` reference; resolving it builds
  # the model as the trainer's own call would.
  model = gin.query_parameter("train_eval_model.model").resolve()
  check_model_sizes(model, config)
  return model


def check_model_sizes(model, config: dict) -> None:
  """What the file's `model` block states is what was built (each key
  read from the model's attribute of that name, public or with a
  leading underscore), and the model's optimizer is the one the
  `learner` block states: on a probe it moves a weight as the plain
  Adam of `harness/follow.py` does with the block's numbers."""
  import jax.numpy as jnp
  import numpy as np

  from benchmark.harness import follow

  for key, stated in config["model"].items():
    built = getattr(model, key, getattr(model, f"_{key}", None))
    if isinstance(built, tuple):
      built = list(built)
    if built != stated:
      raise ValueError(
          f"{config['name']}: file states {key}={stated}, the program "
          f"built {built}")
  learner = config["learner"]
  if learner["optimizer"] != "adam":
    raise ValueError(f"{config['name']}: the check follows Adam, the "
                     f"file states {learner['optimizer']}")
  # Small weights: the expected move is a difference of two of them.
  grad = {"w": jnp.asarray([0.5, -2.0, 0.05], jnp.float32)}
  weight = {"w": jnp.asarray([1e-3, -1e-3, 2.5e-4], jnp.float32)}
  count, nu0 = 7, 1e-4
  updates, _ = model.tx.update(
      grad, seeded_adam(model.tx, weight, count, nu0), weight)
  want, _, _ = follow.adam(
      learner, weight, {"w": jnp.zeros(3, jnp.float32)},
      {"w": jnp.full(3, nu0, jnp.float32)}, count + 1, grad)
  moved = np.asarray(updates["w"])
  if not np.allclose(moved, np.asarray(want["w"] - weight["w"]),
                     rtol=1e-3, atol=0.0):
    raise ValueError(
        f"{config['name']}: the model's optimizer moves the probe by "
        f"{moved}, the file's learner block by "
        f"{np.asarray(want['w'] - weight['w'])}")


def seeded_adam(tx, placed, step: int, nu0: float):
  """`tx`'s state as a run `step` steps old holds it: Adam's count at
  `step` and a second moment that has long warmed up. From all-zero
  moments Adam's first updates are the gradients' signs, and the
  rounding of a near-zero gradient then moves a weight by a whole
  learning rate: the K steps of a dispatch would amplify what the
  check measures."""
  import jax
  import jax.numpy as jnp

  return tuple(
      part._replace(
          count=jnp.asarray(step, part.count.dtype),
          nu=jax.tree_util.tree_map(
              lambda x: jnp.full_like(x, nu0), part.nu))
      if hasattr(part, "nu") else part
      for part in tx.init(placed))


def _seeded(ts, tx, params: Dict, stats: Dict, step: int, nu0: float):
  """`ts` (a `TrainState` of shapes only: nothing of the program's is
  initialised) holding the benchmark's weights at `step`."""
  import jax.numpy as jnp

  from benchmark.harness import weights

  placed = weights.place(ts.params, params)
  return ts.replace(
      step=jnp.asarray(step, ts.step.dtype),
      params=placed,
      batch_stats=weights.place(ts.batch_stats, stats),
      opt_state=seeded_adam(tx, placed, step, nu0))


def seeded_train_state(model, params: Dict, stats: Dict, step: int,
                       nu0: float):
  """A T2R model's `TrainState` holding the benchmark's weights: the
  tree comes from the program, every value from the benchmark."""
  import jax

  shapes = jax.eval_shape(
      lambda: model.create_train_state(jax.random.PRNGKey(0),
                                       batch_size=2))
  return _seeded(shapes, model.tx, params, stats, step, nu0)


def seeded_state(learner, params: Dict, stats: Dict, step: int):
  """The program's learner state holding the benchmark's weights: the
  tree comes from the program, every value from the benchmark."""
  import jax
  import jax.numpy as jnp

  from benchmark.harness import weights

  state = jax.eval_shape(
      lambda: learner.create_state(jax.random.PRNGKey(0), batch_size=2))
  ts = _seeded(state.train_state, learner.model.tx, params, stats, step,
               weights.ADAM_NU0)
  return state.replace(
      train_state=ts,
      target_params=jax.tree_util.tree_map(jnp.copy, ts.params))
