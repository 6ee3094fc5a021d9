"""The plain reference against the program at a tiny size on the CPU:
it agrees when the program computes in float32, and the comparison
notices when the program's dtype is lowered."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check, program, train_driver, weights
from benchmark.reference import qnet
from tensor2robot_tpu.research.qtopt import GraspingQModel, QTOptLearner
from tensor2robot_tpu.specs import TensorSpecStruct

MODEL = {"image_size": 16, "space_to_depth": 1,
         "torso_filters": [8, 16], "head_filters": [16, 16],
         "dense_sizes": [16, 16], "action_dim": 4,
         "action_embedding_size": 64}
CONFIG = {"model": MODEL,
          "cem": {"iterations": 2, "population": 16, "elites": 3},
          "learner": {"gamma": 0.9, "target_update_tau": 0.05,
                      "learning_rate": 1e-4},
          "reference": {"cem_rows_per_block": 4}}
BATCH, STEPS, SEED, START = 8, 3, 5, 9996


def _learner(dtype, cem_inference="bf16"):
  model = GraspingQModel(
      image_size=16, torso_filters=(8, 16), head_filters=(16, 16),
      dense_sizes=(16, 16), action_dim=4, device_dtype=dtype)
  return QTOptLearner(model, cem_iterations=2, cem_population=16,
                      cem_elites=3, cem_inference=cem_inference)


def _batches():
  rng = np.random.default_rng(0)
  out = []
  for _ in range(STEPS):
    out.append({
        "image": rng.integers(0, 256, (BATCH, 16, 16, 3), dtype=np.uint8),
        "next_image": rng.integers(0, 256, (BATCH, 16, 16, 3),
                                   dtype=np.uint8),
        "action": rng.uniform(-1, 1, (BATCH, 4)).astype(np.float32),
        "reward": (rng.random((BATCH, 1)) < 0.3).astype(np.float32),
        "done": (rng.random((BATCH, 1)) < 0.2).astype(np.float32)})
  return out


def _program_numbers(learner, params, stats, batches):
  """Drives the program's own train_step as the loop does: step s is
  keyed fold_in(PRNGKey(seed + 1), s)."""
  state = program.seeded_state(learner, params, stats, START)
  step_rng = jax.random.PRNGKey(SEED + 1)
  step = jax.jit(learner.train_step)
  for i, batch in enumerate(batches):
    state, metrics = step(
        state, TensorSpecStruct.from_flat_dict(
            {k: jnp.asarray(v) for k, v in batch.items()}),
        jax.random.fold_in(step_rng, START + i))
  return {"params": jax.device_get(params),
          "stats": jax.device_get(stats), "batches": batches,
          "first_metrics": {k: float(v) for k, v in metrics.items()},
          "first_state": jax.device_get(state.train_state),
          "first_step": START + STEPS}


@pytest.fixture(scope="module")
def seeded():
  params, stats = weights.make_weights(SEED, MODEL)
  return params, stats, _batches()


def _numbers(learner, seeded, quant=qnet.REFERENCE):
  params, stats, batches = seeded
  inputs = _program_numbers(learner, params, stats, batches)
  ref_state, ref_metrics = train_driver.follow_reference(
      CONFIG, inputs, SEED, quant)
  return check.numbers_between(
      check.program_state(inputs["first_state"]),
      inputs["first_metrics"], inputs["params"], ref_state, ref_metrics)


def test_weights_cover_the_program_tree(seeded):
  params, stats, _ = seeded
  learner = _learner(jnp.float32)
  state = learner.create_state(jax.random.PRNGKey(0))
  assert set(weights.flatten(state.train_state.params)) == set(params)
  assert set(weights.flatten(state.train_state.batch_stats)) == set(stats)


def test_reference_agrees_with_float32_program(seeded):
  numbers = _numbers(_learner(jnp.float32), seeded)
  assert numbers["loss_rel_gap"] < 1e-5, numbers
  assert numbers["q_next_mean_gap"] < 1e-5, numbers
  assert numbers["grad_norm_rel_gap"] < 1e-3, numbers
  assert numbers["adam_mu_worst_leaf_gap"] < 1e-3, numbers
  assert numbers["param_change_worst_leaf_gap"] < 1e-2, numbers
  assert numbers["bn_stats_worst_leaf_gap"] < 1e-5, numbers


def test_lowered_dtype_is_noticed(seeded):
  exact = _numbers(_learner(jnp.float32), seeded)
  lowered = _numbers(_learner(jnp.bfloat16), seeded)
  assert lowered["grad_norm_rel_gap"] > 10 * exact["grad_norm_rel_gap"]
  assert lowered["adam_mu_worst_leaf_gap"] > 1e-3, lowered


def test_control_precision_moves_the_numbers(seeded):
  """The reference computed at int8/int4 (the control of the chip
  readings) stands further from the float32 program than the
  reference does."""
  learner = _learner(jnp.float32)
  exact = _numbers(learner, seeded)
  control = _numbers(learner, seeded,
                     qnet.Quant(critic_bits=8, tower_bits=4))
  assert control["adam_mu_worst_leaf_gap"] > \
      10 * exact["adam_mu_worst_leaf_gap"], (exact, control)
  assert control["q_next_mean_gap"] > 10 * exact["q_next_mean_gap"]


def test_a_number_held_against_the_control(seeded):
  """`<number>_vs_control`: the program's number over the control's on
  the same rows. The float32 program stands far closer to the reference
  than the control does; the control in the program's place reads 1."""
  params, stats, batches = seeded
  inputs = _program_numbers(_learner(jnp.float32), params, stats,
                            batches)
  run = {"check_inputs": inputs, "seed32": SEED, "k": STEPS}
  limits = {"q_next_mean_gap_vs_control": 0.3,
            "adam_mu_worst_leaf_gap_vs_control": 0.35}
  lines = []
  assert train_driver.check("tiny", CONFIG, run, limits, lines.append)
  assert all(pair["value"] < 0.1 for pair in run["compared"].values())
  # The control's own numbers where the program's stood.
  numbers = train_driver.numbers(CONFIG, run, control=True)

  def control_in_place(config, run, control=False):
    return dict(numbers)

  assert not check.decide(control_in_place, CONFIG, run, limits,
                          lines.append)
  assert all(pair["value"] == 1.0 for pair in run["compared"].values())
