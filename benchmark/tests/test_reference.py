"""The plain reference against the program at a tiny size on the CPU:
it agrees when the program computes in float32, and the comparison
notices when the program's dtype is lowered."""

import json

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


@pytest.fixture(scope="module")
def float32_run(seeded):
  """The float32 program's first dispatch, as `check.decide` takes it."""
  params, stats, batches = seeded
  inputs = _program_numbers(_learner(jnp.float32), params, stats,
                            batches)
  return {"check_inputs": inputs, "seed32": SEED, "k": STEPS}


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


def _tree(seed=0):
  rng = np.random.default_rng(seed)
  return {"a/kernel": rng.normal(size=(3, 3, 8, 16)).astype(np.float32),
          "a/bias": rng.normal(size=(16,)).astype(np.float32),
          "b/kernel": 1e-3 * rng.normal(size=(16, 4)).astype(np.float32)}


def _shuffled(tree):
  """Every leaf's elements in another order: the same norm and the same
  mean leaf by leaf, other elements."""
  rng = np.random.default_rng(1)
  return {k: rng.permutation(v.reshape(-1)).reshape(v.shape)
          for k, v in tree.items()}


@pytest.mark.parametrize("change,expected,gap_of_norms", [
    (lambda tree: tree, 0.0, 0.0),
    (lambda tree: {k: v * (1 + 1e-3) for k, v in tree.items()},
     1e-3, 1e-3),
    # Independent elements of equal norm stand sqrt(2) apart.
    (_shuffled, 2 ** 0.5, 0.0),
], ids=["equal", "scaled", "shuffled"])
def test_rel_err_is_the_norm_of_the_difference(change, expected,
                                               gap_of_norms):
  """`rel_err` reads the distance between two trees. A tree whose every
  leaf has the reference's norm and mean and other elements reads 0 on
  the gap between norms (`worst_leaf_gap`) and on a gap between means:
  the case the older numbers cannot see."""
  reference = _tree()
  got = change(reference)
  assert check.rel_err(got, reference) == pytest.approx(
      expected, rel=0.05, abs=1e-9)
  assert check.worst_leaf_gap(got, reference) == pytest.approx(
      gap_of_norms, rel=0.01, abs=1e-6)
  if change is _shuffled:
    for k, v in reference.items():
      assert np.mean(got[k]) == pytest.approx(np.mean(v), abs=1e-6)


def test_rel_err_weighs_leaves_by_their_size():
  """One tree-wide quotient, not a worst leaf: a small leaf that is all
  noise moves it by the leaf's share of the tree's norm."""
  reference = _tree()
  got = dict(reference, **{"b/kernel": -reference["b/kernel"]})
  small = np.linalg.norm(reference["b/kernel"])
  whole = np.sqrt(sum(np.linalg.norm(v) ** 2 for v in reference.values()))
  assert check.rel_err(got, reference) == pytest.approx(
      2 * small / whole, rel=1e-4)
  assert check.numbers_between(
      {"params": got, "mu": got, "stats": {}}, {"loss": 1.0},
      {k: np.zeros_like(v, np.float64) for k, v in reference.items()},
      {"params": reference, "mu": reference, "stats": {}},
      {"loss": 1.0})["adam_mu_rel_err"] < 1e-3


@pytest.mark.parametrize("cell", ["qtopt_64.train", "qtopt_472.train"])
def test_cell_limits_pass_the_program_and_refuse_the_control(
    float32_run, cell):
  """Under a cell's own limits file the float32 program is `correct`
  and the control in its place is not, at the tiny size; no limit is
  held against the control, so `decide` follows the reference alone.
  The norms of differences stand far apart for the two even where a
  batch of 8 rows swings the plain numbers."""
  from benchmark import run as run_lib
  with open(run_lib.BENCH_FILE) as f:
    limits = check.load_limits(run_lib.data_dir(json.load(f)), cell)
  assert "bn_stats_rel_err" in limits
  assert not any(name.endswith("_vs_control") for name in limits)
  run = float32_run  # shares the reference it followed
  lines, followed = [], []

  def numbers(config, run, control=False):
    followed.append(control)
    return train_driver.numbers(config, run, control)

  assert check.decide(numbers, CONFIG, run, limits, lines.append), lines
  assert followed == [False]
  assert list(run["compared"]) == list(limits)
  program = train_driver.numbers(CONFIG, run)
  # The control's own numbers where the program's stood.
  control = train_driver.numbers(CONFIG, run, control=True)
  assert not check.decide(lambda config, run: dict(control), CONFIG,
                          run, limits, lines.append)
  assert control["bn_stats_rel_err"] > 100 * program["bn_stats_rel_err"]
  assert control["adam_mu_rel_err"] > 100 * program["adam_mu_rel_err"]


@pytest.mark.parametrize("part,moves,leaves", [
    ("critic_only", "bn_stats_rel_err", "q_next_mean_gap"),
    ("tower_only", "q_next_mean_gap", "bn_stats_rel_err"),
])
def test_partial_controls_lower_one_part_alone(float32_run, part,
                                               moves, leaves):
  """The critic's forward pass shows in the running statistics and not
  in the CEM target; the tower the other way round."""
  run = float32_run  # shares the reference it followed
  assert part in train_driver.PARTIAL_CONTROLS
  full = train_driver.numbers(CONFIG, run, control=True)
  alone = train_driver.numbers(CONFIG, run, control=part)
  assert alone[moves] > 0.3 * full[moves], (alone, full)
  assert alone[leaves] < 0.03 * full[leaves], (alone, full)
