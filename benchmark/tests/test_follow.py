"""The reference side of a `train_eval` cell (`harness/follow.py`) and
the leaf-by-leaf comparison (`harness/check.py`): the step donates its
state and computes what it computed undonated; the numbers compared
equal those of the whole-tree arithmetic they replaced to the last
digit; `tools/follow_memory.py` runs; the check starts with the loop's
state freed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as run_lib
from benchmark.harness import check, follow
from benchmark.tests.data import square_stack
from benchmark.tests.data.standin import pose_reference
from benchmark.tools import follow_memory

K = 3


def _standin():
  """The stand-in at its `rehearse_cpu` sizes: configuration and what
  a run's record gives `follow` (weights, K batches that all differ)."""
  import os
  _, _, config, _ = run_lib.load_cell(
      "standin.train_eval",
      os.path.join(run_lib.HERE, "tests", "data", "standin",
                   "BENCHMARK.json"))
  config = run_lib.rehearsal_config(config)
  params, stats = pose_reference.make_weights(11, config)
  rng = np.random.default_rng(11)
  size, rows = config["model"]["image_size"], 16
  batches = [
      {"features": {"image": rng.integers(
          0, 256, (rows, size, size, 3), dtype=np.uint8)},
       "labels": {"target_pose": rng.uniform(
           -1, 1, (rows, config["model"]["pose_dim"])).astype(
               np.float32)}}
      for _ in range(K)]
  return config, {"params": jax.device_get(params),
                  "stats": jax.device_get(stats),
                  "batches": batches, "first_step": 1000 + K}


def _square_stack():
  config = square_stack.config_of(layers=3, width=32, rows=8)
  params, stats = square_stack.make_weights(5, config)
  return config, {"params": jax.device_get(params),
                  "stats": jax.device_get(stats),
                  "batches": square_stack.make_batches(5, config, K),
                  "first_step": K}


FAMILIES = {"standin": _standin, "square_stack": _square_stack}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_compiled_step_aliases_its_state(family):
  """Parameters and both moments go out in the buffers they came in:
  all of the state but a leaf's worth (the step's counter, padding)."""
  config, inputs = FAMILIES[family]()
  with jax.default_matmul_precision("highest"):
    state = follow.start_state(config, inputs, 0)
    memory = follow.donated_step(config).lower(
        state, jax.tree_util.tree_map(jnp.asarray, inputs["batches"][0]),
        jax.random.PRNGKey(0)).compile().memory_analysis()
  leaves = [leaf.nbytes for leaf in jax.tree_util.tree_leaves(state)]
  assert memory.alias_size_in_bytes >= sum(leaves) - max(leaves)


@pytest.mark.parametrize("control", [False, True])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_k_donated_steps_equal_the_undonated_arithmetic(family, control):
  config, inputs = FAMILIES[family]()
  seed32 = 2147483000
  got_state, got_metrics = follow.follow(config, inputs, seed32, control)
  assert sorted(got_state) == ["mu", "params", "stats"]

  with jax.default_matmul_precision("highest"):
    step = jax.jit(follow.step_of(config, control))  # nothing donated
    step0 = inputs["first_step"] - K
    state = follow.start_state(config, inputs, step0)
    rng = jax.random.PRNGKey(seed32 + 1)
    for i, batch in enumerate(inputs["batches"]):
      state, metrics = step(
          state, jax.tree_util.tree_map(jnp.asarray, batch),
          jax.random.fold_in(rng, step0 + i))
  assert int(state["count"]) == inputs["first_step"]
  for part in got_state:
    assert sorted(got_state[part]) == sorted(state[part])
    for name, leaf in got_state[part].items():
      np.testing.assert_array_equal(leaf, np.asarray(state[part][name]),
                                    err_msg=f"{part}/{name}")
  assert got_metrics == {name: float(v) for name, v in metrics.items()}
  # The steps moved every leaf.
  assert all(np.any(got_state["params"][name] != start)
             for name, start in inputs["params"].items())


def _whole_tree_numbers(got_state, got_metrics, start, ref_state,
                        ref_metrics):
  """`check.numbers_between` as it stood before it went leaf by leaf:
  the change of every parameter, program's and reference's, as two
  whole trees in float64."""
  def norms(tree):
    return {k: float(np.linalg.norm(np.asarray(v, np.float64)))
            for k, v in tree.items()}

  def worst_leaf_gap(program, reference):
    p, r = norms(program), norms(reference)
    floor = float(np.median(list(r.values())))
    return max(abs(p[k] - r[k]) / max(r[k], floor, 1e-30) for k in r)

  def rel_err(program, reference):
    diff = size = 0.0
    for k, r in reference.items():
      r = np.asarray(r, np.float64)
      diff += float(np.sum(np.square(
          np.asarray(program[k], np.float64) - r)))
      size += float(np.sum(np.square(r)))
    return float(np.sqrt(diff / max(size, 1e-300)))

  delta = {k: np.asarray(got_state["params"][k], np.float64) - start[k]
           for k in start}
  ref_delta = {k: np.asarray(ref_state["params"][k], np.float64)
               - start[k] for k in start}
  numbers = {
      f"{name}_rel_gap":
          abs(got_metrics[name] - ref) / max(abs(ref), 1e-30)
      for name, ref in ref_metrics.items() if name in got_metrics}
  numbers["adam_mu_worst_leaf_gap"] = worst_leaf_gap(got_state["mu"],
                                                     ref_state["mu"])
  numbers["param_change_worst_leaf_gap"] = worst_leaf_gap(delta,
                                                          ref_delta)
  numbers["adam_mu_rel_err"] = rel_err(got_state["mu"], ref_state["mu"])
  numbers["param_change_rel_err"] = rel_err(delta, ref_delta)
  if ref_state["stats"]:
    numbers["bn_stats_worst_leaf_gap"] = worst_leaf_gap(
        got_state["stats"], ref_state["stats"])
    numbers["bn_stats_rel_err"] = rel_err(got_state["stats"],
                                          ref_state["stats"])
  return numbers


def _made_up_trees(seed, stats=True, zero_leaf=False):
  """Start weights, a reference's state and a program's a rounding
  away from it, as float32 leaves of shapes that do not divide
  evenly."""
  rng = np.random.default_rng(seed)
  shapes = {"torso/conv/kernel": (3, 3, 5, 17), "torso/bn/scale": (17,),
            "head/dense/kernel": (129, 33), "head/dense/bias": (33,),
            "head/log_temperature": ()}
  f32 = lambda x: np.asarray(x, np.float32)
  start = {k: f32(rng.standard_normal(s)) for k, s in shapes.items()}
  ref = {"params": {k: f32(v + 1e-3 * rng.standard_normal(v.shape))
                    for k, v in start.items()},
         "mu": {k: f32(1e-2 * rng.standard_normal(s))
                for k, s in shapes.items()},
         "stats": {"torso/bn/mean": f32(rng.standard_normal(17)),
                   "torso/bn/var": f32(rng.uniform(0.5, 2, 17))}
                  if stats else {}}
  if zero_leaf:  # a leaf that neither side moves, and whose moment is 0
    ref["params"]["head/dense/bias"] = start["head/dense/bias"].copy()
    ref["mu"]["head/dense/bias"] = np.zeros((33,), np.float32)
  got = {part: {k: f32(v * (1 + 3e-3 * rng.standard_normal(v.shape)))
                for k, v in tree.items()}
         for part, tree in ref.items()}
  if zero_leaf:
    got["params"]["head/dense/bias"] = start["head/dense/bias"].copy()
  metrics = {"loss": 0.731, "grad_norm": 4.2, "pose_error": 0.11}
  got_metrics = {"loss": 0.7312, "grad_norm": 4.23, "other": 1.0}
  return got, got_metrics, start, ref, metrics


@pytest.mark.parametrize("seed", [0, 1, 2147483659])
@pytest.mark.parametrize("shape", ["with_stats", "empty_stats",
                                   "one_all_zero_leaf"])
def test_leaf_by_leaf_equals_the_whole_tree_to_the_last_digit(shape,
                                                             seed):
  trees = _made_up_trees(seed, stats=shape != "empty_stats",
                         zero_leaf=shape == "one_all_zero_leaf")
  got, want = check.numbers_between(*trees), _whole_tree_numbers(*trees)
  assert list(got) == list(want)
  assert got == want  # floats compared exactly
  assert ("bn_stats_rel_err" in got) == (shape != "empty_stats")
  assert all(np.isfinite(v) and v > 0 for v in got.values())


def test_the_walk_leaves_its_trees_as_they_were():
  """The leaves are worked on in place in the walk's own two buffers,
  never in the caller's arrays, float64 ones among them."""
  got, got_metrics, start, ref, metrics = _made_up_trees(3)
  f64 = lambda tree: {k: v.astype(np.float64) for k, v in tree.items()}
  got["mu"], ref["mu"], start = f64(got["mu"]), f64(ref["mu"]), f64(start)
  trees = (got, got_metrics, start, ref, metrics)
  before = [{k: v.copy() for k, v in tree.items()}
            for tree in (got["params"], got["mu"], ref["params"],
                         ref["mu"], ref["stats"], start)]
  assert check.numbers_between(*trees) == _whole_tree_numbers(*trees)
  for tree, kept in zip((got["params"], got["mu"], ref["params"],
                         ref["mu"], ref["stats"], start), before):
    for k in kept:
      np.testing.assert_array_equal(tree[k], kept[k])
  assert check.rel_err(got["mu"], ref["mu"]) == check.numbers_between(
      *trees)["adam_mu_rel_err"]


def test_follow_memory_runs_a_tiny_size():
  out = follow_memory.measure(params=3000, width=32, rows=8, steps=2,
                              seed=2147483659)
  assert out["parameters"] == 3 * 32 * 32 and "follow_failed" not in out
  assert out["numbers"]["param_change_rel_err"] > 0
  assert out["host_peak_rss_bytes"] > 0
  assert all(stage["now"] > 0 and stage["peak"] > 0
             for stage in out["host_rss_bytes"].values())
  assert list(out["host_rss_bytes"]) == [
      "reached_the_device", "weights_on_the_host",
      "followed_the_reference", "followed_the_control", "compared"]
  if out["platform"] == "cpu":  # no device peak under a CPU's name
    assert out["peak_bytes_in_use"] is None
    assert "device_bytes_per_parameter" not in out


def test_the_check_starts_with_the_loops_state_freed(capsys,
                                                     monkeypatch):
  """A whole run of the stand-in (`run.main`, rehearsed on the CPU):
  when `driver.check` starts, no array that the run made is alive. The
  exception that closes the window, its traceback and the trainer's
  frame stand in a reference cycle with the state (57 arrays here, 7.6
  to 8.2 GB in the language-model cells, where the reference then met
  them on the device; PERF.md §6, PR 35 and 40): `run.py` collects
  before the check."""
  import gc
  import os
  import sys

  from benchmark.harness import train_eval_driver

  bench_file = os.path.join(run_lib.HERE, "tests", "data", "standin",
                            "BENCHMARK.json")
  monkeypatch.setattr(sys, "argv", [
      "run.py", "--bench-file", bench_file, "--workload",
      "standin.train_eval", "--seed", "2147483659", "--seconds", "1",
      "--trace", "0", "--rehearse-cpu"])
  gc.collect()
  before = jax.live_arrays()  # other tests': held, so no id comes again
  known = {id(x) for x in before}
  alive = []
  check_of = train_eval_driver.check

  def check_and_count(cell_name, config, run, limits):
    alive.append([x.shape for x in jax.live_arrays()
                  if id(x) not in known])
    return check_of(cell_name, config, run, limits)

  monkeypatch.setattr(train_eval_driver, "check", check_and_count)
  gc.disable()  # no collection by chance between the loop and the check
  try:
    assert run_lib.main() == 0
  finally:
    gc.enable()
  capsys.readouterr()
  assert alive == [[]]
