"""A whole run with the harness's look for a chip skipped
(`--rehearse-cpu`): `correct` comes out true for the shipped loop and
false when the timed path is broken underneath it."""

import json
import sys


from benchmark import run as run_lib
from tensor2robot_tpu.research.qtopt.qtopt_learner import QTOptLearner


def _run(capsys, monkeypatch, cell="qtopt_64.train"):
  monkeypatch.setattr(sys, "argv", [
      "run.py", "--workload", cell, "--seed", "2147483659",
      "--seconds", "1", "--trace", "0", "--rehearse-cpu"])
  assert run_lib.main() == 0
  lines = capsys.readouterr().out.strip().splitlines()
  return json.loads(lines[-1]), lines


def test_sound_run_is_correct(capsys, monkeypatch):
  result, lines = _run(capsys, monkeypatch)
  assert result["correct"] is True, lines
  assert result["failed"] == 0 and result["attempted"] > 0
  assert sum(line.startswith("check ") for line in lines) >= 5


def test_step_that_returns_its_state_unchanged(capsys, monkeypatch):
  monkeypatch.setattr(QTOptLearner, "apply_gradients",
                      lambda self, state, grads, new_stats: state)
  result, lines = _run(capsys, monkeypatch)
  assert result["correct"] is False
  assert any("param_change_worst_leaf_gap" in line and "FAILED" in line
             for line in lines)


def test_part_of_the_batch_left_out(capsys, monkeypatch):
  """The critic trained on the first half of every batch only."""
  whole = QTOptLearner.train_grads

  def half(self, state, transitions, rng, axis_name=None):
    import jax
    n = jax.tree_util.tree_leaves(transitions)[0].shape[0] // 2
    return whole(self, state,
                 jax.tree_util.tree_map(lambda x: x[:n], transitions),
                 rng, axis_name=axis_name)

  monkeypatch.setattr(QTOptLearner, "train_grads", half)
  result, _ = _run(capsys, monkeypatch)
  assert result["correct"] is False


def test_no_accelerator_means_no_result(capsys, monkeypatch):
  monkeypatch.setattr(sys, "argv", [
      "run.py", "--workload", "qtopt_64.train", "--seed", "1",
      "--seconds", "1", "--trace", "0"])
  assert run_lib.main() != 0
  assert capsys.readouterr().out.strip() == ""
