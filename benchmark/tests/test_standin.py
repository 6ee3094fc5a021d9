"""The `train_eval` kind proved on a shipped model: the stand-in (the
pose-env regression model of `train_pose_env.gin` through
`train_eval_model`, its own BENCHMARK.json under tests/data/standin)
goes through `run.main` with the harness's look for a chip skipped.
`correct` comes out true for the shipped step and false when the timed
path is broken underneath it."""

import json
import os
import sys

from benchmark import run as run_lib
from tensor2robot_tpu.research.pose_env.pose_env_models import (
    PoseEnvRegressionModel)

BENCH_FILE = os.path.join(run_lib.HERE, "tests", "data", "standin",
                          "BENCHMARK.json")


def _run(capsys, monkeypatch, trace="0"):
  monkeypatch.setattr(sys, "argv", [
      "run.py", "--bench-file", BENCH_FILE, "--workload",
      "standin.train_eval", "--seed", "2147483659", "--seconds", "1",
      "--trace", trace, "--rehearse-cpu"])
  assert run_lib.main() == 0
  captured = capsys.readouterr()
  lines = captured.out.strip().splitlines()
  return json.loads(lines[-1]), lines, captured.err


def test_sound_run_is_correct(capsys, monkeypatch):
  result, lines, err = _run(capsys, monkeypatch)
  assert result["correct"] is True, lines
  assert result["failed"] == 0 and result["attempted"] > 0
  assert result["metric_names"] == ["setup_s", "train_steps_per_s"]
  # Each number beside its limit: the result's last key, and the last
  # lines of standard error.
  assert list(result)[-1] == "check" and len(result["check"]) >= 5
  assert all(pair["value"] <= pair["limit"]
             for pair in result["check"].values())
  last = err.strip().splitlines()[-len(result["check"]):]
  assert [line.split()[1].rstrip(":") for line in last] \
      == list(result["check"])


def test_span_readers_find_train_eval_models_dispatches(capsys,
                                                        monkeypatch):
  """`train_eval_model` names its dispatch span `train.dispatch`; the
  driver states that on the run's record, and the readers of the
  program's spans cut their window by it (they looked for
  `qtopt.dispatch` and found nothing; ISSUE 33)."""
  from tensor2robot_tpu.telemetry import core, metrics
  # One run a process on the chip; here the runs of the tests before
  # left their spans, of the same steps, in the process's ring.
  core.reset_for_tests()
  metrics.reset_for_tests()
  result, lines, _ = _run(capsys, monkeypatch, trace="1")
  assert result["correct"] is True, lines
  assert {"feed_sample_ms_per_step", "feed_stack_ms_per_step",
          "feed_device_put_ms_per_step", "feed_queue_full_share",
          "loop_log_sync_ms", "loop_save_ms", "loop_run_ahead_share",
          "host_unnamed_share"} <= set(result["metric_names"])


def test_step_that_returns_its_state_unchanged(capsys, monkeypatch):
  monkeypatch.setattr(PoseEnvRegressionModel, "apply_gradients",
                      lambda self, state, grads, new_stats: state)
  result, lines, _ = _run(capsys, monkeypatch)
  assert result["correct"] is False
  assert any("param_change_worst_leaf_gap" in line and "FAILED" in line
             for line in lines)


def test_half_of_every_batch_left_out(capsys, monkeypatch):
  whole = PoseEnvRegressionModel.train_grads

  def half(self, state, features, labels, rng, axis_name=None):
    import jax
    n = jax.tree_util.tree_leaves(features)[0].shape[0] // 2
    first = lambda tree: jax.tree_util.tree_map(lambda x: x[:n], tree)
    return whole(self, state, first(features), first(labels), rng,
                 axis_name=axis_name)

  monkeypatch.setattr(PoseEnvRegressionModel, "train_grads", half)
  result, _, _ = _run(capsys, monkeypatch)
  assert result["correct"] is False


def test_a_model_the_file_does_not_state_is_refused(monkeypatch):
  import pytest
  from benchmark.harness import program

  _, _, config, _ = run_lib.load_cell("standin.train_eval", BENCH_FILE)
  config = run_lib.rehearsal_config(config)
  wrong = dict(config, model=dict(config["model"], embedding_size=32))
  with pytest.raises(ValueError, match="embedding_size"):
    program.build_model(wrong)
  wrong = dict(config, learner=dict(config["learner"],
                                    learning_rate=3e-4))
  with pytest.raises(ValueError, match="optimizer"):
    program.build_model(wrong)
