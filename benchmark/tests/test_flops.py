"""The benchmark's FLOP count equals the program's as of PR 23, for
both configurations; from here on the program's may move, the
yardstick's may not."""

import json
import os

import jax
import pytest

from benchmark.harness import flops, peaks, program
from tensor2robot_tpu.utils import profiling

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "configs")


@pytest.mark.parametrize("name", ["qtopt_64", "qtopt_472"])
def test_flops_copy_equals_the_programs(name):
  with open(os.path.join(CONFIGS, f"{name}.json")) as f:
    config = json.load(f)
  learner = program.build_learner(config)
  state = jax.eval_shape(
      lambda: learner.create_state(jax.random.PRNGKey(0)))
  batch = config["train"]["batch_size_per_chip"]
  theirs = profiling.qtopt_step_flops(
      learner, batch, params=state.train_state.params)
  assert flops.qtopt_step_flops(config, batch) == pytest.approx(
      theirs, rel=1e-12)


def test_unknown_device_kind_is_an_error():
  assert peaks.peak("TPU v5 lite", "bf16_flops") == 197e12
  with pytest.raises(KeyError):
    peaks.peak("TPU v9", "bf16_flops")
