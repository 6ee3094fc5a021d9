"""Prints the int8 activation scales a configuration file fixes: the
program's own calibration (`QTOptLearner.calibrate`) on the benchmark's
seed-0 weights and a batch of the benchmark's seed-0 replay rows, times
1.25 of headroom for other seeds' weights. Run once when a
configuration is written: `python benchmark/tools/calibrate_scales.py
qtopt_64 [rows]`."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(name: str, rows: int) -> None:
  import jax.numpy as jnp

  from benchmark.harness import program, replay_fill, weights
  from tensor2robot_tpu.research.qtopt.replay_buffer import ReplayBuffer

  with open(os.path.join(ROOT, "benchmark", "configs",
                         f"{name}.json")) as f:
    config = json.load(f)
  learner = program.build_learner(config, fix_scales=False)
  params, stats = weights.make_weights(0, config["model"])
  state = program.seeded_state(learner, params, stats, 0)
  buffer = ReplayBuffer(learner.transition_specification(),
                        capacity=rows, seed=0)
  replay_fill.fill(buffer, rows, 0, rows)
  batch = {k: jnp.asarray(v)
           for k, v in buffer.sample(rows).to_flat_dict().items()}
  scales = learner.calibrate(state, batch)
  print(json.dumps({k: float(f"{1.25 * v:.3g}")
                    for k, v in sorted(scales.items())}, indent=2))


if __name__ == "__main__":
  main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 64)
