"""Reads, on the chip and in one process, the full control's numbers of
a `train_eval` cell over an explicit list of seeds WITHOUT the cell's
loop: the reference and the control (the reference one precision lower)
each follow the K steps from the seed's weights on the seed's first K
batches, and the control stands in the program's place in
`check.numbers_between`, exactly as `tools/read_limits.py` has it.

  chiprun -- python3 benchmark/tools/read_control.py \\
      qwen3next_80b_a3b_ep16.train_eval 3000:4 2147482200 --tag a

Why beside `read_limits.py`: that tool drives the cell's loop once a
seed: in a cell whose start costs minutes (a 7.5 GB state written,
restored and saved) a seed costs three to four minutes of chip where
the control's two follows cost 75 s. The sound runs' numbers are those that every run of the cell
prints beside its limits; the control's, which no run computes, are
this tool's. Its output has `read_limits.py`'s layout
(chiprun_out/limits_<cell>_control_<tag>.json, role `control` alone),
written after every seed.

The batches are the ones the loop's stream yields first
(`harness/seeded_rows.py`: the table, then each batch's rows, from one
generator of the seed); only integer features that the configuration
bounds (`train.int_below`) are made here, which is all a
language-model cell has.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def first_batches(config: dict, seed32: int, batch: int, k: int):
  """The K batches `seeded_rows.SeededRows` yields first for a model
  whose features are the integer leaves of `train.int_below`, each one
  row of `sequence_length + 1` ids, and that has no labels."""
  import numpy as np

  train = config["train"]
  rng = np.random.default_rng(seed32)
  length = config["model"]["sequence_length"] + 1
  table = {key: rng.integers(0, below, (train["data_rows"], length),
                             dtype=np.int32)
           for key, below in train["int_below"].items()}
  batches = []
  for _ in range(k):
    rows = rng.integers(0, train["data_rows"], batch)
    batches.append({"features": {key: leaf[rows]
                                 for key, leaf in table.items()},
                    "labels": {}})
  return batches


def main() -> None:
  from benchmark import run as run_lib
  from benchmark.tools import read_limits

  parser = argparse.ArgumentParser()
  parser.add_argument("cell")
  parser.add_argument("seeds", nargs="+",
                      help="numbers, or <first>:<count> for a block")
  parser.add_argument("--tag", default="all")
  parser.add_argument("--rehearse-cpu", action="store_true",
                      help="sandbox only: the cell's tiny stand-in")
  args = parser.parse_args()

  import jax

  from benchmark.harness import check, follow, window
  from tensor2robot_tpu.startup import compile_cache

  _, cell, config, _ = run_lib.load_cell(args.cell)
  if args.rehearse_cpu:
    config = run_lib.rehearsal_config(config)
  compile_cache.configure_compilation_cache()
  train = config["train"]
  k = train["steps_per_dispatch"]
  batch = train["batch_size_per_chip"] * cell["chips"]
  first_step = window.resume_step(train["save_checkpoints_steps"], k) + k
  out = os.path.join(ROOT, "chiprun_out")
  os.makedirs(out, exist_ok=True)
  seeds = read_limits.parse_seeds(args.seeds)
  numbers, reference_metrics = {"control": []}, []
  for i, seed in enumerate(seeds):
    t = time.perf_counter()
    seed32 = seed % (2 ** 31 - 1)
    weights, stats = follow.module_of(config, "weights").make_weights(
        seed, config)
    inputs = {"params": jax.device_get(weights),
              "stats": jax.device_get(stats),
              "batches": first_batches(config, seed32, batch, k),
              "first_step": first_step}
    del weights, stats
    reference = follow.follow(config, inputs, seed32, False)
    t_control = time.perf_counter()
    control = follow.follow(config, inputs, seed32, True)
    numbers["control"].append(check.numbers_between(
        *control, inputs["params"], *reference))
    reference_metrics.append(reference[1])
    print(f"seed {seed}: reference {t_control - t:.1f} s, control and "
          f"comparison {time.perf_counter() - t_control:.1f} s",
          flush=True)
    print(f"  reference {json.dumps(reference[1])}", flush=True)
    print(f"  control {json.dumps(numbers['control'][-1])}", flush=True)
    with open(os.path.join(
        out, f"limits_{args.cell}_control_{args.tag}.json"), "w") as f:
      json.dump({"seeds": seeds[:i + 1], "numbers": numbers,
                 "reference_metrics": reference_metrics}, f, indent=1)


if __name__ == "__main__":
  main()
