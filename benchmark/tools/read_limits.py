"""Reads, on the chip and in one process, what the limits of a cell are
set from: over a dozen seeds or more, the numbers of the outputs check
for the program (sound runs) and for the control, which is the
reference computed one precision below the configuration's. The cell's
traffic kind gives both (`<kind>_driver.run` and `.numbers`); this
tool holds no kind.

  chiprun -- python3 benchmark/tools/read_limits.py qtopt_64.train 12
  ... read_limits.py <cell> <seeds> <first seed> [further seeds ...]
      [--bench-file <a stand-in's file>]

Each seed drives the cell's own loop through one warm dispatch at the
cell's own sizes (a window of zero seconds: training's readings need
none). Writes chiprun_out/limits_<cell>.json and prints, per number,
the sound runs' largest, the control's smallest, and the largest
quotient of the two on one seed.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
  from benchmark import run as run_lib

  parser = argparse.ArgumentParser()
  parser.add_argument("cell")
  parser.add_argument("n_seeds", type=int, nargs="?", default=12)
  parser.add_argument("first_seed", type=int, nargs="?", default=3000)
  parser.add_argument("further_seeds", type=int, nargs="*")
  parser.add_argument("--bench-file", default=run_lib.BENCH_FILE)
  parser.add_argument("--rehearse-cpu", action="store_true",
                      help="sandbox only: the cell's tiny stand-in")
  args = parser.parse_args()

  import jax

  bench, cell, config, traffic = run_lib.load_cell(args.cell,
                                                   args.bench_file)
  if args.rehearse_cpu:
    config = run_lib.rehearsal_config(config)
  driver = run_lib.driver_of(traffic["kind"])
  devices = jax.devices()[:cell["chips"]]
  seeds = list(range(args.first_seed, args.first_seed + args.n_seeds))
  seeds += args.further_seeds
  sound, controls = [], []
  out = os.path.join(ROOT, "chiprun_out")
  os.makedirs(out, exist_ok=True)

  def write(summary=None):  # after every seed: a lost call keeps them
    with open(os.path.join(out, f"limits_{args.cell}.json"), "w") as f:
      json.dump({"seeds": seeds[:len(sound)], "sound": sound,
                 "control": controls, "summary": summary}, f, indent=1)

  for seed in seeds:
    work_dir = tempfile.mkdtemp(prefix="t2r_limits_")
    try:
      run = driver.run(
          # One dispatch is all the check reads.
          config, dict(traffic, warm_dispatches=1), seed=seed,
          seconds=0.0, trace=False,
          devices=devices, clock_start=time.perf_counter(),
          work_dir=work_dir)
    finally:
      shutil.rmtree(work_dir, ignore_errors=True)
    t = time.perf_counter()
    sound.append(driver.numbers(config, run))
    t_ref = time.perf_counter() - t
    # The control in the program's place: its state and metrics after
    # the same K steps, held against the same reference.
    controls.append(driver.numbers(config, run, control=True))
    print(f"seed {seed}: reference and comparison {t_ref:.1f} s\n"
          f"  sound   {json.dumps(sound[-1])}\n"
          f"  control {json.dumps(controls[-1])}", flush=True)
    write()
  summary = {}
  for name in sound[0]:
    hi = max(s[name] for s in sound)
    lo = min(c[name] for c in controls)
    # Seed by seed, the program's number over the control's: what a
    # limit named `<number>_vs_control` holds (`check.decide`).
    quotient = max(s[name] / c[name] for s, c in zip(sound, controls))
    summary[name] = {"sound_largest": hi, "control_smallest": lo,
                     "ratio": lo / hi if hi else None,
                     "vs_control_largest": quotient}
    print(f"{name}: sound largest {hi:.4g} (seed "
          f"{seeds[[s[name] for s in sound].index(hi)]}), control "
          f"smallest {lo:.4g}, ratio "
          f"{lo / hi if hi else float('inf'):.2f}; sound over control, "
          f"seed by seed, largest {quotient:.3f}")
  write(summary)


if __name__ == "__main__":
  main()
