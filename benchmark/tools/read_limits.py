"""Reads, on the chip and in one process, what the limits of a train
cell are set from: over a dozen seeds, the numbers of the outputs check
for the program (sound runs) and for the control, which is the
reference computed one precision below the configuration's (int8 for
the bf16 critic update, int4 for the int8 CEM tower).

  chiprun -- python3 benchmark/tools/read_limits.py qtopt_64.train 12

Each seed drives the cell's own loop through its warm dispatches at the
cell's own sizes (a window of zero seconds: training's readings need
none). Writes chiprun_out/limits_<cell>.json and prints, per number,
the sound runs' largest and the control's smallest.
"""

import json
import os
import sys
import tempfile
import time
import shutil

CLOCK = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(cell_name: str, n_seeds: int, first_seed: int) -> None:
  import jax

  from benchmark import run as run_lib
  from benchmark.harness import check, train_driver
  from benchmark.reference import qnet

  bench, cell, config, traffic = run_lib.load_cell(cell_name)
  devices = jax.devices()[:cell["chips"]]
  control = qnet.Quant(critic_bits=8, tower_bits=4)
  sound, controls = [], []
  for seed in range(first_seed, first_seed + n_seeds):
    work_dir = tempfile.mkdtemp(prefix="t2r_limits_")
    try:
      run = train_driver.run(
          # One dispatch is all the check reads.
          config, dict(traffic, warm_dispatches=1), seed=seed,
          seconds=0.0, trace=False,
          devices=devices, clock_start=time.perf_counter(),
          work_dir=work_dir)
    finally:
      shutil.rmtree(work_dir, ignore_errors=True)
    inputs = run["check_inputs"]
    t = time.perf_counter()
    ref_state, ref_metrics = check.follow_reference(
        config, inputs, run["seed32"])
    t_ref = time.perf_counter() - t
    sound.append(check.train_numbers(inputs, ref_state, ref_metrics))
    # The control in the program's place: its state and metrics after
    # the same K steps, held against the same reference.
    ctl_state, ctl_metrics = check.follow_reference(
        config, inputs, run["seed32"], control)
    controls.append(check.numbers_between(
        ctl_state, ctl_metrics, inputs["params"], ref_state,
        ref_metrics))
    print(f"seed {seed}: reference {t_ref:.1f} s\n  sound   "
          f"{json.dumps(sound[-1])}\n  control {json.dumps(controls[-1])}",
          flush=True)
  summary = {}
  for name in sound[0]:
    hi = max(s[name] for s in sound)
    lo = min(c[name] for c in controls)
    summary[name] = {"sound_largest": hi, "control_smallest": lo,
                     "ratio": lo / hi if hi else None}
    print(f"{name}: sound largest {hi:.4g}, control smallest "
          f"{lo:.4g}, ratio {lo / hi if hi else float('inf'):.2f}")
  out = os.path.join(ROOT, "chiprun_out")
  os.makedirs(out, exist_ok=True)
  with open(os.path.join(out, f"limits_{cell_name}.json"), "w") as f:
    json.dump({"sound": sound, "control": controls,
               "summary": summary}, f, indent=1)


if __name__ == "__main__":
  main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 12,
       int(sys.argv[3]) if len(sys.argv) > 3 else 3000)
