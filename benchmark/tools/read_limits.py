"""Reads, on the chip and in one process, what the limits of a cell are
set from: over an explicit list of seeds, the numbers of the outputs
check for the program (sound runs), for the control (the reference
computed one precision below the configuration's) and, on the first
seeds, for each partial control the kind has (one part lowered alone).
The cell's traffic kind gives all of them (`<kind>_driver.run`,
`.numbers`, `.PARTIAL_CONTROLS`); this tool holds no kind.

  chiprun -- python3 benchmark/tools/read_limits.py qtopt_64.train \\
      3000:24 2147482200 1478447722 --partial-controls 12 --tag a
  python3 benchmark/tools/read_limits.py qtopt_64.train --summarize \\
      chiprun_out/limits_qtopt_64.train_a.json [more files ...]

A seed is a number or `<first>:<count>` for a block. Each seed drives
the cell's own loop through one warm dispatch at the cell's own sizes (a
window of zero seconds: training's readings need none). Writes
chiprun_out/limits_<cell>_<tag>.json after every seed, so a lost call
keeps what it read. `--summarize` needs no chip: over all the files'
seeds it prints, per number, for the sound runs and for each control,
the smallest, the largest, and the mean and standard deviation of the
logarithm, and whether the number meets the rule for a precision limit
(`precision_limit`, below); then what the cell's limits file refuses of
each: the sound runs on no seed, the full control on every one.
"""

import argparse
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

SIGMAS = 4.0  # of the logarithms, on each side of a precision limit
FACTOR = 2.0  # from the largest sound and the smallest control reading
NORM_OF_DIFFERENCES = "_rel_err"  # `check.rel_err`'s numbers end so


def parse_seeds(words) -> list:
  seeds = []
  for word in words:
    first, _, count = word.partition(":")
    seeds += range(int(first), int(first) + int(count or 1))
  return seeds


def log_stats(values) -> dict:
  """Smallest, largest, mean and standard deviation of the logarithm."""
  logs = [math.log(v) for v in values]
  return {"n": len(values), "smallest": min(values),
          "largest": max(values), "log_mean": statistics.fmean(logs),
          "log_sd": statistics.stdev(logs) if len(logs) > 1 else 0.0}


def precision_limit(sound: dict, control: dict):
  """The rule for a precision limit: a limit L with log L at least
  `SIGMAS` standard deviations above the mean of the sound runs'
  logarithms and as many below the mean of the full control's, and at
  least `FACTOR` times from the largest sound reading and from the
  smallest control reading. Returns the lowest and the highest L that
  meet it, and whether any does."""
  lowest = max(math.exp(sound["log_mean"] + SIGMAS * sound["log_sd"]),
               FACTOR * sound["largest"])
  highest = min(math.exp(control["log_mean"]
                         - SIGMAS * control["log_sd"]),
                control["smallest"] / FACTOR)
  return {"lowest": lowest, "highest": highest,
          "meets": lowest <= highest}


def sigmas_from(stats: dict, limit: float) -> float:
  """How many standard deviations of the logarithm `limit` stands from
  the mean (positive: above)."""
  return (math.log(limit) - stats["log_mean"]) / max(stats["log_sd"],
                                                     1e-12)


def margins(sound: dict, control: dict, limit: float) -> dict:
  """Where a held `limit` stands: in standard deviations of the
  logarithm above the sound runs' mean and below the full control's,
  as a factor from the largest sound and the smallest control reading,
  and the share of sound runs that a normal fit of the logarithms has
  above it."""
  above = sigmas_from(sound, limit)
  return {"limit": limit,
          "sigmas_above_sound": above,
          "sigmas_below_control": -sigmas_from(control, limit),
          "factor_above_largest_sound": limit / sound["largest"],
          "factor_below_smallest_control": control["smallest"] / limit,
          "fitted_share_of_sound_runs_refused":
              0.5 * math.erfc(above / math.sqrt(2.0))}


def refused(rows: dict, limits: dict) -> dict:
  """Per role, on how many of its seeds each limit fails, and on how
  many any does: the sound runs have to read 0 of n under `any`, the
  full control n of n."""
  from benchmark.harness import check

  def fails(read, name, limit):
    return not check.verdict(read, {name: limit}, out=lambda line: None)

  return {
      role: {"n": len(per_seed),
             "any": sum(any(fails(r, name, limit)
                            for name, limit in limits.items())
                        for r in per_seed),
             **{name: sum(fails(r, name, limit) for r in per_seed)
                for name, limit in limits.items()}}
      for role, per_seed in rows.items()}


def summarize(files, limits=None, out=print) -> dict:
  """The table over every seed of `files` (this tool's own outputs),
  and what `limits` (a cell's) refuse of each role."""
  seeds, rows = [], {}
  for path in files:
    with open(path) as f:
      read = json.load(f)
    seeds += read["seeds"]
    for role, per_seed in read["numbers"].items():
      rows.setdefault(role, []).extend(per_seed)
  if len(set(seeds)) != len(seeds):
    raise SystemExit("a seed was read twice: its numbers would count "
                     "double")
  summary = {"seeds": seeds, "numbers": {}}
  for name in rows["sound"][0]:
    stats = {role: log_stats([r[name] for r in per_seed])
             for role, per_seed in rows.items()}
    # Seed by seed, the program's number over the full control's.
    stats["sound_over_control"] = log_stats(
        [s[name] / c[name] for s, c in zip(rows["sound"],
                                           rows["control"])])
    rule = precision_limit(stats["sound"], stats["control"])
    summary["numbers"][name] = {**stats, "precision_limit": rule}
    out(f"{name}: a precision limit "
        + ("exists from {lowest:.4g} to {highest:.4g}" if rule["meets"]
           else "does not exist ({lowest:.4g} > {highest:.4g})"
           ).format(**rule))
    for role, s in stats.items():
      out(f"  {role:18s} n {s['n']:3d}  smallest {s['smallest']:.4g}  "
          f"largest {s['largest']:.4g}  log mean {s['log_mean']:.3f} "
          f"(= {math.exp(s['log_mean']):.4g})  log sd {s['log_sd']:.3f}")
  if limits:
    # Only a norm of differences has logarithms that a normal fit
    # describes: a gap between scalars or norms has density at zero,
    # which stretches the fit's spread and says nothing of its top.
    summary["held"] = {
        name: margins(read["sound"], read["control"], limits[name])
        for name, read in summary["numbers"].items()
        if name in limits and name.endswith(NORM_OF_DIFFERENCES)}
    for name, m in summary["held"].items():
      out(f"{name} held at {m['limit']}: {m['sigmas_above_sound']:.1f} "
          f"sd above the sound runs, {m['sigmas_below_control']:.1f} sd "
          f"below the control, {m['factor_above_largest_sound']:.2f}x "
          f"the largest sound reading, the smallest control reading "
          f"{m['factor_below_smallest_control']:.2f}x it; fitted share "
          f"of sound runs refused "
          f"{m['fitted_share_of_sound_runs_refused']:.1e}")
    summary["refused"] = refused(rows, limits)
    for role, counts in summary["refused"].items():
      out(f"{role}: refused on {counts['any']} of {counts['n']} seeds; "
          + ", ".join(f"{name} {count}" for name, count in counts.items()
                      if name not in ("n", "any") and count))
  return summary


def main() -> None:
  from benchmark import run as run_lib

  parser = argparse.ArgumentParser()
  parser.add_argument("cell")
  parser.add_argument("seeds", nargs="*",
                      help="numbers, or <first>:<count> for a block")
  parser.add_argument("--partial-controls", type=int, default=0,
                      help="read the kind's partial controls too, on "
                      "this many of the first seeds")
  parser.add_argument("--tag", default="all",
                      help="names the output file: one call's share")
  parser.add_argument("--summarize", nargs="+", metavar="FILE",
                      help="no chip: the table over these outputs")
  parser.add_argument("--bench-file", default=run_lib.BENCH_FILE)
  parser.add_argument("--rehearse-cpu", action="store_true",
                      help="sandbox only: the cell's tiny stand-in")
  args = parser.parse_args()
  out = os.path.join(ROOT, "chiprun_out")
  os.makedirs(out, exist_ok=True)
  if args.summarize:
    from benchmark.harness import check
    with open(args.bench_file) as f:
      data_dir = run_lib.data_dir(json.load(f))
    summary = summarize(args.summarize,
                        check.load_limits(data_dir, args.cell))
    with open(os.path.join(out, f"limits_{args.cell}_summary.json"),
              "w") as f:
      json.dump(summary, f, indent=1)
    return

  import jax

  bench, cell, config, traffic = run_lib.load_cell(args.cell,
                                                   args.bench_file)
  if args.rehearse_cpu:
    config = run_lib.rehearsal_config(config)
  driver = run_lib.driver_of(traffic["kind"])
  devices = jax.devices()[:cell["chips"]]
  seeds = parse_seeds(args.seeds)
  partial = tuple(getattr(driver, "PARTIAL_CONTROLS", ()))
  numbers = {role: [] for role in ("sound", "control") + partial}
  reference_metrics = []  # the reference's own loss, gradient norm, ...

  for i, seed in enumerate(seeds):
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
    numbers["sound"].append(driver.numbers(config, run))
    reference_metrics.append(run["reference"][1] if "reference" in run
                             else None)  # `check.numbers_of` leaves it
    # A control in the program's place: its state and metrics after
    # the same K steps, held against the same reference.
    numbers["control"].append(driver.numbers(config, run, control=True))
    if i < args.partial_controls:
      for part in partial:
        numbers[part].append(driver.numbers(config, run, control=part))
    print(f"seed {seed}: reference, controls and comparison "
          f"{time.perf_counter() - t:.1f} s", flush=True)
    for role, per_seed in numbers.items():
      if len(per_seed) == i + 1:
        print(f"  {role} {json.dumps(per_seed[-1])}", flush=True)
    with open(os.path.join(out, f"limits_{args.cell}_{args.tag}.json"),
              "w") as f:
      json.dump({"seeds": seeds[:i + 1], "numbers": numbers,
                 "reference_metrics": reference_metrics}, f, indent=1)


if __name__ == "__main__":
  main()
