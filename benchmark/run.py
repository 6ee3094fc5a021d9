"""One run of one cell of BENCHMARK.json.

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                           --trace <0|1>

Everything that belongs to one cell is a file found by name: the cell
in BENCHMARK.json, its configuration under configs/, its traffic mix
under traffic/, the driver of the mix's `kind`
(`harness/<kind>_driver.py`), its limits under limits/, and one reader
per per-layer metric under layer_metrics/. This file holds no table of
kinds. The last line of standard output is the result as one JSON
object.
"""

import time

CLOCK_START = time.perf_counter()  # set-up counts from here

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

BENCH_FILE = os.path.join(ROOT, "BENCHMARK.json")


def data_dir(bench: dict) -> str:
  """Where a benchmark file's traffic/ and limits/ live: the first of
  its `paths` (`benchmark/` for the root's file)."""
  return os.path.join(ROOT, bench["paths"][0])


def load_cell(name: str, bench_file: str = BENCH_FILE):
  """(benchmark file, cell, configuration, traffic mix) of one cell of
  `bench_file`: the root's BENCHMARK.json, or a file of the same keys
  beside data directories of its own (a test's stand-in)."""
  with open(bench_file) as f:
    bench = json.load(f)
  cells = {w["name"]: w for w in bench["workloads"]}
  if name not in cells:
    raise SystemExit(f"no workload {name!r} in {bench_file}; it has "
                     f"{sorted(cells)}")
  cell = cells[name]
  config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
  with open(os.path.join(ROOT, config_entry["file"])) as f:
    config = json.load(f)
  with open(os.path.join(data_dir(bench), "traffic",
                         f"{cell['traffic']}.json")) as f:
    traffic = json.load(f)
  return bench, cell, config, traffic


def kinds():
  """The traffic kinds that exist: one `harness/<kind>_driver.py`
  each."""
  suffix = "_driver.py"
  return sorted(name[:-len(suffix)]
                for name in os.listdir(os.path.join(HERE, "harness"))
                if name.endswith(suffix))


def driver_of(kind: str):
  """The module `benchmark.harness.<kind>_driver`: `run`, `check` and
  `numbers` of one traffic kind (benchmark/README.md, "The driver
  contract")."""
  name = f"benchmark.harness.{kind}_driver"
  try:
    return importlib.import_module(name)
  except ModuleNotFoundError as e:
    if e.name != name:
      raise  # the driver is there; something it imports is not
    raise SystemExit(f"no traffic kind {kind!r} (no {name}); the kinds "
                     f"are {kinds()}") from None


def metrics_of(bench: dict, cell: dict, group: str):
  """The entries of `group` that this cell reports."""
  return [m for m in bench[group]
          if "workloads" not in m or cell["name"] in m["workloads"]]


def rehearsal_config(config: dict) -> dict:
  """The configuration shrunk to what a CPU can run in a minute."""
  small = dict(config)
  small.update(config["rehearse_cpu"])
  return small


def main() -> int:
  parser = argparse.ArgumentParser()
  parser.add_argument("--workload", required=True)
  parser.add_argument("--seed", type=int, required=True)
  parser.add_argument("--seconds", type=float, required=True)
  parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
  parser.add_argument(
      "--rehearse-cpu", action="store_true",
      help="sandbox only: tiny sizes on whatever devices JAX has; "
      "prints no device metric")
  parser.add_argument(
      "--bench-file", default=BENCH_FILE,
      help="sandbox and builder's chip runs only: another file of "
      "BENCHMARK.json's keys (a test's stand-in)")
  args = parser.parse_args()
  bench, cell, config, traffic = load_cell(args.workload,
                                           args.bench_file)
  if args.rehearse_cpu:
    config = rehearsal_config(config)

  marks = {"python_and_args_s": time.perf_counter() - CLOCK_START}
  import jax
  marks["import_jax_s"] = time.perf_counter() - CLOCK_START
  devices = jax.devices()
  marks["reach_chip_s"] = time.perf_counter() - CLOCK_START
  if not args.rehearse_cpu and devices[0].platform != "tpu":
    print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
    return 2
  if len(devices) < cell["chips"]:
    print(f"cell needs {cell['chips']} chips, JAX found "
          f"{len(devices)}", file=sys.stderr)
    return 2
  devices = devices[:cell["chips"]]

  driver = driver_of(traffic["kind"])
  marks["import_program_s"] = time.perf_counter() - CLOCK_START
  work_dir = tempfile.mkdtemp(prefix="t2r_bench_")
  try:
    run = driver.run(config, traffic, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     devices=devices, clock_start=CLOCK_START,
                     work_dir=work_dir)
    run["device_kind"] = devices[0].device_kind
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    breakdown = None
    if args.trace and devices[0].platform == "tpu":
      from benchmark.harness import trace_reduce
      t_trace = run["trace_span"][0]  # the recording's time zero
      trace = trace_reduce.reduce_trace(
          trace_reduce.find_xplane(run["trace_dir"]), len(devices),
          program=run["trace_program"],
          host_events=[(name, (start - t_trace) * 1e9,
                        (end - start) * 1e9)
                       for name, start, end in run["host_spans"]])
      run["trace"] = trace
      device["busy_s"] = trace["busy_s"]
      device["window_s"] = trace["window_s"]
      breakdown = {"device_ops": trace["device_ops"],
                   "idle_gaps": trace["idle_gaps"]}
    print("setup split:", json.dumps({**marks, **run["setup_split"]}))
    print("window:", json.dumps({
        key: run.get(key) for key in ("steps", "window_s",
                                      "checkpoint_stalls_ms")}))
    # The reference runs now, after the program's state is freed, so
    # that memory_peak_bytes above is the program's alone. Freed: the
    # exception that closed the window, its traceback and the loop's
    # frame stand in a reference cycle that holds the state's arrays
    # until a collection (PERF.md §6, PR 35 and 40).
    gc.collect()
    from benchmark.harness import check
    t_check = time.perf_counter()
    if "limits" in config:  # only a rehearsal's tiny sizes have them
      limits = {k: v for k, v in config["limits"].items()
                if not k.startswith("_")}
    else:
      limits = check.load_limits(data_dir(bench), cell["name"])
    correct = driver.check(cell["name"], config, run, limits)
    print(f"check took {time.perf_counter() - t_check:.1f} s")
  finally:
    shutil.rmtree(work_dir, ignore_errors=True)

  metrics = {}
  if args.trace:
    for entry in metrics_of(bench, cell, "per_layer"):
      reader = importlib.import_module(
          f"benchmark.layer_metrics.{entry['name']}")
      value = reader.read(run)
      if value is not None:
        metrics[entry["name"]] = {"value": value,
                                  "unit": entry["unit"]}
  else:
    for entry in metrics_of(bench, cell, "end_to_end"):
      metrics[entry["name"]] = {
          "value": run["end_to_end"][entry["name"]],
          "unit": entry["unit"]}
  result = {"correct": bool(correct), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics,
            "device": device}
  if breakdown:
    result["breakdown"] = breakdown
  # Each number compared beside its limit: the last lines of standard
  # error, and the last key of the result's line.
  compared = run.get("compared", {})
  for name, pair in compared.items():
    print(f"check {name}: {pair['value']!r} limit {pair['limit']!r}",
          file=sys.stderr)
  result["check"] = compared
  if args.rehearse_cpu:
    # A CPU's numbers never go under a device metric's name.
    result = {"rehearsal_on": device["platform"],
              "correct": result["correct"],
              "attempted": result["attempted"],
              "failed": result["failed"],
              "metric_names": sorted(metrics),
              "check": compared}
  print(json.dumps(result))
  return 0


if __name__ == "__main__":
  sys.exit(main())
