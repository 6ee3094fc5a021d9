"""What the reference side of a `train_eval` cell costs at a train
state of a chosen size: runs `follow.follow` and
`check.numbers_between` as a run's check does, on the synthetic family
of `tests/data/square_stack.py`, and prints the device's peak, its
limit and the process's peak resident size as one JSON line.

  python3 benchmark/tools/follow_memory.py --params 710e6

A chip tool: on a CPU it proves the path and prints no device peak. The
reference follows K steps, then the control (the family's `loss` one
precision lower) follows the same steps in the program's place, as
`check.numbers_of(control=True)` has it, and the two are compared: so
the host holds the start weights and two followed states (20 bytes a
parameter; a run holds the loop's first checkpoint where this holds
the control, 24), over what the process held when it reached the
device (`host_rss_bytes` has the resident size stage by stage). One
size a process: a peak never falls again.
"""

import argparse
import json
import math
import os
import resource
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _host_rss() -> dict:
  """The process's resident size now and at its peak, in bytes."""
  with open("/proc/self/statm") as f:
    now = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
  # Linux counts ru_maxrss in KiB.
  return {"now": now, "peak": 1024 * resource.getrusage(
      resource.RUSAGE_SELF).ru_maxrss}


def measure(params: float, width: int, rows: int, steps: int,
            seed: int) -> dict:
  import jax

  from benchmark.harness import check, follow
  from benchmark.tests.data import square_stack

  layers = math.ceil(params / width ** 2)
  config = square_stack.config_of(layers, width, rows)
  device = jax.devices()[0]
  out = {"platform": device.platform, "kind": device.device_kind,
         "parameters": layers * width ** 2, "layers": layers,
         "width": width, "rows": rows, "steps": steps, "seed": seed}
  # Where the host's memory goes, stage by stage.
  stages = out["host_rss_bytes"] = {"reached_the_device": _host_rss()}
  weights, stats = square_stack.make_weights(seed, config)
  inputs = {"params": jax.device_get(weights),
            "stats": jax.device_get(stats),
            "batches": square_stack.make_batches(seed, config, steps),
            "first_step": steps}
  del weights
  stages["weights_on_the_host"] = _host_rss()
  seed32 = seed % (2 ** 31 - 1)
  t = time.perf_counter()
  try:
    reference = follow.follow(config, inputs, seed32, False)
    stages["followed_the_reference"] = _host_rss()
    control = follow.follow(config, inputs, seed32, True)
    stages["followed_the_control"] = _host_rss()
  except jax.errors.JaxRuntimeError as e:
    out["follow_failed"] = str(e).strip().splitlines()[0][:300]
  else:
    out["follow_s"] = time.perf_counter() - t
    t = time.perf_counter()
    out["numbers"] = check.numbers_between(
        *control, inputs["params"], *reference)
    out["numbers_between_s"] = time.perf_counter() - t
    stages["compared"] = _host_rss()
  memory = device.memory_stats() or {}  # a CPU reports none
  for key in ("peak_bytes_in_use", "bytes_limit"):
    out[key] = memory.get(key)
  if out["peak_bytes_in_use"]:
    out["device_bytes_per_parameter"] = (
        out["peak_bytes_in_use"] / out["parameters"])
  out["host_peak_rss_bytes"] = _host_rss()["peak"]
  # The process holds gigabytes before any array of ours exists (14.2
  # GB on the v5e machine: the TPU runtime's own); ours is the rest.
  out["host_bytes_per_parameter_over_start"] = (
      out["host_peak_rss_bytes"]
      - stages["reached_the_device"]["now"]) / out["parameters"]
  return out


def main() -> int:
  parser = argparse.ArgumentParser()
  parser.add_argument("--params", type=float, required=True,
                      help="at least so many: rounded up to whole layers")
  parser.add_argument("--width", type=int, default=4096)
  parser.add_argument("--rows", type=int, default=256)
  parser.add_argument("--steps", type=int, default=2)
  parser.add_argument("--seed", type=int, default=7)
  args = parser.parse_args()
  out = measure(args.params, args.width, args.rows, args.steps,
                args.seed)
  print(json.dumps(out))
  return 1 if "follow_failed" in out else 0


if __name__ == "__main__":
  sys.exit(main())
