"""From a `jax.profiler` trace (`*.xplane.pb`) to the numbers the
benchmark reports: device busy and idle time, time per operation,
collective time, and the longest idle gaps by what the host did.

What a TPU trace under jax 0.9 looks like (looked at by hand, PR 23;
`tools/trace_probe.py` prints one): a plane `/device:TPU:<n>` per chip
with the lines `XLA Modules` (one event per executed program),
`XLA Ops` (one event per operation, a `%while` or `%conditional`
spanning the operations of its body) and `Async XLA Ops` (copies that
overlap them); a plane `/host:CPU` with one line per host thread, among
them `python` lines of the Python tracer. All on one clock, in
nanoseconds from the start of the trace.

The arithmetic works on plain `(name, start_ns, duration_ns)` tuples so
that it can be checked on made-up events as well as on a recording.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def find_xplane(trace_dir: str) -> str:
  paths = sorted(glob.glob(
      os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
  if not paths:
    raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
  return paths[-1]


def load(path: str):
  """{plane name: {line name: [Event]}} of one recording. Lines of the
  same name in a plane (host threads called `python`) are merged."""
  import jax

  data = jax.profiler.ProfileData.from_file(path)
  planes: Dict[str, Dict[str, List[Event]]] = {}
  for plane in data.planes:
    lines = planes.setdefault(plane.name, {})
    for line in plane.lines:
      lines.setdefault(line.name, []).extend(
          (ev.name, float(ev.start_ns), float(ev.duration_ns))
          for ev in line.events)
  return planes


def op_name(hlo: str) -> str:
  """`%fusion.9 = (bf16[...` -> `fusion.9`."""
  head = hlo.split(" = ", 1)[0].strip()
  return head.lstrip("%") or hlo[:40]


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
  """Total length covered by [start, end) intervals."""
  total, cur_start, cur_end = 0.0, None, None
  for start, end in sorted(intervals):
    if cur_end is None or start > cur_end:
      if cur_end is not None:
        total += cur_end - cur_start
      cur_start, cur_end = start, end
    else:
      cur_end = max(cur_end, end)
  if cur_end is not None:
    total += cur_end - cur_start
  return total


def self_times(events: Sequence[Event]) -> List[Tuple[str, float, float, float, bool]]:
  """(name, start, end, self_ns, is_leaf) per event of one line.

  An event that spans later-starting events (a `%while` over its body)
  is an umbrella: its self time is its duration less what its children
  cover, and it is not a leaf. Summing durations over a line counts the
  body twice; summing self times does not.
  """
  order = sorted(events, key=lambda e: (e[1], -e[2]))
  out = []
  stack: List[list] = []  # [name, start, end, child_ns, has_child]

  def close(until: float):
    while stack and stack[-1][2] <= until:
      name, start, end, child_ns, has_child = stack.pop()
      out.append((name, start, end, max(end - start - child_ns, 0.0),
                  not has_child))
      if stack:
        stack[-1][3] += end - start
        stack[-1][4] = True

  for name, start, dur in order:
    close(start)
    end = start + dur
    if stack and end > stack[-1][2]:
      end = stack[-1][2]  # clock rounding: a child never outlives its parent
    stack.append([name, start, end, 0.0, False])
  close(float("inf"))
  return out


def device_summary(ops: Sequence[Event], window: Tuple[float, float]):
  """Busy time (union of leaf operations inside `window`), time per
  operation kind (self times), collective time, of one chip."""
  lo, hi = window
  per_kind: Dict[str, float] = {}
  leaves = []
  collective = 0.0
  for name, start, end, self_ns, leaf in self_times(ops):
    if end <= lo or start >= hi:
      continue
    short = op_name(name)
    per_kind[short] = per_kind.get(short, 0.0) + self_ns
    if leaf:
      leaves.append((max(start, lo), min(end, hi)))
      if any(c in short for c in COLLECTIVES):
        collective += self_ns
  return {"busy_ns": union_ns(leaves), "per_op_ns": per_kind,
          "collective_ns": collective}


def idle_gaps(modules: Sequence[Event], host: Sequence[Event],
              window: Tuple[float, float], top: int = 10):
  """The gaps between executed programs inside `window`, summed by what
  the host did in each: the shortest host event that covers at least
  half of the gap (the innermost frame that explains it), else the one
  that overlaps it most."""
  lo, hi = window
  spans = sorted((s, s + d) for _, s, d in modules
                 if s + d > lo and s < hi)
  gaps = []
  cursor = lo
  for start, end in spans:
    if start > cursor:
      gaps.append((cursor, start))
    cursor = max(cursor, end)
  if hi > cursor:
    gaps.append((cursor, hi))
  by_name: Dict[str, float] = {}
  host_sorted = sorted(host, key=lambda e: e[1])
  for g0, g1 in gaps:
    length = g1 - g0
    covering, widest = None, ("unattributed", 0.0)
    for name, start, dur in host_sorted:
      if start >= g1:
        break
      overlap = min(start + dur, g1) - max(start, g0)
      if overlap <= 0:
        continue
      if overlap >= 0.5 * length and (covering is None
                                      or dur < covering[1]):
        covering = (name, dur)
      if overlap > widest[1]:
        widest = (name, overlap)
    name = covering[0] if covering else widest[0]
    by_name[name] = by_name.get(name, 0.0) + length
  ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
  return [[name, ns / 1e9] for name, ns in ranked]


def whole_runs(runs: Sequence[Tuple[float, float]],
               ops: Sequence[Event]) -> List[Tuple[float, float]]:
  """Those of `runs` ([start, end) of a program's executions on one
  chip) that the recording holds whole; `ops` is that chip's line of
  operations.

  A loop that keeps a dispatch in flight is inside a program when the
  harness starts and stops its recording, and the recording then holds
  the tail of one execution and the head of another, each as an
  execution of its own. Counted as such they give 4 runs for the busy
  time of three, and a time per step 4/3 short (PERF.md §6, PR 31 and
  33). Only an execution that holds the chip's first or last recorded
  operation can be cut. Such a one counts where it holds as many
  operations as another execution of the program does: whole
  executions of one program agree in that to the operation (10,696
  each in `qtopt_64.train`, where the cut ones held 9,639 and 1,264),
  a part agrees with nothing. Lengths would not do: whole executions
  differ by a tenth where the feed sets the pace, and a tail can be
  nine tenths of a whole. An execution clear of both edges is whole,
  whatever it holds.
  """
  if not ops:
    return []
  starts = sorted(start for _, start, _ in ops)
  first, last = starts[0], max(start + dur for _, start, dur in ops)
  held = [bisect.bisect_left(starts, end)
          - bisect.bisect_left(starts, start) for start, end in runs]
  return [run for i, run in enumerate(runs)
          if (run[0] > first and run[1] < last)
          or held[i] in held[:i] + held[i + 1:]]


def reduce_trace(path: str, chips: int,
                 window: Optional[Tuple[float, float]] = None,
                 program: Optional[str] = None,
                 host_events: Sequence[Event] = ()) -> dict:
  """The whole reduction of one recording.

  window: (start_ns, end_ns) on the trace's clock; default: from the
    start of the recording to the end of the last executed program on
    any chip. The harness starts a recording just after a dispatch
    has finished, so that window holds as many waits for the next
    dispatch as it holds dispatches.
  program: count only executions of programs whose name starts with
    this (`jit_k_steps`) in `program_runs` / `program_busy_s`, and of
    those only the ones the recording holds whole (`whole_runs`).
    `busy_s`, `window_s`, `device_ops` and `idle_gaps` keep every
    operation, as the idle share must.
  host_events: the harness's own host spans on the trace's clock,
    beside whatever host events the recording holds.
  """
  return reduce_planes(load(path), chips, window, program, host_events)


def reduce_planes(planes: Dict[str, Dict[str, List[Event]]], chips: int,
                  window: Optional[Tuple[float, float]] = None,
                  program: Optional[str] = None,
                  host_events: Sequence[Event] = ()) -> dict:
  """`reduce_trace` on what `load` gives: {plane: {line: [Event]}}, so
  that it can be checked on a made-up recording."""
  devices = sorted(
      (int(m.group(1)), name) for name in planes
      if (m := DEVICE_PLANE.match(name)))
  if len(devices) < chips:
    raise ValueError(
        f"trace has {len(devices)} device planes, cell needs {chips}")
  devices = devices[:chips]
  if window is None:
    spans = [(s, s + d) for _, name in devices
             for _, s, d in planes[name].get(MODULES_LINE, [])]
    if not spans:
      raise ValueError("no program ran on the device in this trace")
    window = (0.0, max(e for _, e in spans))
  host = [ev for line, events in planes.get(HOST_PLANE, {}).items()
          for ev in events] + list(host_events)
  per_device = []
  per_op: Dict[str, float] = {}
  for _, name in devices:
    lines = planes[name]
    summary = device_summary(lines.get(OPS_LINE, []), window)
    modules = lines.get(MODULES_LINE, [])
    runs = whole_runs(
        [(s, s + d) for n, s, d in modules
         if (program is None or n.startswith(program))
         and s >= window[0] and s + d <= window[1]],
        lines.get(OPS_LINE, []))
    prog_busy = sum(
        device_summary(lines.get(OPS_LINE, []), run)["busy_ns"]
        for run in runs)
    summary.update(program_runs=len(runs), program_busy_ns=prog_busy)
    per_device.append(summary)
    for op, ns in summary["per_op_ns"].items():
      per_op[op] = per_op.get(op, 0.0) + ns / len(devices)
  first = planes[devices[0][1]]
  n = len(per_device)
  ranked = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
  return {
      "window_s": (window[1] - window[0]) / 1e9,
      "busy_s": sum(d["busy_ns"] for d in per_device) / n / 1e9,
      "collective_s": sum(d["collective_ns"] for d in per_device) / n / 1e9,
      "program_runs": per_device[0]["program_runs"],
      "program_busy_s": sum(d["program_busy_ns"]
                            for d in per_device) / n / 1e9,
      "device_ops": [[op, ns / 1e9] for op, ns in ranked],
      "idle_gaps": idle_gaps(first.get(MODULES_LINE, []), host, window),
  }
