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
What an operation's event metadata says of it (`tf_op`: the HLO
`op_name` with the program's `jax.named_scope`s in it; `hlo_category`)
travels beside them, keyed by the event's name (`op_metadata`).
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
# The program's named scopes, in the order they are looked for in an
# operation's `tf_op`: the first that occurs names the operation. An
# umbrella stands after what it holds (`mtp/block` after the mixer's
# and the FFN's scopes), except QT-Opt's `backward`, the critic's
# forward and backward pass, which is read whole and so stands before
# the `torso` and `q_head` inside it. A later PR that adds a scope to
# the program appends it here with its reader.
SCOPES = (
    "gated_delta/scan", "gated_delta/conv", "gated_attention",
    "mla/attend", "mla/q_proj", "mla/kv_proj", "mla/o_proj",
    "dense_ffn", "moe/route", "moe/experts", "moe/shared",
    "lm_head_loss", "mtp_head_loss", "mtp/combine", "mtp/block",
    "backward", "cem_tower", "cem_pool", "torso", "q_head",
    "bellman_loss", "optimizer", "polyak")
OTHER, UNNAMED = "other", "unnamed"  # a `tf_op` with none of them; none
PASSES = ("forward", "recompute", "backward")
KERNEL_CATEGORY = "custom-call"
# An execution that starts or ends this close to the recording's
# first or last operation holds that edge (`whole_runs`): a thousand
# times the clock's rounding, a thousandth of the shortest program.
EDGE_MARGIN_NS = 1000.0
# A scope stands in a `tf_op` between `/`, or inside the transforms
# that wrap it: `jit(k_steps)/transpose(jvp(mla/attend))/dot_general:`.
_SCOPE_RES = tuple(
    (scope, re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:$|[/):])"))
    for scope in SCOPES)
_NUMBERED = re.compile(r"(?:\.(?:\d+|remat\d*|clone))+$")

OpMetadata = Dict[str, Tuple[str, str]]  # event name: tf_op, hlo_category


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


def _varint(data: bytes, pos: int) -> Tuple[int, int]:
  value = shift = 0
  while True:
    byte = data[pos]
    pos += 1
    value |= (byte & 0x7F) << shift
    if byte < 0x80:
      return value, pos
    shift += 7


def _fields(data: bytes, pos: int, end: int):
  """(field number, value) of one protobuf message's fields in
  data[pos:end]: a varint's value, or the (start, end) of a
  length-delimited field; fixed-width fields are passed over."""
  while pos < end:
    key, pos = _varint(data, pos)
    wire = key & 7
    if wire == 0:
      value, pos = _varint(data, pos)
      yield key >> 3, value
    elif wire == 2:
      size, pos = _varint(data, pos)
      yield key >> 3, (pos, pos + size)
      pos += size
    elif wire in (1, 5):
      pos += 8 if wire == 1 else 4
    else:
      raise ValueError(f"wire type {wire} at byte {pos}")


def _map_entry(data: bytes, span):
  """(key, value) of one entry of a protobuf map from integers to
  messages; a key of 0 and an empty message are left off the wire."""
  entry = dict(_fields(data, *span))
  return entry.get(1, 0), entry.get(2, (0, 0))


def op_metadata(path: str) -> Dict[str, OpMetadata]:
  """{device plane: {event name: (tf_op, hlo_category)}} of one
  recording: what `load`'s events cannot say of themselves, since
  `jax.profiler.ProfileData` gives an event's own statistics and not
  those of its metadata. Read off the file's wire format, the five
  messages of tsl's `xplane.proto` that lead there (XSpace.planes = 1;
  XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
  XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
  .str_value = 5, .ref_value = 7; XStatMetadata.name = 2); the lines
  and the host's planes are passed over unread. tensorflow's
  `xplane_pb2` reads the same, after importing all of tensorflow into
  the process that holds the chip. An operation with no `tf_op` (a
  copy, XLA's own custom calls) has the empty string."""
  with open(path, "rb") as f:
    data = f.read()
  text = lambda span: data[span[0]:span[1]].decode("utf-8", "replace")  # noqa: E731
  out: Dict[str, OpMetadata] = {}
  for field, plane in _fields(data, 0, len(data)):
    if field != 1:
      continue
    name, events, stat_names = "", [], {}
    for field, value in _fields(data, *plane):
      if field == 2:
        name = text(value)
      elif field == 4:
        events.append(_map_entry(data, value)[1])
      elif field == 5:
        key, stat = _map_entry(data, value)
        stat_names[key] = next(
            (text(v) for f, v in _fields(data, *stat) if f == 2), "")
    if not DEVICE_PLANE.match(name):
      continue
    wanted = {key: stat for key, stat in stat_names.items()
              if stat in ("tf_op", "hlo_category")}
    ops = out.setdefault(name, {})
    for event in events:
      event_name, found = "", {}
      for field, value in _fields(data, *event):
        if field == 2:
          event_name = text(value)
        elif field == 5:
          stat = dict(_fields(data, *value))
          if stat.get(1) in wanted:
            found[wanted[stat[1]]] = (
                text(stat[5]) if 5 in stat
                else stat_names.get(stat.get(7), ""))
      if "hlo_category" in found:
        ops[event_name] = (found.get("tf_op", ""),
                           found["hlo_category"])
  return out


def op_name(hlo: str) -> str:
  """`%fusion.9 = (bf16[...` -> `fusion.9`."""
  head = hlo.split(" = ", 1)[0].strip()
  return head.lstrip("%") or hlo[:40]


def kind_name(short: str) -> str:
  """An operation's name without its trailing numbers:
  `flash_attention.296` -> `flash_attention`, `fusion.3204.remat` ->
  `fusion`."""
  return _NUMBERED.sub("", short) or short


def group_name(short: str) -> str:
  """The kind under which the breakdown sums an operation: its
  `kind_name`, a fusion of any flavour (`multiply_add_fusion.416`)
  as `fusion`, so that ten lines of scope and kind cover most of a
  step (68 % of the JoyAI cell's busy time with the flavours apart)."""
  kind = kind_name(short)
  return "fusion" if kind.endswith("fusion") else kind


def primitive_of(tf_op: str) -> str:
  """The last component of a `tf_op`, JAX's primitive:
  `.../gated_delta/scan/checkpoint/pallas_call:` -> `pallas_call`."""
  return tf_op.rstrip(":").rsplit("/", 1)[-1]


@functools.lru_cache(maxsize=None)  # an event a call, a tf_op a program's op
def scope_of(tf_op: str) -> str:
  """The first of `SCOPES` that stands in `tf_op`; `other` for a
  `tf_op` with none of them, `unnamed` for no `tf_op`."""
  if not tf_op:
    return UNNAMED
  return next((scope for scope, pattern in _SCOPE_RES
               if pattern.search(tf_op)), OTHER)


def pass_of(tf_op: str) -> str:
  """Which pass of a training step an operation belongs to, by its
  `tf_op` (jax 0.9): what a `jax.checkpoint` runs again on the way
  back stands under `rematted_computation`, the other operations of
  the way back under `transpose(`."""
  if "rematted_computation" in tf_op:
    return "recompute"
  return "backward" if "transpose(" in tf_op else "forward"


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


def device_summary(ops: Sequence[Event], window: Tuple[float, float],
                   timed: Optional[Sequence[tuple]] = None,
                   metadata: Optional[OpMetadata] = None):
  """Busy time (union of leaf operations inside `window`), self time
  per operation (`per_op_ns`) and per group `<scope>:<group name>`
  (`per_group_ns`: `mla/attend:flash_attention`, `mla/q_proj:fusion`),
  collective time, of one chip; `timed` is `self_times(ops)` where the
  caller has it already, and without `metadata` no group is made."""
  lo, hi = window
  per_kind: Dict[str, float] = {}
  per_group: Dict[str, float] = {}
  leaves = []
  collective = 0.0
  for name, start, end, self_ns, leaf in (
      self_times(ops) if timed is None else timed):
    if end <= lo or start >= hi:
      continue
    short = op_name(name)
    per_kind[short] = per_kind.get(short, 0.0) + self_ns
    if metadata is not None:
      group = (f"{scope_of(metadata.get(name, ('', ''))[0])}:"
               f"{group_name(short)}")
      per_group[group] = per_group.get(group, 0.0) + self_ns
    if leaf:
      leaves.append((max(start, lo), min(end, hi)))
      if any(c in short for c in COLLECTIVES):
        collective += self_ns
  return {"busy_ns": union_ns(leaves), "per_op_ns": per_kind,
          "per_group_ns": per_group, "collective_ns": collective}


def by_scope(timed: Sequence[tuple], runs: Sequence[Tuple[float, float]],
             metadata: OpMetadata):
  """Self time by named scope and the kernels' calls, over the
  operations that lie inside one of `runs` (a program's whole
  executions on one chip); `timed` is `self_times` of the chip's
  operations. Returns (`scope_ns`, `kernels`):

    scope_ns: {scope: {pass: ns}}: the first of `SCOPES` in the
      operation's `tf_op` (`scope_of`), split by `pass_of`; an umbrella
      (`%while`) counts its self time only, so nothing counts twice and
      the whole sums to the self time of the executions.
    Each operation counts as the median of its occurrences' self times
    in those executions times their number (a step's operation occurs
    K times an execution). The device stalls now and then (PR 40: in
    two traced runs of nine one occurrence stood 15 ms over its three
    others, or a stretch of operations a third over theirs), and the
    sum put that on whichever scope it hit: 4-6 % of a scope of 150 ms.
    kernels: {(kind name, scope, pass, primitive): [calls, ns]} of
      every operation of category `custom-call`: the Pallas programs
      (`flash_attention` under `mla/attend`, primitive `pallas_call`)
      and XLA's own: `ragged-dot-none`, whose `tf_op` is its own name
      and so stands under `other`, and the anonymous `custom-call`s of
      a few nanoseconds beside every loop.
  """
  runs = sorted(runs)
  starts = [start for start, _ in runs]
  scope_ns: Dict[str, Dict[str, float]] = {}
  kernels: Dict[Tuple[str, str, str, str], List[float]] = {}
  occurrences: Dict[str, List[float]] = {}
  for name, start, end, self_ns, _ in timed:
    i = bisect.bisect_right(starts, start) - 1
    if i >= 0 and end <= runs[i][1]:
      occurrences.setdefault(name, []).append(self_ns)
  for name, times in occurrences.items():
    typical_ns = statistics.median(times) * len(times)
    tf_op, category = metadata.get(name, ("", ""))
    scope, which = scope_of(tf_op), pass_of(tf_op)
    passes = scope_ns.setdefault(scope, dict.fromkeys(PASSES, 0.0))
    passes[which] += typical_ns
    if category == KERNEL_CATEGORY:
      entry = kernels.setdefault(
          (kind_name(op_name(name)), scope, which, primitive_of(tf_op)),
          [0, 0.0])
      entry[0] += len(times)
      entry[1] += typical_ns
  return scope_ns, kernels


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
  whatever it holds; clear by `EDGE_MARGIN_NS`, since a cut
  execution's event and the operation at the recording's edge are
  rounded apart (one traced run in five counted a tail as whole, and
  read `lm_mla_step_mfu` 40.9 for 27.5; PERF.md §6, PR 37 and 40).
  """
  if not ops:
    return []
  starts = sorted(start for _, start, _ in ops)
  first, last = starts[0], max(start + dur for _, start, dur in ops)
  held = [bisect.bisect_left(starts, end)
          - bisect.bisect_left(starts, start) for start, end in runs]
  return [run for i, run in enumerate(runs)
          if (run[0] > first + EDGE_MARGIN_NS
              and run[1] < last - EDGE_MARGIN_NS)
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
    this (`jit_k_steps`) in `program_runs` / `program_busy_s`,
    `scope_ns` and `kernels`, and of those only the ones the recording
    holds whole (`whole_runs`). `busy_s`, `window_s`, `device_ops` and
    `idle_gaps` keep every operation, as the idle share must.
  host_events: the harness's own host spans on the trace's clock,
    beside whatever host events the recording holds.
  """
  return reduce_planes(load(path), chips, window, program, host_events,
                       op_metadata(path))


def reduce_planes(planes: Dict[str, Dict[str, List[Event]]], chips: int,
                  window: Optional[Tuple[float, float]] = None,
                  program: Optional[str] = None,
                  host_events: Sequence[Event] = (),
                  metadata: Optional[Dict[str, OpMetadata]] = None
                  ) -> dict:
  """`reduce_trace` on what `load` and `op_metadata` give: {plane:
  {line: [Event]}} and {plane: {event name: (tf_op, hlo_category)}},
  so that it can be checked on a made-up recording. Beside
  `reduce_trace`'s numbers, over the whole executions of `program`
  and a mean over the chips (`by_scope`):

    scope_ns: {scope: {"forward" | "recompute" | "backward": ns}}
    kernels: [{"name", "scope", "pass", "primitive", "calls", "ns"}],
      by time
    program_self_s: the self time of every operation in them, each at
      the median of its occurrences, which `scope_ns` sums to
  """
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
  n = len(devices)
  per_device = []
  per_group: Dict[str, float] = {}
  scope_ns: Dict[str, Dict[str, float]] = {}
  kernels: Dict[Tuple[str, str, str, str], List[float]] = {}
  for _, name in devices:
    lines = planes[name]
    ops = lines.get(OPS_LINE, [])
    named = (metadata or {}).get(name, {})
    timed = self_times(ops)
    summary = device_summary(ops, window, timed, named)
    runs = whole_runs(
        [(s, s + d) for n_, s, d in lines.get(MODULES_LINE, [])
         if (program is None or n_.startswith(program))
         and s >= window[0] and s + d <= window[1]], ops)
    prog_busy = sum(device_summary(ops, run, timed)["busy_ns"]
                    for run in runs)
    summary.update(program_runs=len(runs), program_busy_ns=prog_busy)
    per_device.append(summary)
    for group, ns in summary["per_group_ns"].items():
      per_group[group] = per_group.get(group, 0.0) + ns / n
    scopes, calls = by_scope(timed, runs, named)
    for scope, passes in scopes.items():
      mean = scope_ns.setdefault(scope, dict.fromkeys(PASSES, 0.0))
      for which, ns in passes.items():
        mean[which] += ns / n
    for key, (count, ns) in calls.items():
      entry = kernels.setdefault(key, [0.0, 0.0])
      entry[0] += count / n
      entry[1] += ns / n
  first = planes[devices[0][1]]
  ranked = sorted(per_group.items(), key=lambda kv: -kv[1])[:10]
  return {
      "window_s": (window[1] - window[0]) / 1e9,
      "busy_s": sum(d["busy_ns"] for d in per_device) / n / 1e9,
      "collective_s": sum(d["collective_ns"] for d in per_device) / n / 1e9,
      "program_runs": per_device[0]["program_runs"],
      "program_busy_s": sum(d["program_busy_ns"]
                            for d in per_device) / n / 1e9,
      "program_self_s": sum(ns for passes in scope_ns.values()
                            for ns in passes.values()) / 1e9,
      "scope_ns": scope_ns,
      "kernels": [
          {"name": name, "scope": scope, "pass": which,
           "primitive": primitive, "calls": count, "ns": ns}
          for (name, scope, which, primitive), (count, ns) in sorted(
              kernels.items(), key=lambda kv: -kv[1][1])],
      "device_ops": [[group, ns / 1e9] for group, ns in ranked],
      "idle_gaps": idle_gaps(first.get(MODULES_LINE, []), host, window),
  }
