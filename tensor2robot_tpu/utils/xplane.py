"""Minimal xplane.pb reader: per-op device time from a jax.profiler trace.

The image's tensorboard profile plugin can't parse traces (protobuf /
pywrap version skew), so this module decodes the XSpace wire format
directly — enough to aggregate device time by HLO op name (the tests
call it; the benchmark reduces by trace_reduce.py). Schema (stable tsl
profiler protos): XSpace.planes=1; XPlane{name=2, lines=3,
event_metadata=4 (map<int64, XEventMetadata{name=2}>)};
XLine{name=2, events=4}; XEvent{metadata_id=1, duration_ps=3}.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterator, List, Tuple


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
  shift = result = 0
  while True:
    b = buf[i]
    result |= (b & 0x7F) << shift
    i += 1
    if not b & 0x80:
      return result, i
    shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, bytes]]:
  """Yields (field_number, wire_type, value) over a message buffer."""
  i = 0
  n = len(buf)
  while i < n:
    tag, i = _varint(buf, i)
    field, wire = tag >> 3, tag & 7
    if wire == 0:  # varint
      value, i = _varint(buf, i)
      yield field, wire, value
    elif wire == 1:  # fixed64
      yield field, wire, buf[i:i + 8]
      i += 8
    elif wire == 2:  # length-delimited
      length, i = _varint(buf, i)
      yield field, wire, buf[i:i + length]
      i += length
    elif wire == 5:  # fixed32
      yield field, wire, buf[i:i + 4]
      i += 4
    else:
      raise ValueError(f"unsupported wire type {wire}")


def _event_metadata_name(buf: bytes) -> Tuple[int, str]:
  """map entry -> (id, XEventMetadata.name)."""
  meta_id, name = 0, ""
  for field, wire, value in _fields(buf):
    if field == 1 and wire == 0:
      meta_id = value
    elif field == 2 and wire == 2:
      for f2, w2, v2 in _fields(value):
        if f2 == 1 and w2 == 0:
          meta_id = v2
        elif f2 == 2 and w2 == 2:
          name = v2.decode("utf-8", "replace")
  return meta_id, name


def op_times_ms(trace_dir: str,
                plane_filter: str = "TPU") -> Dict[str, float]:
  """Aggregates device time (ms) by op/event name across a trace dir.

  Sums XEvent durations over every line of every plane whose name
  contains `plane_filter` (case-insensitive). Covers all .xplane.pb
  files under `trace_dir`.
  """
  paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                    recursive=True)
  totals: Dict[str, float] = {}
  for path in paths:
    buf = open(path, "rb").read()
    for field, wire, plane in _fields(buf):
      if field != 1 or wire != 2:
        continue
      name = ""
      metadata: Dict[int, str] = {}
      lines: List[bytes] = []
      for pf, pw, pv in _fields(plane):
        if pf == 2 and pw == 2:
          name = pv.decode("utf-8", "replace")
        elif pf == 3 and pw == 2:
          lines.append(pv)
        elif pf == 4 and pw == 2:
          mid, mname = _event_metadata_name(pv)
          metadata[mid] = mname
      if plane_filter.lower() not in name.lower():
        continue
      for line in lines:
        for lf, lw, lv in _fields(line):
          if lf != 4 or lw != 2:
            continue
          meta_id = duration_ps = 0
          for ef, ew, ev in _fields(lv):
            if ef == 1 and ew == 0:
              meta_id = ev
            elif ef == 3 and ew == 0:
              duration_ps = ev
          op = metadata.get(meta_id, f"op_{meta_id}")
          totals[op] = totals.get(op, 0.0) + duration_ps / 1e9
  return totals


_ASYNC_WINDOW = re.compile(
    r"%(copy|fusion|all-gather|all-reduce|reduce-scatter"
    r"|collective-permute|all-to-all|send|recv)[\w.]*-(start|done)")


def is_async_window(name: str) -> bool:
  """True for async -start/-done events (copy/collective windows).

  Their recorded durations are WALL SPANS that overlap compute —
  prefetch/communication windows, not busy time — so a table meant to
  attribute device time to compute must drop them (the round-4 lesson:
  both committed top_ops tables were 10/10 copy-starts, attributing
  nothing).
  """
  return bool(_ASYNC_WINDOW.match(name))


def top_ops(trace_dir: str, k: int = 20,
            plane_filter: str = "TPU",
            hlo_only: bool = False,
            compute_only: bool = False) -> List[Tuple[str, float]]:
  """Top-k (op name, device ms) pairs, descending.

  `hlo_only` keeps only leaf HLO instruction events: names must start
  with '%', and '%while'-prefixed spans are dropped too — a while
  instruction is itself an umbrella covering every loop iteration's
  ops, so it would top the table with ~the whole dispatch attributed
  to one "op". `compute_only` additionally drops async -start/-done
  window events (see `is_async_window`), leaving fusions/convs/
  matmuls whose durations are actual busy time and sum to ≈ the
  dispatch's device time.
  """
  totals = op_times_ms(trace_dir, plane_filter)
  items = totals.items()
  if hlo_only:
    items = [(n, v) for n, v in items
             if n.startswith("%") and not n.startswith("%while")]
  if compute_only:
    items = [(n, v) for n, v in items if not is_async_window(n)]
  return sorted(items, key=lambda kv: -kv[1])[:k]
