"""What the readers of the program's own spans share (not a metric).

The loop's process keeps its stage spans in the tracer's ring
(`tensor2robot_tpu/telemetry/core.py`; docs/OBSERVABILITY.md, "Standard
spans"). The readers run in that process after the run, so they read
the ring itself. This module picks the dispatches of the measured
window out of it and cuts each thread's time into named parts:

  * a dispatch belongs to the window when its first step lies in the
    range the window's log records cover (`run["records"]`);
  * `seq`, which the loop's dispatch span carries beside `step`, ties
    the feed thread's spans (`feed.*`) and `loop.wait_feed` to it. Each
    train loop names that span after itself (`qtopt.dispatch`,
    `train.dispatch`): the kind's driver states the name on the run's
    record, `run["dispatch_span"]`;
  * a thread's window runs from the first span of the first such
    dispatch's cycle to the first span of the cycle after the last, so
    it holds whole cycles and no span straddles its edges;
  * self time is a span's duration less what its children on the same
    thread cover (`harness/trace_reduce.self_times`), so the self times
    of a thread add up to the time its spans cover, and the rest of the
    window is time no span names.

Nothing is returned when a span of the window is missing: the ring is
bounded, and a long run rolls it (a parent of the PR that added the
spans has none at all). A partial number is never computed.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from benchmark.harness import trace_reduce

DISPATCH = "qtopt.dispatch"  # where a record states no other
WAIT = "loop.wait_feed"
PULL, SAMPLE, STACK = "feed.pull", "feed.sample", "feed.stack"
DEVICE_PUT, QUEUE_PUT = "feed.device_put", "feed.queue_put"


def _thread_part(spans: Sequence[dict], tid: int, t0: float,
                 t1: float) -> dict:
  """Self seconds by name of one thread's spans inside [t0, t1)."""
  # A span's end is `ts + dur` in floating point, which can land an
  # ulp past the next span's start and make that one its child: a
  # nanosecond off every duration keeps neighbours apart.
  events = [(s["name"], s["ts"], max(s["dur"] - 1e-9, 0.0))
            for s in spans if s["tid"] == tid and t0 <= s["ts"] < t1]
  self_s: Dict[str, float] = {}
  for name, _, _, own, _ in trace_reduce.self_times(events):
    self_s[name] = self_s.get(name, 0.0) + own
  return {"tid": tid, "t0": t0, "t1": t1, "seconds": t1 - t0,
          "self_s": self_s,
          "unnamed_s": max(t1 - t0 - sum(self_s.values()), 0.0)}


def select(spans: Sequence[dict], record_steps: Sequence[int],
           log_every_steps: int, k: int,
           dispatch: str = DISPATCH) -> Optional[dict]:
  """The window's dispatches out of `spans` (`Tracer.snapshot_spans`);
  `dispatch` is the name of the loop's dispatch span.

  Returns None where any span of the window is missing, else
  `{"steps", "seqs", "spans" (name -> the window's spans of that
  name), "loop", "feed" (each a `_thread_part`)}`.
  """
  if not record_steps or not spans:
    return None
  lo = min(record_steps) - log_every_steps
  hi = max(record_steps)
  dispatches = sorted(
      (s for s in spans if s["name"] == dispatch
       and lo <= s.get("args", {}).get("step", lo - 1) < hi),
      key=lambda s: s["args"]["step"])
  if len(dispatches) * k != hi - lo:
    return None
  seqs = [s["args"]["seq"] for s in dispatches]
  first, last, inside = seqs[0], seqs[-1], set(seqs)

  by_name: Dict[str, List[dict]] = {dispatch: dispatches}
  after: Dict[str, float] = {}  # thread -> start of the cycle after
  for s in spans:
    args = s.get("args", {})
    if s["name"] in (WAIT, PULL, SAMPLE, STACK, DEVICE_PUT, QUEUE_PUT):
      if args.get("seq") in inside:
        by_name.setdefault(s["name"], []).append(s)
      elif args.get("seq") == last + 1:
        thread = "loop" if s["name"] == WAIT else "feed"
        after[thread] = min(s["ts"], after.get(thread, s["ts"]))
    elif s["name"].startswith("loop.") \
        and lo < args.get("step", lo) <= hi:
      by_name.setdefault(s["name"], []).append(s)
  n = len(seqs)
  # `feed.pull` wraps a stacked dispatch's samples and stack; a
  # program without stacking (K = 1) has neither it nor `feed.stack`.
  want = {WAIT: n, SAMPLE: n * k, DEVICE_PUT: n, QUEUE_PUT: n,
          STACK: n if k > 1 else 0, PULL: n if k > 1 else 0}
  if any(len(by_name.get(name, ())) != count
         for name, count in want.items()):
    return None

  def part(thread: str, tid: int, first_spans: Sequence[str]) -> dict:
    """From the first span of the first cycle to that of the cycle
    after the last (where the run ended there: to the thread's end)."""
    t0 = min(s["ts"] for name in first_spans
             for s in by_name.get(name, ()) if s["args"]["seq"] == first)
    t1 = after.get(thread) or max(
        s["ts"] + s["dur"] for s in spans
        if s["tid"] == tid and s["ts"] >= t0)
    return _thread_part(spans, tid, t0, t1)

  return {
      "steps": n * k, "seqs": seqs, "spans": by_name,
      "loop": part("loop", dispatches[0]["tid"], (WAIT,)),
      "feed": part("feed", by_name[DEVICE_PUT][0]["tid"],
                   (PULL, SAMPLE)),
  }


def of_run(run: dict) -> Optional[dict]:
  """`select` on this process's ring for the run's window; kept on
  the run's record so that the readers share one pass."""
  if "span_window" not in run:
    from tensor2robot_tpu import telemetry
    run["span_window"] = select(
        telemetry.get_tracer().snapshot_spans(),
        [rec["step"] for rec in run["records"]],
        run["config"]["train"]["log_every_steps"], run["k"],
        run.get("dispatch_span", DISPATCH))
  return run["span_window"]


def total_ms_per_step(run: dict, name: str) -> Optional[float]:
  """Summed duration of the window's spans called `name`, over the
  window's steps."""
  window = of_run(run)
  if window is None or not window["spans"].get(name):
    return None
  return 1e3 * sum(s["dur"] for s in window["spans"][name]) \
      / window["steps"]


def median_ms(run: dict, name: str) -> Optional[float]:
  """Median duration of the window's spans called `name`."""
  import statistics

  window = of_run(run)
  if window is None or not window["spans"].get(name):
    return None
  return 1e3 * statistics.median(
      s["dur"] for s in window["spans"][name])
