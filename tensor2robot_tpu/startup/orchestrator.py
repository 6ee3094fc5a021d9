"""Overlapped startup phases: compile ∥ restore ∥ input spin-up.

A cold process start has three independent serial costs — AOT
compilation (CPU-bound in XLA, releases the GIL), orbax checkpoint
restore (disk I/O + H2D), and input-pipeline spin-up (host CPU /
tf.data) — that today run back-to-back. They touch disjoint resources,
so threads recover most of the sum; `run_overlapped` is the one shared
primitive: named thunks, all started together, all joined, per-phase
wall timings recorded, failures surfaced only AFTER every phase has
finished (a half-started phase must never leak a worker thread or a
prefetcher holding device buffers).

`Phase` is how every stretch of a trainer's start is put on the one
tracer and into the registry (docs/OBSERVABILITY.md, "Start-up"): a
span `startup.<name>` and a gauge `startup.<name>_s`, from one reading
of the clock.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Any, Callable, Dict, Mapping, Optional

from tensor2robot_tpu.telemetry import core as tcore
from tensor2robot_tpu.telemetry import metrics as tmetrics

log = logging.getLogger(__name__)

# Of each thread: how many phases deep it is, and the seconds of the
# phases it has finished at depth 0. The second is what a trainer's
# thread has a name for of its own start, tracer on or off.
_thread = threading.local()


class Phase:
  """`with Phase(name, **args):` is `telemetry.span("startup." + name,
  **args)` and the gauge `startup.<name>_s` of the same `seconds`,
  which is set with the tracer off as well. What is known only when
  the work is done (a state's bytes, the slowest of the joined phases)
  goes into `args` inside the `with`."""

  def __init__(self, name: str, **args):
    self.name, self.args = name, args
    self.seconds: Optional[float] = None

  def __enter__(self) -> "Phase":
    self._depth = getattr(_thread, "depth", 0)
    _thread.depth = self._depth + 1
    self.t0 = time.monotonic()
    return self

  def __exit__(self, exc_type, exc, tb) -> bool:
    self.seconds = time.monotonic() - self.t0
    _thread.depth = self._depth
    if not self._depth:
      _thread.top_level_s = top_level_seconds() + self.seconds
    if exc_type is not None:
      self.args["error"] = exc_type.__name__  # as `telemetry.span` does
    tcore.get_tracer().record("startup." + self.name, self.t0,
                              self.seconds, **self.args)
    tmetrics.gauge(f"startup.{self.name}_s").set(self.seconds)
    return False


def top_level_seconds() -> float:
  """The seconds of the phases that the calling thread has finished
  outside any other phase, since `restart_account` on it."""
  return getattr(_thread, "top_level_s", 0.0)


def restart_account() -> None:
  """A trainer's entry: the calling thread's `top_level_seconds` start
  again at 0 (and its depth, which a run that died inside a phase left
  standing)."""
  _thread.depth, _thread.top_level_s = 0, 0.0


@dataclasses.dataclass
class StartupReport:
  """Outcome of one `run_overlapped` call."""

  mode: str                      # "overlapped" | "serial"
  results: Dict[str, Any]        # phase name → thunk return value
  seconds: Dict[str, float]      # phase name → wall seconds
  total_seconds: float           # wall of the whole join
  errors: Dict[str, BaseException] = dataclasses.field(
      default_factory=dict)      # phase name → what it raised

  def raise_first(self, order=None) -> None:
    """Re-raises the first failed phase (in `order`, default insertion)."""
    for name in (order or self.errors):
      if name in self.errors:
        raise self.errors[name]

  @property
  def serial_seconds(self) -> float:
    """What the same phases would have cost back-to-back."""
    return sum(self.seconds.values())

  @property
  def overlap_saved_seconds(self) -> float:
    return max(self.serial_seconds - self.total_seconds, 0.0)


def run_overlapped(phases: Mapping[str, Callable[[], Any]],
                   overlap: bool = True,
                   span_args: Optional[Mapping[str, Mapping[str, Any]]]
                   = None) -> StartupReport:
  """Runs named startup thunks concurrently (or serially) and joins all.

  Args:
    phases: {name: zero-arg thunk}. Thunks must be independent — no
      phase may read another's result (pass data through the returned
      report instead).
    overlap: False runs the phases back-to-back in dict order — the
      reference serial path, kept selectable so equivalence is
      testable and a pathological environment (e.g. a jax backend
      that is not thread-safe) has an escape hatch.
    span_args: {name: arguments of that phase's span}.

  Each thunk runs inside `Phase(name)` on the thread that runs it, the
  start-and-join inside `Phase("join")` on the caller's, whose self
  time is the wait for the slowest phase.

  Returns a StartupReport; failures land in `report.errors` (never
  raised here) so the caller can release any sibling phase's
  resources — e.g. a prefetcher pinning device buffers — before
  calling `report.raise_first()`.
  """
  results: Dict[str, Any] = {}
  seconds: Dict[str, float] = {}
  errors: Dict[str, BaseException] = {}

  def run_one(name: str, fn: Callable[[], Any]) -> None:
    phase = Phase(name, **(span_args or {}).get(name, {}))
    try:
      with phase:
        results[name] = fn()
    except BaseException as e:  # re-raised below, never swallowed
      errors[name] = e
    seconds[name] = phase.seconds

  with Phase("join", mode="overlapped" if overlap else "serial") as join:
    if overlap:
      threads = [
          threading.Thread(target=run_one, args=(name, fn),
                           name=f"startup-{name}", daemon=True)
          for name, fn in phases.items()
      ]
      for t in threads:
        t.start()
      for t in threads:
        t.join()
    else:
      for name, fn in phases.items():
        run_one(name, fn)
    join.args["slowest"] = max(seconds, key=seconds.get, default="")
  total = join.seconds

  report = StartupReport(
      mode=join.args["mode"],
      results=results, seconds=seconds, total_seconds=total,
      errors=errors)
  if errors:
    return report
  log.info(
      "Startup (%s): %s → %.2fs wall (serial sum %.2fs, saved %.2fs)",
      report.mode,
      ", ".join(f"{k}={v:.2f}s" for k, v in seconds.items()),
      total, report.serial_seconds, report.overlap_saved_seconds)
  return report


def close_quietly(obj: Optional[Any]) -> None:
  """Best-effort close of a phase result during error unwinding."""
  if obj is None:
    return
  close = getattr(obj, "close", None)
  if close is None:
    return
  try:
    close()
  except Exception:  # already unwinding a real error
    log.warning("close() failed during startup unwinding", exc_info=True)
