"""The measured window, shared by every traffic kind that drives one of
the program's train loops: the hook that opens and closes it from
inside the loop, the compile counter, the iterator that keeps a
stream's first batches for the check, and the assembly of the run's
record.

Both loops (`train_qtopt`, `train_eval_model`) speak the same `Hook`
interface (`after_step`, `after_checkpoint`, `end`), dispatch K steps
through `prefetch.scan_k_steps` and feed through `ShardedPrefetcher`,
so nothing here knows which loop it sits in: a driver passes the loop's
name for the two span labels, builds what is its loop's own, calls the
loop inside `until_closed` and assembles the record with `record`.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from tensor2robot_tpu.hooks import Hook


class WindowClosed(Exception):
  """Raised by the hook to end the loop once the window is shut."""


class CompileCounter:
  """Counts backend compile requests while `armed`: a compile inside
  the window fails the run."""

  EVENT = "/jax/compilation_cache/compile_requests_use_cache"

  def __init__(self):
    import jax.monitoring as monitoring
    self.armed = False
    self.count = 0
    monitoring.register_event_listener(self._on_event)

  def _on_event(self, event: str, **kwargs) -> None:
    if self.armed and event == self.EVENT:
      self.count += 1


class WindowHook(Hook):
  """Opens the window after `warm` dispatches and closes it `seconds`
  later, each time behind a `block_until_ready` on the dispatch's
  metrics; in a traced run records a few dispatches in between.

  The window closes on the first dispatch after `seconds` that also
  completes a whole save period (`period_steps`) since it opened, so
  that every window holds as many saves per step as the loop makes:
  closed on any dispatch, a window of eight dispatches held four
  saves and one of nine five, and the rate swung with that count."""

  def __init__(self, warm: int, seconds: float, period_steps: int,
               compiles: CompileCounter, clock_start: float,
               trace_dir: Optional[str] = None,
               trace_dispatches: int = 0,
               loop_name: str = "train_qtopt"):
    self._warm, self._seconds = warm, seconds
    self._period = period_steps
    self.compiles = compiles
    self._clock_start = clock_start
    self.trace_dir, self._trace_n = trace_dir, trace_dispatches
    self._loop_name = loop_name
    self._dispatches = 0
    self._tracing_until: Optional[int] = None
    self.t0 = self.t1 = None
    self.step0 = self.step1 = None
    self.setup_s: Optional[float] = None
    self.first_metrics: Optional[Dict[str, float]] = None
    self.first_state: Any = None
    self.first_step: Optional[int] = None
    self._last_step: Optional[int] = None
    self.checkpoint_stalls_ms: List[float] = []
    self.trace_span = None  # [t_start, t_stop] on the host clock
    # (name, start, end) on the host clock while the trace records:
    # what the loop was doing, for the trace's idle gaps where the
    # program has no spans of its own.
    self.host_spans: List[tuple] = []
    self.loop_thread: Optional[int] = None  # `threading.get_ident()`
    self._last_after_step: Optional[float] = None
    self.first_dispatch_done: Optional[float] = None

  def after_step(self, step: int, metrics: dict) -> None:
    self._dispatches += 1
    now = time.perf_counter()
    if self._tracing_until is not None and self._last_after_step:
      self.host_spans.append(
          (f"{self._loop_name}: wait for the feed, dispatch, log",
           self._last_after_step, now))
    self._last_step, self._last_after_step = step, now
    if self._dispatches == 1:
      self.first_metrics = {k: float(v) for k, v in
                            jax.device_get(metrics).items()}
      self.first_step = step
      self.first_dispatch_done = time.perf_counter()
      self.loop_thread = threading.get_ident()
    if self.t0 is None:
      if self._dispatches >= self._warm:
        jax.block_until_ready(metrics)
        self.t0, self.step0 = time.perf_counter(), step
        self.setup_s = self.t0 - self._clock_start
        self.compiles.armed = True
        if self.trace_dir:
          # Device tracing only. With the host tracer at level 1 or 2
          # (or the Python tracer) this loop's host side grew by a
          # quarter of a GB a second until the machine's 40 GiB were
          # gone and no dispatch finished (my chip runs, PR 23).
          options = jax.profiler.ProfileOptions()
          options.python_tracer_level = 0
          options.host_tracer_level = 0
          t_trace = time.perf_counter()  # the recording's time zero
          jax.profiler.start_trace(self.trace_dir,
                                   profiler_options=options)
          self._tracing_until = self._dispatches + self._trace_n
          self.trace_span = [t_trace, None]
      return
    if self._tracing_until is not None \
        and self._dispatches >= self._tracing_until:
      jax.block_until_ready(metrics)
      self.trace_span[1] = time.perf_counter()
      jax.profiler.stop_trace()
      self._tracing_until = None
    if (now >= self.t0 + self._seconds and self._tracing_until is None
        and (step - self.step0) % self._period == 0):
      jax.block_until_ready(metrics)
      self.t1, self.step1 = time.perf_counter(), step
      self.compiles.armed = False
      raise WindowClosed()

  def after_checkpoint(self, step: int, state, model_dir: str) -> None:
    now = time.perf_counter()
    if self.first_state is None:
      if step != self.first_step:
        raise RuntimeError(
            f"first checkpoint at step {step}, first dispatch ended at "
            f"{self.first_step}: the resume step is not aligned")
      self.first_state = jax.device_get(state)
    elif self.t0 is not None and step == self._last_step:
      # `_last_after_step` is still this step's `after_step`.
      self.checkpoint_stalls_ms.append(
          (now - self._last_after_step) * 1e3)
      if self._tracing_until is not None:
        self.host_spans.append((f"{self._loop_name}: checkpoint",
                                self._last_after_step, now))
      self._last_after_step = now

  def end(self, step: int, state, model_dir: str) -> None:
    if self._tracing_until is not None:  # loop died inside the trace
      jax.profiler.stop_trace()
      self._tracing_until = None


class KeepFirst:
  """Iterator over a batch stream that keeps the first `keep` batches,
  each through `flatten` (batch -> nested dict of host arrays). A
  class, not a generator, so that the prefetcher can close it from
  another thread.

  A kept leaf that does not own its memory (a view of a buffer that the
  stream writes again, as a gather straight into a ring slot would
  yield) is copied; one that does is kept as it is, which costs
  nothing. The copies fall in the warm dispatches, outside the
  window."""

  def __init__(self, inner, kept: List[dict], keep: int,
               flatten: Callable[[Any], dict]):
    self._inner, self._kept, self._keep = iter(inner), kept, keep
    self._flatten = flatten

  def __iter__(self):
    return self

  def __next__(self):
    batch = next(self._inner)
    if len(self._kept) < self._keep:
      self._kept.append(
          jax.tree_util.tree_map(_owned, self._flatten(batch)))
    return batch

  def close(self) -> None:
    closer = getattr(self._inner, "close", None)
    if callable(closer):
      closer()


def _owned(leaf):
  leaf = np.asarray(leaf)
  if leaf.base is not None or not leaf.flags.owndata:
    return np.array(leaf)
  return leaf


def resume_step(save_every: int, k: int) -> int:
  """The loop resumes a run some ten thousand steps old, one dispatch
  short of a save: its first dispatch ends on a checkpoint step."""
  return save_every * -(-10000 // save_every) - k


def hook_for(*, loop_name: str, traffic: dict, seconds: float,
             period_steps: int, clock_start: float, work_dir: str,
             trace: bool) -> WindowHook:
  """The hook of one run of a train loop, from the traffic mix's warm
  and traced dispatches."""
  return WindowHook(
      traffic["warm_dispatches"], seconds, period_steps,
      CompileCounter(), clock_start,
      os.path.join(work_dir, "trace") if trace else None,
      traffic["trace_dispatches"], loop_name)


@contextlib.contextmanager
def until_closed(hook: WindowHook, loop_name: str, marks: dict):
  """`with until_closed(hook, ...): <the program's train loop with
  hooks=[hook]>`: ends when the hook closes the window; `marks` gains
  the set-up split of the loop's start. A context manager and not a
  function that is handed the call: the driver calls the loop itself,
  so that no frame of the harness stands between the two. Every frame
  there is part of the location of every operation that the loop's
  first call traces and lowers; two of them took that call from 4.5 to
  7.6 s in `qtopt_64.train` (my chip runs, PR 26)."""
  from tensor2robot_tpu import telemetry

  cache0 = telemetry.registry().scalars("compile_cache.")
  t_loop = time.perf_counter()
  try:
    yield
  except WindowClosed:
    pass
  else:
    raise RuntimeError(
        f"{loop_name} returned before the window closed")
  cache1 = telemetry.registry().scalars("compile_cache.")
  marks["loop_start_to_first_dispatch_s"] = (
      hook.first_dispatch_done - t_loop)
  marks["first_dispatch_to_window_s"] = (
      hook.t0 - hook.first_dispatch_done)
  marks["compile_cache"] = {
      key: cache1.get(key, 0.0) - cache0.get(key, 0.0)
      for key in cache1}


def _window_records(model_dir: str, step0: int, step1: int):
  """The loop's own log records whose interval lies in the window."""
  path = os.path.join(model_dir, "metrics_train.jsonl")
  records = []
  if os.path.exists(path):
    with open(path) as f:
      for line in f:
        rec = json.loads(line)
        if step0 < rec["step"] <= step1:
          records.append({"step": rec["step"], **rec["payload"]})
  return records


def _stage_spans(hook: WindowHook) -> List[tuple]:
  """The program's own spans of the loop thread inside the traced
  window, as (name, start, end) on the host clock, which is the
  tracer's (PERF.md §5, "One clock"): what the loop was doing while the
  device waited, stage by stage. Empty where the loop records none."""
  from tensor2robot_tpu import telemetry

  if not hook.trace_span or hook.trace_span[1] is None:
    return []
  lo, hi = hook.trace_span
  return [(s["name"], s["ts"], s["ts"] + s["dur"])
          for s in telemetry.get_tracer().snapshot_spans()
          if s["tid"] == hook.loop_thread and s["dur"] > 0
          and s["ts"] < hi and s["ts"] + s["dur"] > lo]


def record(hook: WindowHook, *, kind: str, config: dict, devices,
           k: int, batch: int, seed32: int, resume_step: int,
           model_dir: str, trace_program: str, dispatch_span: str,
           marks: dict, check_inputs: dict) -> dict:
  """The run's record, as `run.py` and the per-layer readers take it
  (benchmark/README.md, "The driver contract")."""
  peak = max(d.memory_stats()["peak_bytes_in_use"] for d in devices) \
      if devices[0].platform != "cpu" else 0
  window_s = hook.t1 - hook.t0
  steps = hook.step1 - hook.step0
  return {
      "kind": kind,
      "config": config, "chips": len(devices), "k": k, "batch": batch,
      "seed32": seed32, "resume_step": resume_step,
      "window_s": window_s, "steps": steps,
      "attempted": steps // k, "failed": hook.compiles.count,
      "end_to_end": {"train_steps_per_s": steps / window_s,
                     "setup_s": hook.setup_s},
      "records": _window_records(model_dir, hook.step0, hook.step1),
      "checkpoint_stalls_ms": hook.checkpoint_stalls_ms,
      "trace_dir": hook.trace_dir, "trace_span": hook.trace_span,
      "host_spans": _stage_spans(hook) or hook.host_spans,
      "trace_program": trace_program,
      "dispatch_span": dispatch_span,
      "memory_peak_bytes": peak,
      "setup_split": marks,
      "check_inputs": {
          **check_inputs,
          "first_metrics": hook.first_metrics,
          "first_state": hook.first_state,
          "first_step": hook.first_step,
      },
  }
