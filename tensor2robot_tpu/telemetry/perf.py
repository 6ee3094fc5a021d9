"""Live performance attribution + resource watermarks (ISSUE 15).

PR 11's plane answers "what happened"; this module answers "is the run
healthy and how fast should it be", always on:

  * `mfu_value` — THE one MFU formula. `utils.profiling.mfu` and
    the trainers' live gauges both call it, and the FLOPs
    denominator the trainers pass comes from the one
    `utils.profiling.analytic_flops` model, so every ``perf.mfu``
    of the repo is one formula over one model count (the
    shared-code-path pin in tests/test_perf_plane.py).
  * `PerfMeter` — per-process live attribution: at log cadence
    publishes the ``perf.mfu`` and ``perf.flops_per_sec`` gauges into
    the registry (so every ``metrics_<tag>.jsonl`` envelope and the
    Prometheus endpoint carry utilization for free). Device-count
    aware: the pod trainers pass their device count so MFU stays the
    per-chip fraction-of-peak at any scale.
  * `ResourceSampler` — a daemon sampler thread per process role:
    host RSS (``/proc/self/status``), optional device-memory sources
    (`utils.profiling.device_memory_source` — jax stays out of THIS
    package), and peak watermarks over selected registry fill gauges
    (replay ring, ingestion queue, arena residency), published as
    ``rsrc.*`` gauges with ``_peak`` watermark twins. Because they
    live in the ordinary registry they ride the fleet's existing
    ``telemetry_push`` RPC — the orchestrator's poll aggregates them
    fleet-wide with zero new transport.

The whole plane honors one switch: `set_plane_enabled(False)` (or env
``T2R_PERF_PLANE=0``) turns publication, sampling, and the sentinel
off — the off arm of an overhead comparison.

jax-free BY CONTRACT like the rest of the package (IMP401 worker-safe
set): actors run the sampler too; anything device-specific arrives as
an injected source callable.
"""

from __future__ import annotations

import atexit
import logging
import os
import threading
from typing import Callable, Dict, Iterable, Optional, Sequence

from tensor2robot_tpu.telemetry import core
from tensor2robot_tpu.telemetry import metrics as tmetrics

log = logging.getLogger(__name__)

# Registry gauges the sampler tracks peak watermarks for (fill/queue
# depths whose PEAK is the capacity-planning signal; the live values
# are already published at their event sites).
DEFAULT_WATCHED_GAUGES = (
    "replay.fill",
    "replay.ingest_queue_depth",
    "serving.arena.resident_bytes",
    "serving.microbatch_queue_depth",
)

_PLANE_ENV = "T2R_PERF_PLANE"
_plane_enabled: Optional[bool] = None
_plane_lock = threading.Lock()


def plane_enabled() -> bool:
  """Whether the always-on perf plane (live gauges, resource sampler,
  sentinel) is active in this process. Default on; ``T2R_PERF_PLANE=0``
  or `set_plane_enabled(False)` disables (an A/B's off arm)."""
  global _plane_enabled
  if _plane_enabled is None:
    _plane_enabled = os.environ.get(_PLANE_ENV, "1") not in (
        "0", "false", "off")
  return _plane_enabled


def set_plane_enabled(enabled: Optional[bool]) -> None:
  """Overrides the plane switch (None = re-read the environment)."""
  global _plane_enabled
  _plane_enabled = enabled


def mfu_value(steps_per_sec: float,
              flops_per_step: Optional[float],
              peak_flops: Optional[float],
              devices: int = 1) -> Optional[float]:
  """Model FLOPs utilization: achieved / (per-chip peak × devices).

  THE one MFU formula — `utils.profiling.mfu` (a caller's own rate) and
  `PerfMeter.publish` (the live gauges) both call it, so the two can
  never drift. None when the peak or the denominator is unknowable
  (e.g. XLA:CPU with no `T2R_PEAK_FLOPS_OVERRIDE`).
  """
  if not peak_flops or not flops_per_step:
    return None
  return steps_per_sec * flops_per_step / (peak_flops * max(devices, 1))


class PerfMeter:
  """Per-process live performance attribution (one per train loop).

  Usage (`train_loop.TrainLoop`, which the three trainers drive):

      meter = perf.PerfMeter(flops_per_step=..., peak_flops=...,
                             devices=D)
      ...
      with meter.dispatch("qtopt.dispatch", step=step):  # the span
        state, metrics = train_step(...)
      ...
      scalars.update(meter.publish(grad_steps_per_sec))

  ``flops_per_step`` is the analytic MODEL flops of one GLOBAL train
  step (`utils.profiling.analytic_flops`; pod trainers multiply their
  per-device count by D); ``devices`` scales the peak so ``perf.mfu``
  stays the per-chip fraction-of-peak.
  """

  def __init__(self,
               flops_per_step: Optional[float] = None,
               peak_flops: Optional[float] = None,
               devices: int = 1,
               registry: Optional[tmetrics.MetricsRegistry] = None,
               enabled: Optional[bool] = None):
    self.flops_per_step = flops_per_step
    self.peak_flops = peak_flops
    self.devices = max(int(devices), 1)
    self._registry = registry or tmetrics.registry()
    self.enabled = plane_enabled() if enabled is None else bool(enabled)

  def dispatch(self, name: str, **args):
    """The train loops' standard dispatch span. It closes when the
    ENQUEUE returns, not when the device is done (PERF.md): it times
    the host's side of a dispatch and is no measure of device time."""
    return core.span(name, **args)

  def publish(self, steps_per_sec: float) -> Dict[str, float]:
    """Publishes the interval's perf gauges; returns them as scalars
    for the trainer's `metrics_<tag>.jsonl` record (one call per log
    interval)."""
    if not self.enabled:
      return {}
    out: Dict[str, float] = {}
    if self.flops_per_step:
      out["perf.flops_per_sec"] = steps_per_sec * self.flops_per_step
    util = mfu_value(steps_per_sec, self.flops_per_step,
                     self.peak_flops, devices=self.devices)
    if util is not None:
      out["perf.mfu"] = util
    if "perf.flops_per_sec" in out:
      self._registry.gauge("perf.flops_per_sec").set(
          out["perf.flops_per_sec"])
    if "perf.mfu" in out:
      self._registry.gauge("perf.mfu").set(out["perf.mfu"])
    return out


def host_rss_source() -> Callable[[], Dict[str, float]]:
  """Resident-set-size source from ``/proc/self/status`` (jax-free,
  no psutil dependency; yields nothing on hosts without procfs)."""

  def sample() -> Dict[str, float]:
    try:
      with open("/proc/self/status") as f:
        for line in f:
          if line.startswith("VmRSS:"):
            kb = float(line.split()[1])
            return {"host_rss_bytes": kb * 1024.0}
    except (OSError, ValueError, IndexError):
      pass
    return {}

  return sample


class ResourceSampler:
  """Daemon sampler thread publishing ``rsrc.*`` gauges + watermarks.

  Every period it runs each source callable (dict name → value; a
  failing source is logged once and skipped, never raises out), sets
  ``rsrc.<name>`` and the peak watermark ``rsrc.<name>_peak``, and
  mirrors the peak of each watched registry gauge as
  ``rsrc.<gauge>_peak``. Lock-free on the hot paths it observes: it
  only READS registry gauges and sets its own (per-metric
  arithmetic-only locks — the CON301 contract).
  """

  def __init__(self,
               sources: Sequence[Callable[[], Dict[str, float]]] = (),
               watched_gauges: Iterable[str] = DEFAULT_WATCHED_GAUGES,
               period_secs: float = 1.0,
               registry: Optional[tmetrics.MetricsRegistry] = None):
    self._sources = list(sources) or [host_rss_source()]
    self._watched = tuple(watched_gauges)
    self._period = max(float(period_secs), 0.05)
    self._registry = registry or tmetrics.registry()
    self._peaks: Dict[str, float] = {}
    self._stop = threading.Event()
    self._thread: Optional[threading.Thread] = None
    self.samples = 0

  def _publish(self, name: str, value: float) -> None:
    self._registry.gauge(f"rsrc.{name}").set(value)
    peak = self._peaks.get(name)
    if peak is None or value > peak:
      self._peaks[name] = value
      self._registry.gauge(f"rsrc.{name}_peak").set(value)

  def sample_once(self) -> None:
    """One sampling pass (also the test seam)."""
    for source in self._sources:
      try:
        values = source()
      except Exception:  # noqa: BLE001 — sampling must never raise
        log.warning("resource source %r failed; skipping", source,
                    exc_info=True)
        continue
      for name, value in (values or {}).items():
        self._publish(str(name), float(value))
    if self._watched:
      gauges = self._registry.snapshot().get("gauges", {})
      for name in self._watched:
        if name in gauges:
          value = float(gauges[name])
          peak = self._peaks.get(name)
          if peak is None or value > peak:
            self._peaks[name] = value
            self._registry.gauge(f"rsrc.{name}_peak").set(value)
    self.samples += 1

  def _run(self) -> None:
    while not self._stop.is_set():
      try:
        self.sample_once()
      except Exception:  # noqa: BLE001 — the thread must outlive bugs
        log.warning("resource sampling pass failed", exc_info=True)
      self._stop.wait(self._period)

  def start(self) -> "ResourceSampler":
    if self._thread is None:
      self._thread = threading.Thread(
          target=self._run, name="t2r-rsrc-sampler", daemon=True)
      self._thread.start()
    return self

  def close(self, timeout_secs: float = 2.0) -> None:
    self._stop.set()
    thread, self._thread = self._thread, None
    if thread is not None:
      thread.join(timeout=timeout_secs)


_SAMPLER: Optional[ResourceSampler] = None


def start_resource_sampler(
    sources: Sequence[Callable[[], Dict[str, float]]] = (),
    period_secs: float = 1.0) -> Optional[ResourceSampler]:
  """Starts (or returns) the process-wide resource sampler. Idempotent
  per process — the first caller's sources win (one sampler per
  process role, the ISSUE-15 contract). No-op returning None while the
  plane is disabled."""
  global _SAMPLER
  if not plane_enabled():
    return None
  with _plane_lock:
    if _SAMPLER is None:
      _SAMPLER = ResourceSampler(
          sources=list(sources) + [host_rss_source()],
          period_secs=period_secs).start()
      # Joined at interpreter exit, BEFORE teardown: a device-memory
      # source mid-call into jax's C++ while the main thread tears
      # down XLA aborts the process ("terminate called without an
      # active exception" — found by the fleet learner, which exits
      # right after training). atexit runs with the interpreter still
      # whole, so the thread stops cleanly first.
      atexit.register(stop_resource_sampler)
  return _SAMPLER


def stop_resource_sampler() -> None:
  """Stops the process-wide sampler (tests / clean teardown)."""
  global _SAMPLER
  with _plane_lock:
    sampler, _SAMPLER = _SAMPLER, None
  if sampler is not None:
    sampler.close()
