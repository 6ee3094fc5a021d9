"""Persistent XLA compilation cache wiring + compile observability.

jax's persistent compilation cache keys each backend compile on the
(HLO, compile options, backend version) fingerprint and stores the
serialized executable under `jax_compilation_cache_dir`; a process that
re-traces the same program skips XLA entirely and deserializes the
cached binary (the pjit/TPUv4 scaling work, arXiv:2204.06514, is what
makes frequent restarts affordable at pod scale). This module is the
ONE place the cache is placed — all three train loops, the predictor
and the serving engine call `configure_compilation_cache()`.

Placement contract (the directory is part of the cache key, so it must
not move between the processes that are meant to share it):

  * `JAX_COMPILATION_CACHE_DIR` set — jax reads it itself (it is the
    default of the `jax_compilation_cache_dir` flag). This module
    creates the directory and NEVER updates that flag, whatever gin or
    a caller passes.
  * unset — the cache lives at `DEFAULT_CACHE_DIR`, one fixed path
    inside the checkout resolved from the package location. An
    explicit `cache_dir=` may override only in this case (the tests'
    cold and warm child processes).

`CompileWatch` taps `jax.monitoring` for the cache's hit/miss events —
the proof obligation for every warm-start claim in this repo is
"`cache_misses == 0`", counted here, not inferred from wall clock.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Optional

import jax
from jax.experimental.compilation_cache import (
    compilation_cache as jax_compilation_cache,
)

from tensor2robot_tpu import config as gin
from tensor2robot_tpu.telemetry import core as tcore
from tensor2robot_tpu.telemetry import metrics as tmetrics

log = logging.getLogger(__name__)

# jax's own variable: the default of its `jax_compilation_cache_dir` flag.
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_BACKEND_COMPILE_DURATION = "/jax/core/compile/backend_compile_duration"
# What jax times of a jitted function on its way to an executable
# (`jax/_src/dispatch.py`), each with the function's name: event ->
# (span, counter of its seconds). jax holds the last one around
# `compile_or_get_cached`, so on a cache hit it is the read and the
# deserialisation.
_TRACE_DURATION = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_STAGES = {
    _TRACE_DURATION: ("jit.trace", "compile.trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("jit.lower", "compile.lower_s"),
    _BACKEND_COMPILE_DURATION: ("jit.compile", "compile.backend_s"),
}
# The persistent cache's own seconds (`jax/_src/compiler.py`): event ->
# counter. `saved_s` is what the compile took when the entry was
# written, less this read.
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "compile_cache.retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec":
        "compile_cache.saved_s",
}
COUNTERS = ("compile_cache.hits", "compile_cache.misses",
            "compile_cache.requests", "compile_cache.backend_compiles",
            *(seconds for _, seconds in _COMPILE_STAGES.values()),
            *_CACHE_SECONDS.values())


def aval_of(x):
  """ShapeDtypeStruct twin of a jax array, keeping its sharding.

  THE leaf helper for building AOT-lowering avals from live pytrees
  (trainer state, serving-engine state) — shared so the aval semantics
  cannot drift between the startup paths that compile ahead of time.
  Non-array leaves pass through untouched.
  """
  if isinstance(x, jax.Array):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
  return x


@gin.configurable
def configure_compilation_cache(cache_dir: Optional[str] = None) -> str:
  """Places jax's persistent compilation cache; returns its directory.

  Idempotent and safe to call from every entry point. Call order vs.
  jit does not matter — a directory change resets jax's
  once-per-process cache latch (`_reset_jax_cache_latch`). Every
  executable is persisted, however small or quick to compile: restart
  latency is the point, and a warm start is proven by ZERO misses.

  Args:
    cache_dir: explicit directory. Honoured only when
      `JAX_COMPILATION_CACHE_DIR` is unset (module docstring); it then
      stays in force for later no-arg calls in the process, so a
      library entry point (train loop, serving engine) never re-points
      a probe's or a test's cache.
  """
  # Every entry point that places the cache also gets the registry tap.
  CompileWatch.install_tap()
  env_dir = os.environ.get(ENV_CACHE_DIR)
  if env_dir:
    resolved = env_dir
    if cache_dir and os.path.abspath(cache_dir) != os.path.abspath(env_dir):
      log.info("%s=%s is set; ignoring cache_dir=%s", ENV_CACHE_DIR,
               env_dir, cache_dir)
  else:
    resolved = os.path.abspath(
        cache_dir or jax.config.jax_compilation_cache_dir
        or DEFAULT_CACHE_DIR)
    if jax.config.jax_compilation_cache_dir != resolved:
      jax.config.update("jax_compilation_cache_dir", resolved)
      _reset_jax_cache_latch()
      log.info("Persistent XLA compilation cache at %s", resolved)
  os.makedirs(resolved, exist_ok=True)
  jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  return resolved


def _reset_jax_cache_latch() -> None:
  """Clears jax's once-per-process cache-initialization latch.

  jax initializes the persistent cache lazily at the FIRST compile and
  never re-reads `jax_compilation_cache_dir` afterwards — so a single
  compile anywhere in the import chain (flax init, orbax, a spec
  helper) before this module runs would pin the process to whatever
  the flag said then. The reset makes configuration order-independent;
  already-compiled programs simply stay in the in-process jit cache.
  """
  jax_compilation_cache.reset_cache()


def reset_compilation_cache_config() -> None:
  """Forgets an explicit `cache_dir=` placement, so the next
  `configure_compilation_cache()` goes back to `DEFAULT_CACHE_DIR`
  (tests and probes restore isolation). A no-op under
  `JAX_COMPILATION_CACHE_DIR`, where no placement was ever made."""
  if os.environ.get(ENV_CACHE_DIR):
    return
  jax.config.update("jax_compilation_cache_dir", None)
  _reset_jax_cache_latch()


def cache_entry_count(cache_dir: str) -> int:
  """Number of persisted executables (one `-cache` file per program)."""
  if not os.path.isdir(cache_dir):
    return 0
  return sum(1 for name in os.listdir(cache_dir)
             if name.endswith("-cache"))


class CompileWatch:
  """Counts compilation-cache traffic via `jax.monitoring`.

  Usage::

      with CompileWatch() as watch:
        ...  # everything that might compile
      assert watch.cache_misses == 0   # the warm-path proof

  `cache_misses` counts compile requests the persistent cache could
  not serve — each one is a real XLA compilation (and a subsequent
  cache write). `cache_hits` counts executables deserialized from the
  cache instead of compiled. `backend_compiles` counts trips through
  jax's backend-compile path regardless of cache state (nonzero even
  on a fully warm start — retrieval runs inside it); the zero-compile
  claim is therefore ALWAYS `cache_misses == 0` with
  `cache_requests > 0`, never `backend_compiles == 0`.

  jax.monitoring offers no unregister, so the listeners stay installed
  for the process lifetime and count only while a watch is active
  (nested watches each observe the same events).
  """

  _lock = threading.Lock()
  _active: list = []
  _installed = False

  def __init__(self):
    self.cache_hits = 0
    self.cache_misses = 0
    self.cache_requests = 0
    self.backend_compiles = 0

  @classmethod
  def _install(cls) -> None:
    with cls._lock:
      if cls._installed:
        return
      import jax.monitoring as monitoring

      # Registry twin counters: once the listeners exist, EVERY cache
      # event lands in the telemetry registry whether or not a watch
      # is active — this is what closes the CompileWatch gap (ISSUE
      # 11): warm-path recompiles surface in ordinary training logs
      # (`compile_cache.misses` in metrics_<tag>.jsonl), not only
      # under an explicit watch. Names resolve PER EVENT (not
      # captured handles): a registry reset (test isolation) must not
      # orphan these counters for the rest of the process — compiles
      # are rare, the lookup is nothing.
      _event_names = {
          _CACHE_HIT_EVENT: "compile_cache.hits",
          _CACHE_MISS_EVENT: "compile_cache.misses",
          _CACHE_REQUEST_EVENT: "compile_cache.requests",
      }

      # Of the thread an event comes on: `cache`, what the cache
      # answered to the compile request it is inside of (the hit or
      # miss event comes before the end of the backend-compile stage
      # that holds it), and `tracing`, how many traces deep it is.
      here = threading.local()

      def on_event(event: str, **kwargs):
        name = _event_names.get(event)
        if name is not None:
          tmetrics.counter(name).inc()
        if event == _CACHE_HIT_EVENT:
          here.cache = "hit"
        elif event == _CACHE_MISS_EVENT:
          here.cache = "miss"
        with cls._lock:
          watches = list(cls._active)
        for watch in watches:
          watch._observe_event(event)

      def on_duration(event: str, duration: float, **kwargs):
        if event == _BACKEND_COMPILE_DURATION:
          tmetrics.counter("compile_cache.backend_compiles").inc()
        elif event in _CACHE_SECONDS:
          tmetrics.counter(_CACHE_SECONDS[event]).inc(duration)
        with cls._lock:
          watches = list(cls._active)
        for watch in watches:
          watch._observe_duration(event)

      def on_stage_start(event: str, value: float, **kwargs):
        if event == _TRACE_DURATION:
          here.tracing = getattr(here, "tracing", 0) + 1

      def on_time_span(event: str, start: float, end: float,
                       fun_name: str = "", **kwargs):
        # A span on the tracer's clock, under whatever span this
        # thread is in: jax's own stamps are `time.time()`, and the
        # event comes as the stage ends.
        stage = _COMPILE_STAGES.get(event)
        if stage is None:
          return
        if event == _TRACE_DURATION:
          # The trace of a jitted function called inside another's
          # (every `jnp` helper is one: ten to one in a QT-Opt run) is
          # part of that one's seconds, and no span of its own.
          here.tracing = max(getattr(here, "tracing", 0) - 1, 0)
          if here.tracing:
            return
        span, seconds = stage
        dur = end - start
        tmetrics.counter(seconds).inc(dur)
        args = {"fun": str(fun_name)}
        if event == _BACKEND_COMPILE_DURATION:
          args["cache"] = getattr(here, "cache", "off")
          here.cache = "off"  # until the next request's answer
        tcore.get_tracer().record(span, time.monotonic() - dur, dur,
                                  **args)

      monitoring.register_event_listener(on_event)
      monitoring.register_event_duration_secs_listener(on_duration)
      monitoring.register_scalar_listener(on_stage_start)
      monitoring.register_event_time_span_listener(on_time_span)
      cls._installed = True

  @classmethod
  def install_tap(cls) -> None:
    """Installs the jax.monitoring listeners WITHOUT opening a watch:
    the registry counters above start accumulating for the process
    lifetime. Trainers call this at entry so compile-cache traffic —
    especially warm-path recompiles — shows up in their logs. The
    counter names are touched on EVERY call (listener install is
    once-per-process) so the keys exist in the registry — at zero —
    even before the first cache event or after a registry reset."""
    for name in COUNTERS:
      tmetrics.counter(name)
    cls._install()

  def _observe_event(self, event: str) -> None:
    # Compiles can run on startup-overlap threads; counter updates
    # take the class lock so none are lost.
    with type(self)._lock:
      if event == _CACHE_HIT_EVENT:
        self.cache_hits += 1
      elif event == _CACHE_MISS_EVENT:
        self.cache_misses += 1
      elif event == _CACHE_REQUEST_EVENT:
        self.cache_requests += 1

  def _observe_duration(self, event: str) -> None:
    with type(self)._lock:
      if event == _BACKEND_COMPILE_DURATION:
        self.backend_compiles += 1

  def __enter__(self) -> "CompileWatch":
    type(self)._install()
    with type(self)._lock:
      type(self)._active.append(self)
    return self

  def __exit__(self, *exc) -> bool:
    with type(self)._lock:
      type(self)._active.remove(self)
    return False

  def counts(self) -> dict:
    return {
        "cache_hits": self.cache_hits,
        "cache_misses": self.cache_misses,
        "cache_requests": self.cache_requests,
        "backend_compiles": self.backend_compiles,
    }
