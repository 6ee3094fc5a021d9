"""The dispatch loop under the three trainers.

`train_qtopt`, `train_eval_model` and `train_anakin` build their own
state, feed and step program, and drive one `TrainLoop` each:

    loop = TrainLoop(model_dir, hooks, dispatch_span="qtopt.dispatch",
                     steps_per_dispatch=..., max_train_steps=..., ...)
    loop.begin(model, step, save_payload=lambda: (state,),
               hook_state=lambda: state, own_scalars=..., ...)
    loop.attach_feed(prefetcher)            # Anakin has none
    with loop:                              # the one teardown
      for batch in loop.dispatches():
        with loop.dispatch():               # the trainer's span
          state, metrics = train_step(state, batch, ...)
        loop.after_dispatch(metrics)

The jitted call stays in the trainer's frame: a loop that took it as a
callback would stand in the location of every operation its first call
traces (PR 26: two such frames took that call from 4.5 to 7.6 s and
doubled the peak of host memory). The state stays there too, read
through the trainer's closures: a reference kept here would hold every
donated state until the dispatch after it had returned.
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Any, Callable, Dict, Iterable, Iterator, Optional

import jax
import numpy as np

from tensor2robot_tpu import telemetry
from tensor2robot_tpu.data import prefetch as prefetch_lib
from tensor2robot_tpu.hooks import Hook, HookList
from tensor2robot_tpu.startup import compile_cache
from tensor2robot_tpu.telemetry import perf as perf_lib
from tensor2robot_tpu.telemetry import sentinel as sentinel_lib
from tensor2robot_tpu.utils import checkpoints as ckpt_lib
from tensor2robot_tpu.utils import profiling

log = logging.getLogger(__name__)


class MetricLogger:
  """Scalar metric sink: stdout + JSONL file per tag (train/eval).

  Every record is the unified telemetry envelope
  ``{"step", "wall", "role", "payload"}`` (telemetry.records — the
  ISSUE 11 schema every producer shares: this trainer, anakin, the
  fleet learner, the success-eval hooks). ``role`` defaults to the
  process's telemetry role; read back with
  `telemetry.records.read_records`, which also normalizes pre-envelope
  files.
  """

  def __init__(self, model_dir: str, role: Optional[str] = None):
    self._model_dir = model_dir
    self._role = role
    os.makedirs(model_dir, exist_ok=True)
    self._files: Dict[str, Any] = {}

  def write(self, tag: str, step: int, metrics: Dict[str, Any]) -> None:
    scalars = {k: float(np.asarray(v)) for k, v in metrics.items()}
    if tag not in self._files:
      self._files[tag] = open(
          os.path.join(self._model_dir, f"metrics_{tag}.jsonl"), "a")
    record = telemetry.records.make_record(step, scalars,
                                           role=self._role)
    self._files[tag].write(json.dumps(record) + "\n")
    self._files[tag].flush()
    rendered = ", ".join(f"{k}={v:.5g}" for k, v in scalars.items())
    log.info("[%s] step %d: %s", tag, step, rendered)

  def close(self) -> None:
    for f in self._files.values():
      f.close()
    self._files.clear()


def host_payload(state) -> tuple:
  """What a save writes of a state gathered to the host: the state,
  and its params and batch statistics as the inference payload."""
  host = jax.device_get(state)
  return host, host.train_state.params, host.train_state.batch_stats


class TrainLoop:
  """Wait → dispatch → hooks → log → save → teardown, once: the run's
  host-side services and their teardown order, the cadences, the stage
  spans (docs/OBSERVABILITY.md) and the common part of the log record.

  Construction validates the dispatch quantization BEFORE any side
  effect (a hook's begin() starts actor threads; a late ValueError
  would leak them). A process that has not configured the tracer gets
  the role `trainer` in memory mode: a bounded ring, nothing written,
  and a sentinel page's flight record holds the loop's last spans. A
  caller's configuration, `enabled=False` included, is left alone.

  Multi-process learner group (ISSUE 19): every rank runs the SAME
  jitted program (one GSPMD computation over the shared mesh, each
  rank feeding its local batch shard), but HOST-side effects — metric
  logs, sentinel pages, replay step-tags — belong to the chief alone.
  Rank > 0 would otherwise race the chief on the same model_dir files.
  Checkpoint saves are the one exception: orbax save/wait are
  COLLECTIVE (`sync_global_processes` barriers inside the writer), so
  every rank must make the calls — orbax's primary-host ownership
  still makes process 0 the only rank that writes checkpoint data.
  `after_checkpoint` runs on every rank too (rank > 0 carries no
  publish hook), to keep per-rank hook bookkeeping in step.
  Single-process runs are process 0.
  """

  def __init__(self, model_dir: str, hooks: Iterable[Hook], *,
               dispatch_span: str, steps_per_dispatch: int,
               max_train_steps: int, log_every_steps: int,
               save_checkpoints_steps: int,
               max_checkpoints_to_keep: int,
               role: Optional[str] = None,
               **other_cadences: Optional[int]):
    self.k = prefetch_lib.validate_steps_per_dispatch(
        steps_per_dispatch, log_every_steps=log_every_steps,
        save_checkpoints_steps=save_checkpoints_steps,
        max_train_steps=max_train_steps, **other_cadences)
    if telemetry.get_tracer().role is None:
      telemetry.configure("trainer")
    self.model_dir = model_dir
    self.max_train_steps = max_train_steps
    self._dispatch_span = dispatch_span
    self._log_every = log_every_steps
    self._save_every = save_checkpoints_steps
    self._max_to_keep = max_checkpoints_to_keep
    os.makedirs(model_dir, exist_ok=True)
    self.chief = jax.process_index() == 0
    self.metric_logger = (MetricLogger(model_dir, role)
                          if self.chief else None)
    self.hook_list = HookList(list(hooks))
    # Places the persistent compile cache and taps its traffic into the
    # telemetry registry: a warm-path recompile lands in the loop's log.
    compile_cache.configure_compilation_cache()
    # The always-on perf plane (ISSUE 15): resource watermarks sampled
    # per process, sentinel rules evaluated at log cadence, and the
    # live MFU gauges of the `PerfMeter` that `begin` builds.
    perf_lib.start_resource_sampler(
        sources=[profiling.device_memory_source()])
    self.watch_sentinel = (sentinel_lib.build_for_run(model_dir)
                           if self.chief else None)
    self.feed: Optional[prefetch_lib.TimedIterator] = None
    self._prefetcher = self._writer = None

  def begin(self, model, step: int, *,
            flops_per_step: Optional[float], devices: int,
            save_payload: Callable[[], tuple],
            hook_state: Callable[[], Any],
            own_scalars: Callable[[dict, int, float, float], str],
            hook_metrics: Callable[[Any], Any] = lambda metrics: metrics,
            tag_step: Optional[Callable[[int], None]] = None,
            boundary_work: Optional[Callable[[int], None]] = None
            ) -> None:
    """Checks that the resume `step` lies on a dispatch boundary
    (before any hook begins), opens the writer and begins the hooks; a
    failure closes what the run had opened.

    The trainer's own, each reading the state as the trainer holds it
    when called: `save_payload()` is what `CheckpointWriter.save` takes
    after the step; `hook_state()` and `hook_metrics(metrics)` are what
    the hooks see; `own_scalars(scalars, steps, dt, stall_secs)` adds
    its scalars to a log record and returns the key of its rate
    (`stall_secs`: what saves and boundary work took of the `dt`
    seconds since the last record); `tag_step(step)` runs on the chief
    before the first dispatch and after each; `boundary_work(step)`
    after each dispatch's save. `flops_per_step` (of one GLOBAL step)
    and `devices` are the `PerfMeter`'s."""
    self.step = step
    self._save_payload, self._hook_state = save_payload, hook_state
    self._own_scalars, self._hook_metrics = own_scalars, hook_metrics
    self._tag_step = tag_step if self.chief else None
    self._boundary_work = boundary_work
    try:
      if self.k > 1 and step % self.k and step < self.max_train_steps:
        raise ValueError(
            f"Resumed at step {step}, not a multiple of "
            f"steps_per_dispatch={self.k}: the checkpoint/log "
            "boundaries would never align. Resume with K=1 (or a K "
            "dividing the resume step) first.")
      self._last_saved = ckpt_lib.latest_step(self.model_dir)
      self._writer = ckpt_lib.CheckpointWriter(
          self.model_dir, max_to_keep=self._max_to_keep)
      self._meter = perf_lib.PerfMeter(
          flops_per_step=flops_per_step,
          peak_flops=profiling.device_peak_flops(), devices=devices)
      self.hook_list.begin(model, self.model_dir)
    except BaseException:
      self.close()
      raise

  def attach_feed(self, prefetcher) -> None:
    """The prefetcher whose items `dispatches` yields (each wait a
    `loop.wait_feed` span) and the teardown closes, consumed or not."""
    self._prefetcher = prefetcher
    self.feed = prefetch_lib.TimedIterator(prefetcher)

  def dispatches(self) -> Iterator[Any]:
    """One item of the feed (None without one) for every dispatch up
    to `max_train_steps`, then the final save if the loop ended off
    the save interval."""
    if self._tag_step is not None:
      # The data plane tags rows with the learner step at add time;
      # seed the tag before actors race the first dispatch. Chief-only:
      # on the sharded plane the tag is an RPC fan-out to every shard,
      # and N ranks tagging the same step would N-plicate it.
      self._tag_step(self.step)
    self._t_last = time.time()
    self._steps_since_log = 0
    self._stall_secs = 0.0
    if self.feed is None:
      while self.step < self.max_train_steps:
        yield None
    else:
      for item in self.feed:
        if self.step >= self.max_train_steps:
          break
        yield item
    if self._last_saved != self.step:
      self._save()

  def dispatch(self):
    """The span of the enqueue, for the `with` around the jitted call."""
    args = {"step": self.step, "k": self.k}
    if self.feed is not None:
      args["seq"] = self.feed.seq
    return self._meter.dispatch(self._dispatch_span, **args)

  def after_dispatch(self, metrics) -> None:
    """Step tag, `after_step`, log (with its sync), save, boundary
    work: in this order. Hooks get the un-synced device metrics."""
    self.step += self.k
    self._steps_since_log += self.k
    step = self.step
    if self._tag_step is not None:
      self._tag_step(step)  # one int store; actors tag adds with it
    with telemetry.span("loop.after_step", step=step):
      self.hook_list.after_step(step, self._hook_metrics(metrics))
    if self.chief and self._due(self._log_every):
      self._log(metrics)
    if self._due(self._save_every):
      self._save()
    if self._boundary_work is not None:
      t0 = time.perf_counter()
      self._boundary_work(step)
      self._stall_secs += time.perf_counter() - t0

  def write(self, tag: str, step: int, scalars: Dict[str, Any]) -> None:
    """A record of the trainer's own (eval metrics), on the chief."""
    if self.metric_logger is not None:
      self.metric_logger.write(tag, step, scalars)

  def _due(self, every: int) -> bool:
    return self.step % every == 0 or self.step == self.max_train_steps

  def _log(self, metrics) -> None:
    step = self.step
    registry = telemetry.registry()
    with telemetry.span("loop.log", step=step):
      # The one place the loop waits for the device: the dispatch
      # enqueued last has to finish before its metrics exist.
      with telemetry.span("loop.log_sync", step=step):
        scalars = jax.device_get(metrics)
      dt = time.time() - self._t_last
      rate_key = self._own_scalars(scalars, self._steps_since_log, dt,
                                   self._stall_secs)
      if self.feed is not None:
        scalars["input_wait_fraction"] = self.feed.wait_fraction(dt)
      # A compile-cache miss delta after the first interval is a
      # warm-path recompile; the resource watermarks persist with the
      # run (the registry alone dies with the process).
      scalars.update(registry.scalars("compile_cache."))
      scalars.update(registry.scalars("rsrc."))
      registry.gauge(f"train.{rate_key}").set(scalars[rate_key])
      scalars.update(self._meter.publish(scalars[rate_key]))
      self.metric_logger.write("train", step, scalars)
      if self.watch_sentinel is not None:
        self.watch_sentinel.evaluate(
            {**registry.scalars(), **scalars}, step=step)
      self._t_last = time.time()
      self._steps_since_log = 0
      self._stall_secs = 0.0

  def _save(self) -> None:
    step = self.step
    t0 = time.perf_counter()
    with telemetry.span("loop.save", step=step):
      with telemetry.span("loop.save_d2h", step=step):
        payload = self._save_payload()
      with telemetry.span("loop.save_write", step=step):
        self._writer.save(step, *payload)
      with telemetry.span("loop.after_checkpoint", step=step):
        self.hook_list.after_checkpoint(step, self._hook_state(),
                                        self.model_dir)
    self._last_saved = step
    self._stall_secs += time.perf_counter() - t0

  def close(self) -> None:
    """What a run that never got to `with loop:` still has to close;
    the hooks' `end` is `__exit__`'s."""
    for service in (self._prefetcher, self._writer, self.watch_sentinel,
                    self.metric_logger):
      if service is not None:
        service.close()

  def __enter__(self) -> "TrainLoop":
    return self

  def __exit__(self, *exc_info) -> None:
    # end() in the teardown: hooks own real teardown (actor threads);
    # a training-loop exception must not leak collectors.
    try:
      self.hook_list.end(self.step, self._hook_state(), self.model_dir)
    except Exception:  # noqa: BLE001 — don't mask the original error
      log.exception("hook end() failed during teardown")
    self.close()
