"""The dispatch loop under the three trainers.

`train_qtopt`, `train_eval_model` and `train_anakin` build their own
state, feed and step program, and drive one `TrainLoop` each:

    loop = TrainLoop(model_dir, hooks, dispatch_span="qtopt.dispatch",
                     steps_per_dispatch=..., max_train_steps=..., ...)
    loop.begin(model, step, state=lambda: state,
               save_payload=train_loop.host_payload,
               hook_state=lambda st: st.train_state, ...)
    loop.attach_feed(prefetcher)            # Anakin has none
    with loop:                              # the one teardown
      for batch in loop.dispatches():
        with loop.dispatch():               # the trainer's span
          state, metrics = train_step(state, batch, ...)
        loop.after_dispatch(metrics)

The loop runs one dispatch ahead of its own bookkeeping: what follows
dispatch k (`after_step` hooks, the log with its wait for the device,
the save, `after_checkpoint` hooks) runs after dispatch k + 1 has been
enqueued, so the device has its next program queued when one ends and
the host's work lies in that program's shadow. Where a save is due at
k the loop copies the state on the device before the trainer hands it,
donated, to k + 1; the deferred save and hooks get that snapshot. A run
whose trainer does work between dispatches on the live state
(`boundary_work`) or whose hooks drive online collection (one more
dispatch in flight is K more steps of sampling lead) finishes every
dispatch before the next is enqueued.

A state that fills the chip has no room for its copy. The loop decides
from what it can observe, the bytes of the state it was given against
the device's `memory_stats()["bytes_limit"]` (`state_copy_fits`): state
and copy together may take half of the device's memory, the other half
being left to the step (gradients, activations, the feed's dispatches).
The half is a guess, not a measurement: the loop never sees the step
program (it stays in the trainer's frame, below), so it cannot ask what
that program's temporaries take. Two sizes have been run on a 16.9 GB
chip (PR 34): QT-Opt's states of megabytes, which copy, and a 7.5 GB
state beside 9.5 GB of temporaries, which cannot; a state of a quarter
to a half of the limit drains at every save where its copy might have
fitted, which costs time and never memory. Where state and copy would
take more, `begin` compiles and makes no copy, and a save
step finishes its own dispatch and saves the live state before the next
is enqueued; between saves the loop runs ahead as before
(`loop.dispatches.drained` counts the save steps' successors with the
first dispatch, the gauge `loop.state_copy_fits` and the `copied`
argument of `loop.snapshot` say which way the run decided).

The jitted call stays in the trainer's frame: a loop that took it as a
callback would stand in the location of every operation its first call
traces (PR 26: two such frames took that call from 4.5 to 7.6 s and
doubled the peak of host memory). The live state stays there too, read
through the trainer's closure when a snapshot or the teardown needs it:
a reference kept here would hold every donated state until the dispatch
after it had returned.
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Any, Callable, Dict, Iterable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu import telemetry
from tensor2robot_tpu.data import prefetch as prefetch_lib
from tensor2robot_tpu.hooks import Hook, HookList
from tensor2robot_tpu.startup import compile_cache
from tensor2robot_tpu.startup import orchestrator
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


@jax.jit
def _copy_on_device(state):
  """A copy of every leaf in one program, queued behind the dispatch
  that made `state`; each copy has its leaf's sharding. `jnp.copy` and
  not the identity, whose outputs `jit` forwards from its inputs."""
  return jax.tree_util.tree_map(jnp.copy, state)


def _bytes_limit(devices) -> Optional[int]:
  """The smallest `bytes_limit` the runtime reports for `devices`; None
  where it reports none (a CPU)."""
  limits = [stats["bytes_limit"] for stats in
            (device.memory_stats() for device in devices)
            if stats and "bytes_limit" in stats]
  return min(limits) if limits else None


def _device_leaves(state) -> list:
  return [leaf for leaf in jax.tree_util.tree_leaves(state)
          if isinstance(leaf, jax.Array)]


def state_bytes(state) -> int:
  """The bytes one device holds of `state`."""
  return sum(leaf.addressable_shards[0].data.nbytes
             for leaf in _device_leaves(state))


def state_copy_fits(state) -> bool:
  """Whether a copy of `state` fits on its devices beside it: the
  bytes one device holds of the state, twice, against half of that
  device's `bytes_limit` (a guess at the step's own need: the module's
  docstring). True where the runtime reports no limit."""
  leaves = _device_leaves(state)
  if not leaves:
    return True
  limit = _bytes_limit(leaves[0].devices())
  if limit is None:
    return True
  return 2 * state_bytes(state) <= limit // 2


def _on_every_rank(holds: bool) -> bool:
  """Whether `holds` on every rank of a multi-process group: hooks
  differ by rank (the fleet's chief alone publishes), and the ranks
  have to run the same programs and the collective saves in one
  order."""
  if jax.process_count() == 1:
    return holds
  from jax.experimental import multihost_utils
  return bool(np.all(multihost_utils.process_allgather(
      np.asarray(holds))))


# The phases of a start whose seconds the first log record and the
# benchmark's readers take from their gauges; one that a run does not
# go through (a restore without a checkpoint) reads 0.
_PHASE_GAUGES = ("startup.init_state_s", "startup.restore_s",
                 "startup.join_s", "startup.begin_s")


class TrainLoop:
  """Wait → dispatch → hooks → log → save → teardown, once: the run's
  host-side services and their teardown order, the cadences, the stage
  spans (docs/OBSERVABILITY.md) and the common part of the log record.
  Hooks, log and save of a dispatch trail its enqueue by one dispatch
  (the module's docstring).

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
    # The run's time zero, and what jax had compiled before it: the
    # loop closes this account at its first log (`_close_startup`).
    orchestrator.restart_account()
    self._compiled_before = telemetry.registry().scalars("compile")
    with orchestrator.Phase("services") as services:
      self._t_entry = services.t0
      if telemetry.get_tracer().role is None:
        telemetry.configure("trainer")
      services.args["role"] = telemetry.current_role()
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
      # Places the persistent compile cache and taps its traffic into
      # the telemetry registry: a warm-path recompile lands in the
      # loop's log, and every trace, lowering and compile in the ring.
      compile_cache.configure_compilation_cache()
      # The always-on perf plane (ISSUE 15): resource watermarks
      # sampled per process, sentinel rules evaluated at log cadence,
      # and the live MFU gauges of the `PerfMeter` that `begin` builds.
      perf_lib.start_resource_sampler(
          sources=[profiling.device_memory_source()])
      self.watch_sentinel = (sentinel_lib.build_for_run(model_dir)
                             if self.chief else None)
      self.feed: Optional[prefetch_lib.TimedIterator] = None
      self._prefetcher = self._writer = None
      # (step, metrics, snapshot) of the dispatch enqueued last, until
      # the one after it is enqueued.
      self._pending: Optional[tuple] = None
      # From `dispatches`' start to the return of the first jitted
      # call; None before and after.
      self._first_dispatch: Optional[orchestrator.Phase] = None
      # An earlier run's account in this process is not this run's.
      registry = telemetry.registry()
      for name in {*registry.scalars("startup."), *_PHASE_GAUGES}:
        registry.gauge(name).set(0.0)

  def begin(self, model, step: int, *,
            flops_per_step: Optional[float], devices: int,
            state: Callable[[], Any],
            save_payload: Callable[[Any], tuple],
            hook_state: Callable[[Any], Any],
            own_scalars: Callable[[dict, int, float, float], str],
            hook_metrics: Callable[[Any], Any] = lambda metrics: metrics,
            tag_step: Optional[Callable[[int], None]] = None,
            boundary_work: Optional[Callable[[int], None]] = None
            ) -> None:
    """Checks that the resume `step` lies on a dispatch boundary
    (before any hook begins), opens the writer and begins the hooks; a
    failure closes what the run had opened.

    The trainer's own: `state()` is the state as the trainer holds it
    when called, which the next dispatch donates; of that state or of
    the loop's snapshot of it after a save step,
    `save_payload(state)` is what `CheckpointWriter.save` takes
    (`host_payload` itself where the save gathers to the host: the
    loop then starts a snapshot's copy to the host as it takes it) and
    `hook_state(state)` what the hooks see, as `hook_metrics(metrics)`
    is of a dispatch's metrics; `own_scalars(scalars, steps, dt,
    stall_secs)` adds its scalars to a log record and returns the key
    of its rate (`stall_secs`: what saves and boundary work took of the
    `dt` seconds since the last record, less, in a run ahead, what the
    loop waited for the device anyway); `tag_step(step)` runs on the
    chief before the first dispatch and after each enqueue;
    `boundary_work(step)` after each dispatch's save, on the live
    state: a trainer that passes it gives up the run ahead.
    `flops_per_step` (of one GLOBAL step) and `devices` are the
    `PerfMeter`'s."""
    self.step = step
    self._state, self._save_payload = state, save_payload
    self._hook_state = hook_state
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
      with orchestrator.Phase("begin") as begin:
        with orchestrator.Phase("open_writer"):
          self._last_saved = ckpt_lib.latest_step(self.model_dir)
          self._writer = ckpt_lib.CheckpointWriter(
              self.model_dir, max_to_keep=self._max_to_keep)
        self._meter = perf_lib.PerfMeter(
            flops_per_step=flops_per_step,
            peak_flops=profiling.device_peak_flops(), devices=devices)
        # Whether this run keeps a second dispatch in flight (the
        # module's docstring); the trainer sizes its feed's queue by it.
        self.runs_ahead = _on_every_rank(
            boundary_work is None
            and not self.hook_list.drives_online_collection)
        # Whether a save step's snapshot is a copy on the device or the
        # live state of a dispatch that the loop finishes first.
        self.copies_state = self.runs_ahead and _on_every_rank(
            state_copy_fits(self._state()))
        begin.args["copies_state"] = self.copies_state
        telemetry.registry().gauge("loop.state_copy_fits").set(
            float(self.copies_state))
        if self.copies_state:
          # The snapshot's program compiles here, with the run's
          # others: at the first save it would read as a warm-path
          # recompile.
          with orchestrator.Phase("snapshot_program"):
            _copy_on_device(self._state())
        with orchestrator.Phase("hooks_begin"):
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
    to `max_train_steps`, then what is left of the last dispatch and
    the final save if the loop ended off the save interval."""
    self._first_dispatch = orchestrator.Phase("first_dispatch",
                                              step=self.step)
    self._first_dispatch.__enter__()  # `after_dispatch` ends it
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
    self._finish_pending()
    if self._last_saved != self.step:
      # Nothing donates the live state any more: it is its own snapshot.
      self._save(self.step, self._state())

  def dispatch(self):
    """The span of the enqueue, for the `with` around the jitted call."""
    args = {"step": self.step, "k": self.k}
    if self.feed is not None:
      args["seq"] = self.feed.seq
    return self._meter.dispatch(self._dispatch_span, **args)

  def after_dispatch(self, metrics) -> None:
    """Step tag and, on a save step, the snapshot; then `after_step`,
    log (with its sync), save and boundary work, in this order, of the
    dispatch BEFORE this one, and of this one when the next has been
    enqueued (or the loop ends). Hooks get the un-synced device
    metrics. A run that does not run ahead (`begin`) finishes this
    dispatch here."""
    if self._first_dispatch is not None:
      self._first_enqueue()
    self.step += self.k
    step = self.step
    if self._tag_step is not None:
      self._tag_step(step)  # one int store; actors tag adds with it
    save_due = self._due(step, self._save_every)
    snapshot = self._snapshot(step) if save_due else None
    # Enqueued with the after-work of the one before still owed, or
    # with nothing owed: the two add up to the dispatches.
    telemetry.registry().counter(
        "loop.dispatches.drained" if self._pending is None
        else "loop.dispatches.ran_ahead").inc()
    self._finish_pending()
    self._pending = (step, metrics, snapshot)
    if not self.runs_ahead or (save_due and not self.copies_state):
      # The snapshot is the live state: saved before it is donated.
      self._finish_pending()

  def _first_enqueue(self) -> None:
    """The first jitted call has returned: the start's last phase
    ends, and what the start has no name for is known."""
    phase, self._first_dispatch = self._first_dispatch, None
    phase.__exit__(None, None, None)
    telemetry.event("startup.first_enqueue", step=self.step)
    to_first_enqueue = phase.t0 + phase.seconds - self._t_entry
    registry = telemetry.registry()
    registry.gauge("startup.to_first_enqueue_s").set(to_first_enqueue)
    registry.gauge("startup.unnamed_s").set(
        to_first_enqueue - orchestrator.top_level_seconds())

  def _close_startup(self, step: int) -> None:
    """The program's account of its own start, once, when the first
    dispatch's results are on the host: gauges `startup.*` beside the
    phases' own (docs/OBSERVABILITY.md, "Start-up"). What jax traced,
    lowered and compiled is counted from the loop's construction to
    here, so what a caller compiled before and what anyone compiles
    after is not in it."""
    t_metrics = time.monotonic()
    telemetry.event("startup.first_metrics", step=step)
    registry = telemetry.registry()
    now = registry.scalars("compile")
    before, self._compiled_before = self._compiled_before, None
    since = lambda key: now.get(key, 0.0) - before.get(key, 0.0)  # noqa: E731
    for name, value in (
        ("to_first_metrics_s", t_metrics - self._t_entry),
        ("jit_s", since("compile.trace_s") + since("compile.lower_s")
         + since("compile.backend_s")),
        ("programs", since("compile_cache.backend_compiles")),
        ("cache_hits", since("compile_cache.hits")),
        ("cache_misses", since("compile_cache.misses"))):
      registry.gauge(f"startup.{name}").set(value)

  def write(self, tag: str, step: int, scalars: Dict[str, Any]) -> None:
    """A record of the trainer's own (eval metrics), on the chief."""
    if self.metric_logger is not None:
      self.metric_logger.write(tag, step, scalars)

  def _due(self, step: int, every: int) -> bool:
    return step % every == 0 or step == self.max_train_steps

  def _snapshot(self, step: int):
    """The state after `step` as the deferred save will find it: a
    copy on the device, since the next dispatch donates the live one
    (the copy to the host starts here where the save gathers there);
    the live state itself in a run that finishes each dispatch, or this
    one, before the next (`copies_state`)."""
    with telemetry.span("loop.snapshot", step=step,
                        copied=self.copies_state):
      if not self.copies_state:
        return self._state()
      snapshot = _copy_on_device(self._state())
      if self._save_payload is host_payload:
        for leaf in jax.tree_util.tree_leaves(snapshot):
          leaf.copy_to_host_async()
      return snapshot

  def _finish_pending(self) -> None:
    """The after-work of the dispatch enqueued last, if it is still
    owed. An exception out of it (a hook's) leaves the rest undone."""
    if self._pending is None:
      return
    (step, metrics, snapshot), self._pending = self._pending, None
    self._steps_since_log += self.k
    with telemetry.span("loop.after_step", step=step):
      self.hook_list.after_step(step, self._hook_metrics(metrics))
    if self.chief and self._due(step, self._log_every):
      self._log(step, metrics)
    if snapshot is not None:
      self._save(step, snapshot)
      # Letting go of its arrays gives up the interpreter lock leaf by
      # leaf while the writer's thread is busy taking it: a stage of
      # its own, or the time would stand between two spans.
      with telemetry.span("loop.snapshot_free", step=step):
        del snapshot
    if self._boundary_work is not None:
      t0 = time.perf_counter()
      self._boundary_work(step)
      self._stall_secs += time.perf_counter() - t0

  def _log(self, step: int, metrics) -> None:
    registry = telemetry.registry()
    with telemetry.span("loop.log", step=step):
      # The one place the loop waits for the device: the dispatch has
      # to finish before its metrics exist. In a run ahead the next one
      # is queued behind it, and this wait is the host's slack.
      with telemetry.span("loop.log_sync", step=step):
        t0 = time.perf_counter()
        scalars = jax.device_get(metrics)
        waited = time.perf_counter() - t0
      first = self._compiled_before is not None
      if first:
        self._close_startup(step)
      dt = time.time() - self._t_last
      # A run ahead saves beside the next program: a save held the
      # loop only by what this wait for the device did not cover.
      stall_secs = (max(self._stall_secs - waited, 0.0)
                    if self.runs_ahead else self._stall_secs)
      rate_key = self._own_scalars(scalars, self._steps_since_log, dt,
                                   stall_secs)
      if self.feed is not None:
        scalars["input_wait_fraction"] = self.feed.wait_fraction(dt)
      # A compile-cache miss delta after the first interval is a
      # warm-path recompile; the resource watermarks persist with the
      # run (the registry alone dies with the process).
      scalars.update(registry.scalars("compile_cache."))
      scalars.update(registry.scalars("rsrc."))
      if first:  # this record alone holds the start's account
        scalars.update(registry.scalars("startup."))
      registry.gauge(f"train.{rate_key}").set(scalars[rate_key])
      scalars.update(self._meter.publish(scalars[rate_key]))
      self.metric_logger.write("train", step, scalars)
      if self.watch_sentinel is not None:
        self.watch_sentinel.evaluate(
            {**registry.scalars(), **scalars}, step=step)
      self._t_last = time.time()
      self._steps_since_log = 0
      self._stall_secs = 0.0

  def _save(self, step: int, state) -> None:
    t0 = time.perf_counter()
    with telemetry.span("loop.save", step=step):
      with telemetry.span("loop.save_d2h", step=step):
        payload = self._save_payload(state)
      with telemetry.span("loop.save_write", step=step):
        self._writer.save(step, *payload)
      with telemetry.span("loop.after_checkpoint", step=step):
        self.hook_list.after_checkpoint(step, self._hook_state(state),
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
      self.hook_list.end(self.step, self._hook_state(self._state()),
                         self.model_dir)
    except Exception:  # noqa: BLE001 — don't mask the original error
      log.exception("hook end() failed during teardown")
    self._wait_for_device()
    self.close()

  def _wait_for_device(self) -> None:
    """A loop that runs ahead and ends on an exception (a hook's, an
    interrupt) has a dispatch in flight: the device holds its state and
    the step's temporaries until it is done, long after the caller has
    the exception, and whoever then asks the device for memory races
    that program (PR 35: the benchmark's check, which places 2.5 GB
    beside 7.6 GB of state and 8.2 GB reserved for the step on a 16.9
    GB chip). The loop returns a device that is done."""
    try:
      jax.block_until_ready(self._state())
    except Exception:  # noqa: BLE001 — a state donated to a call that
      # raised is deleted; that call's error is the one to show.
      log.debug("live state not ready at teardown", exc_info=True)
