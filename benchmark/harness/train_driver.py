"""Drives a train cell: the shipped `train_qtopt` loop, unedited, with
the benchmark's replay rows, the benchmark's weights (as the checkpoint
the loop resumes from) and one benchmark hook that opens and closes the
measured window from inside the loop.

What is timed is therefore everything the loop does between two device
syncs: replay sampling, K-stacking, the prefetcher's H2D, the K-step
program, logging (with its own syncs) and checkpoints.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

import jax

from benchmark.harness import program, replay_fill, weights
from tensor2robot_tpu.hooks import Hook


class WindowClosed(Exception):
  """Raised by the hook to end `train_qtopt` once the window is shut."""


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
               trace_dispatches: int = 0):
    self._warm, self._seconds = warm, seconds
    self._period = period_steps
    self._compiles = compiles
    self._clock_start = clock_start
    self._trace_dir, self._trace_n = trace_dir, trace_dispatches
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
    # what the loop was doing, for the trace's idle gaps.
    self.host_spans: List[tuple] = []
    self._last_after_step: Optional[float] = None
    self.first_dispatch_done: Optional[float] = None

  def after_step(self, step: int, metrics: dict) -> None:
    self._dispatches += 1
    now = time.perf_counter()
    if self._tracing_until is not None and self._last_after_step:
      self.host_spans.append(
          ("train_qtopt: wait for the feed, dispatch, log",
           self._last_after_step, now))
    self._last_step, self._last_after_step = step, now
    if self._dispatches == 1:
      self.first_metrics = {k: float(v) for k, v in
                            jax.device_get(metrics).items()}
      self.first_step = step
      self.first_dispatch_done = time.perf_counter()
    if self.t0 is None:
      if self._dispatches >= self._warm:
        jax.block_until_ready(metrics)
        self.t0, self.step0 = time.perf_counter(), step
        self.setup_s = self.t0 - self._clock_start
        self._compiles.armed = True
        if self._trace_dir:
          # Device tracing only. With the host tracer at level 1 or 2
          # (or the Python tracer) this loop's host side grew by a
          # quarter of a GB a second until the machine's 40 GiB were
          # gone and no dispatch finished (my chip runs, PR 23).
          options = jax.profiler.ProfileOptions()
          options.python_tracer_level = 0
          options.host_tracer_level = 0
          t_trace = time.perf_counter()  # the recording's time zero
          jax.profiler.start_trace(self._trace_dir,
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
      self._compiles.armed = False
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
        self.host_spans.append(("train_qtopt: checkpoint",
                                self._last_after_step, now))
      self._last_after_step = now

  def end(self, step: int, state, model_dir: str) -> None:
    if self._tracing_until is not None:  # loop died inside the trace
      jax.profiler.stop_trace()
      self._tracing_until = None


def _write_start_checkpoint(learner, params, stats, step: int,
                            model_dir: str, scales: dict) -> None:
  """The run the loop resumes: the benchmark's weights at `step`, and
  beside them the activation scales a first start would have stored."""
  from tensor2robot_tpu.research.qtopt import train_qtopt as tq
  from tensor2robot_tpu.utils import checkpoints as ckpt_lib

  state = jax.device_get(program.seeded_state(learner, params, stats,
                                              step))
  writer = ckpt_lib.CheckpointWriter(model_dir, max_to_keep=2)
  writer.save(step, state, params=state.train_state.params,
              batch_stats=state.train_state.batch_stats)
  writer.close()
  if scales:  # a rehearsal leaves the calibration to the loop
    with open(os.path.join(model_dir, tq.ACT_SCALES_FILE), "w") as f:
      json.dump(scales, f)


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


def run(config: dict, traffic: dict, *, seed: int,
        seconds: float, trace: bool, devices, clock_start: float,
        work_dir: str) -> dict:
  """One run of a train cell; returns the run's record (see run.py)."""
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.research.qtopt.train_qtopt import train_qtopt
  from tensor2robot_tpu.startup import compile_cache

  marks = {"import_trainer_s": time.perf_counter() - clock_start}
  compile_cache.configure_compilation_cache()
  train = config["train"]
  chips = len(devices)
  k = train["steps_per_dispatch"]
  batch = train["batch_size_per_chip"] * chips
  save_every = train["save_checkpoints_steps"]
  # The loop resumes a run some ten thousand steps old, one dispatch
  # short of a save: its first dispatch ends on a checkpoint step.
  resume_step = save_every * -(-10000 // save_every) - k
  seed32 = seed % (2 ** 31 - 1)

  learner = program.build_learner(config)
  marks["build_learner_s"] = time.perf_counter() - clock_start
  t = time.perf_counter()
  buffer = replay_fill.RecordingReplay(
      learner.transition_specification(),
      capacity=train["replay_rows"], seed=seed32, keep=k)
  replay_fill.fill(buffer, train["replay_rows"], seed32,
                   train["replay_fill_block_rows"])
  marks["fill_replay_s"] = time.perf_counter() - t

  t = time.perf_counter()
  params, stats = weights.make_weights(seed, config["model"])
  model_dir = os.path.join(work_dir, "model")
  os.makedirs(model_dir)
  _write_start_checkpoint(learner, params, stats, resume_step,
                          model_dir, config["int8_act_scales"])
  host_params = jax.device_get(params)
  host_stats = jax.device_get(stats)
  del params, stats
  marks["weights_and_checkpoint_s"] = time.perf_counter() - t

  compiles = CompileCounter()
  trace_dir = os.path.join(work_dir, "trace") if trace else None
  hook = WindowHook(traffic["warm_dispatches"], seconds, save_every,
                    compiles, clock_start, trace_dir,
                    traffic["trace_dispatches"])
  from tensor2robot_tpu import telemetry
  cache0 = telemetry.registry().scalars("compile_cache.")
  t_loop = time.perf_counter()
  try:
    train_qtopt(
        learner=learner, model_dir=model_dir, replay_buffer=buffer,
        # Far beyond any window; the hook ends the loop.
        max_train_steps=resume_step + k * 10 ** 7,
        batch_size=batch, save_checkpoints_steps=save_every,
        max_checkpoints_to_keep=train["max_checkpoints_to_keep"],
        log_every_steps=train["log_every_steps"],
        mesh=mesh_lib.create_mesh(devices=devices), hooks=[hook],
        seed=seed32, prefill_random=False, steps_per_dispatch=k,
        shard_weight_update=train["shard_weight_update"])
  except WindowClosed:
    pass
  else:
    raise RuntimeError("train_qtopt returned before the window closed")
  cache1 = telemetry.registry().scalars("compile_cache.")
  marks["loop_start_to_first_dispatch_s"] = (
      hook.first_dispatch_done - t_loop)
  marks["first_dispatch_to_window_s"] = (
      hook.t0 - hook.first_dispatch_done)
  marks["compile_cache"] = {
      key: cache1.get(key, 0.0) - cache0.get(key, 0.0)
      for key in cache1}
  peak = max(d.memory_stats()["peak_bytes_in_use"] for d in devices) \
      if devices[0].platform != "cpu" else 0
  records = _window_records(model_dir, hook.step0, hook.step1)
  window_s = hook.t1 - hook.t0
  steps = hook.step1 - hook.step0
  return {
      "kind": "train",
      "config": config, "chips": chips, "k": k, "batch": batch,
      "seed32": seed32, "resume_step": resume_step,
      "window_s": window_s, "steps": steps,
      "attempted": steps // k, "failed": compiles.count,
      "end_to_end": {"train_steps_per_s": steps / window_s,
                     "setup_s": hook.setup_s},
      "records": records,
      "checkpoint_stalls_ms": hook.checkpoint_stalls_ms,
      "trace_dir": trace_dir, "trace_span": hook.trace_span,
      "host_spans": hook.host_spans,
      "trace_program": "jit_k_steps",
      "memory_peak_bytes": peak,
      "setup_split": marks,
      "check_inputs": {
          "params": host_params, "stats": host_stats,
          "batches": buffer.kept,
          "first_metrics": hook.first_metrics,
          "first_state": hook.first_state,
          "first_step": hook.first_step,
      },
  }
