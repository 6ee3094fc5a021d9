"""The `train_eval_between_saves` kind: kind `train_eval`
(`harness/train_eval_driver.py`) for a job that saves less often than a
window lasts. Its window closes on the first dispatch after `--seconds`
that completes a whole LOG period since it opened, not a whole save
period: a job of a few seconds a step that saves every quarter of an
hour has no save in fifty seconds, and `train_eval`'s window, which
closes on whole save periods alone (`window.WindowHook`), would last a
thousand steps there (PERF.md §6, PR 34: killed at 1500 s).

Everything else is `train_eval`'s, the same calls in the same order:
the model from the configuration's gin file, the rows, the weights as
the checkpoint the loop resumes one dispatch short of a save (so the
warm dispatches hold the save that the check reads, and `setup_s` its
cost), the shipped `train_eval_model`, the check. `run` below is
`train_eval_driver.run` but for `period_steps`; it is written out a
second time because nothing that the benchmark has may change in the PR
that brings a cell (PERF.md §7 asks a `benchmark` PR to make the
closing period a key of the traffic mix and this file three lines).
"""

from __future__ import annotations

import os
import time

import jax

from benchmark.harness import follow, program, seeded_rows, window
from benchmark.harness.train_eval_driver import (  # noqa: F401
    _write_start_checkpoint,
    check,
    numbers,
)


def run(config: dict, traffic: dict, *, seed: int,
        seconds: float, trace: bool, devices, clock_start: float,
        work_dir: str) -> dict:
  """One run of a cell of this kind; returns the run's record
  (benchmark/README.md, "The driver contract")."""
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.startup import compile_cache
  from tensor2robot_tpu.train_eval import train_eval_model

  marks = {"import_trainer_s": time.perf_counter() - clock_start}
  compile_cache.configure_compilation_cache()
  train = config["train"]
  chips = len(devices)
  k = train["steps_per_dispatch"]
  batch = train["batch_size_per_chip"] * chips
  save_every = train["save_checkpoints_steps"]
  log_every = train["log_every_steps"]
  if k < 2:
    raise ValueError(f"{config['name']}: the kind times the K-step "
                     f"scan; steps_per_dispatch is {k}")
  resume_step = window.resume_step(save_every, k)
  seed32 = seed % (2 ** 31 - 1)

  model = program.build_model(config)
  marks["build_model_s"] = time.perf_counter() - clock_start
  t = time.perf_counter()
  maker = follow.module_of(config, "weights")
  params, stats = maker.make_weights(seed, config)
  model_dir = os.path.join(work_dir, "model")
  os.makedirs(model_dir)
  _write_start_checkpoint(model, params, stats, resume_step,
                          maker.ADAM_NU0, model_dir)
  host_params = jax.device_get(params)
  host_stats = jax.device_get(stats)
  del params, stats
  marks["weights_and_checkpoint_s"] = time.perf_counter() - t

  rows = seeded_rows.SeededRows(
      train["data_rows"], seed32, keep=k,
      int_below=train.get("int_below", {}), batch_size=batch)
  hook = window.hook_for(
      loop_name="train_eval_model", traffic=traffic, seconds=seconds,
      period_steps=log_every, clock_start=clock_start,
      work_dir=work_dir, trace=trace)
  with window.until_closed(hook, "train_eval_model", marks):
    train_eval_model(
        model=model, model_dir=model_dir,
        input_generator_train=rows, input_generator_eval=None,
        create_exporters_fn=None, eval_every_steps=None,
        # Far beyond any window; the hook ends the loop.
        max_train_steps=resume_step + k * 10 ** 7,
        batch_size=batch, save_checkpoints_steps=save_every,
        max_checkpoints_to_keep=train["max_checkpoints_to_keep"],
        log_every_steps=log_every,
        sharding_strategy=train["sharding_strategy"],
        mesh=mesh_lib.create_mesh(devices=devices), hooks=[hook],
        seed=seed32, steps_per_dispatch=k)
  return window.record(
      hook, kind="train_eval_between_saves", config=config,
      devices=devices, k=k, batch=batch, seed32=seed32,
      resume_step=resume_step, model_dir=model_dir,
      # `train_eval_model` jits its K-step scan as `k_steps`.
      trace_program="jit_k_steps", dispatch_span="train.dispatch",
      marks=marks,
      check_inputs={"params": host_params, "stats": host_stats,
                    "batches": rows.kept})
