"""The `train_eval` kind: the shipped `train_eval_model` loop, unedited,
for any T2R model that a configuration's gin file binds to it, with the
benchmark's rows (`harness/seeded_rows.py`), the benchmark's weights
(from the module the configuration names, as the checkpoint the loop
resumes from) and the benchmark's hook (`harness/window.py`).

What is timed is everything the loop does between two device syncs: the
generator's sampling, `stack_batches`, the prefetcher's H2D, the K-step
program, logging and checkpoints. No eval generator, no exporters.

A configuration of this kind states, beside what every configuration
states (`gin_file`, `gin_bindings`, `model`, `learner`, `precision`,
`train`): `"benchmark": {"weights": <module>, "reference": <module>}`,
module names under `benchmark/`. The weights module gives
`make_weights(seed, config) -> (params, stats)` as flat dicts by path
on the device and `ADAM_NU0`; the reference module gives `loss`
(`harness/follow.py`).
"""

from __future__ import annotations

import os
import time
from typing import Dict

import jax

from benchmark.harness import check as check_lib
from benchmark.harness import follow, program, seeded_rows, window


def _write_start_checkpoint(model, params, stats, step: int,
                            nu0: float, model_dir: str) -> None:
  """The run the loop resumes: the benchmark's weights at `step`, in
  the trainer's own checkpoint format."""
  from tensor2robot_tpu.utils import checkpoints as ckpt_lib

  state = jax.device_get(
      program.seeded_train_state(model, params, stats, step, nu0))
  writer = ckpt_lib.CheckpointWriter(model_dir, max_to_keep=2)
  writer.save(step, state)
  writer.close()


def run(config: dict, traffic: dict, *, seed: int,
        seconds: float, trace: bool, devices, clock_start: float,
        work_dir: str) -> dict:
  """One run of a train_eval cell; returns the run's record
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
      period_steps=save_every, clock_start=clock_start,
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
        log_every_steps=train["log_every_steps"],
        sharding_strategy=train["sharding_strategy"],
        mesh=mesh_lib.create_mesh(devices=devices), hooks=[hook],
        seed=seed32, steps_per_dispatch=k)
  return window.record(
      hook, kind="train_eval", config=config, devices=devices, k=k,
      batch=batch, seed32=seed32, resume_step=resume_step,
      model_dir=model_dir,
      # `train_eval_model` jits its K-step scan as `k_steps`.
      trace_program="jit_k_steps", dispatch_span="train.dispatch",
      marks=marks,
      check_inputs={"params": host_params, "stats": host_stats,
                    "batches": rows.kept})


def numbers(config: dict, run: dict,
            control: bool = False) -> Dict[str, float]:
  """The numbers the check compares, for the program or for the
  control in its place (`check.numbers_of`, `follow.follow`)."""
  return check_lib.numbers_of(follow.follow, config, run, control)


def check(cell_name: str, config: dict, run: dict,
          limits: Dict[str, float], out=print) -> bool:
  return check_lib.decide(numbers, config, run, limits, out)
