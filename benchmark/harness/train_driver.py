"""The `train` kind: the shipped `train_qtopt` loop, unedited, with the
benchmark's replay rows, the benchmark's weights (as the checkpoint the
loop resumes from) and the benchmark's hook (`harness/window.py`), which
opens and closes the measured window from inside the loop.

What is timed is therefore everything the loop does between two device
syncs: replay sampling, K-stacking, the prefetcher's H2D, the K-step
program, logging (with its own syncs) and checkpoints.

Here is what is QT-Opt's: the learner, the replay fill, the start
checkpoint, the call, and the reference's K Bellman steps that the
outputs check follows.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.harness import program

# The harness's other modules are imported inside the functions that
# use them, after the trainer's own import: imported before it (at this
# module's top), `setup_s` read 3 to 4 s more on the chip machine, most
# of it inside the trainer's import of orbax (PERF.md §6, PR 26: why is
# not known).


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


def run(config: dict, traffic: dict, *, seed: int,
        seconds: float, trace: bool, devices, clock_start: float,
        work_dir: str) -> dict:
  """One run of a train cell; returns the run's record
  (benchmark/README.md, "The driver contract")."""
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.research.qtopt.train_qtopt import train_qtopt
  from tensor2robot_tpu.startup import compile_cache
  from benchmark.harness import replay_fill, weights, window

  marks = {"import_trainer_s": time.perf_counter() - clock_start}
  compile_cache.configure_compilation_cache()
  train = config["train"]
  chips = len(devices)
  k = train["steps_per_dispatch"]
  batch = train["batch_size_per_chip"] * chips
  save_every = train["save_checkpoints_steps"]
  resume_step = window.resume_step(save_every, k)
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

  hook = window.hook_for(
      loop_name="train_qtopt", traffic=traffic, seconds=seconds,
      period_steps=save_every, clock_start=clock_start,
      work_dir=work_dir, trace=trace)
  with window.until_closed(hook, "train_qtopt", marks):
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
  return window.record(
      hook, kind="train", config=config, devices=devices, k=k,
      batch=batch, seed32=seed32, resume_step=resume_step,
      model_dir=model_dir, trace_program="jit_k_steps",
      dispatch_span="qtopt.dispatch", marks=marks,
      check_inputs={"params": host_params, "stats": host_stats,
                    "batches": buffer.kept})


# The controls that lower one part alone (`tools/read_limits.py` reads
# what the check catches of each).
PARTIAL_CONTROLS = ("critic_only", "tower_only")


def control_quant(control=True):
  """The control of the outputs check (`control` True): the reference
  one precision below the configuration's (int8 for the bf16 critic
  update, int4 for the int8 CEM tower); or, by name, one of
  `PARTIAL_CONTROLS`."""
  from benchmark.reference import qnet
  return {True: qnet.Quant(critic_bits=8, tower_bits=4),
          "critic_only": qnet.Quant(critic_bits=8),
          "tower_only": qnet.Quant(tower_bits=4)}[control]


def follow_reference(config: dict, inputs: dict, seed32: int,
                     quant=None):
  """The reference through the first dispatch: K Bellman steps on the
  K batches the loop's stream yielded first, from the benchmark's
  weights. `quant` (a `qnet.Quant`) lowers the precision: None is the
  reference itself. Returns (state after K steps, last step's
  metrics)."""
  from benchmark.harness import weights
  from benchmark.reference import qnet
  quant = quant or qnet.REFERENCE
  cfg = qnet.NetConfig.from_config(config)
  rows = config["reference"]["cem_rows_per_block"]
  step_fn = jax.jit(
      lambda state, batch, rng: qnet.bellman_step(
          cfg, state, batch, rng, quant, rows))
  with jax.default_matmul_precision("highest"):
    k = len(inputs["batches"])
    step0 = inputs["first_step"] - k
    state = qnet.init_state(
        {k: jnp.asarray(v) for k, v in inputs["params"].items()},
        {k: jnp.asarray(v) for k, v in inputs["stats"].items()},
        step0, weights.ADAM_NU0)
    # The loop keys step s with fold_in(PRNGKey(seed + 1), s).
    step_rng = jax.random.PRNGKey(seed32 + 1)
    metrics = None
    for i, batch in enumerate(inputs["batches"]):
      state, metrics = step_fn(
          state, {key: jnp.asarray(v) for key, v in batch.items()},
          jax.random.fold_in(step_rng, step0 + i))
    state = jax.device_get(state)
    metrics = {key: float(v) for key, v in metrics.items()}
  return state, metrics


def numbers(config: dict, run: dict, control=False) -> Dict[str, float]:
  """The numbers the check compares, for the program or for a control
  in its place: True is the full control, a name one of
  `PARTIAL_CONTROLS` (`check.numbers_of`)."""
  from benchmark.harness import check
  return check.numbers_of(
      lambda config, inputs, seed32, control: follow_reference(
          config, inputs, seed32,
          control_quant(control) if control else None),
      config, run, control)


def check(cell_name: str, config: dict, run: dict,
          limits: Dict[str, float], out=print) -> bool:
  from benchmark.harness import check
  return check.decide(numbers, config, run, limits, out)
