"""Cold-start probes: time-to-first-step / time-to-first-prediction.

Each probe is ONE process lifetime — `bench.py --coldstart` launches
them as subprocesses so the in-process jit cache can never fake a warm
start; only the persistent compilation cache (and the orbax checkpoint)
survive between the cold and warm runs. A probe prints one
`COLDSTART_JSON {...}` marker line:

  * `time_to_first_*_secs` — wall from probe entry (imports done) to
    the first train step's metrics on host / the first prediction's
    outputs on host. Imports are excluded from the headline because
    they are identical cold and warm and unaddressable by caching;
    the parent records full subprocess wall alongside for honesty.
  * `compile_watch` — `CompileWatch` counts; a warm probe must report
    `cache_misses == 0` (every program deserialized, zero XLA
    compilations) — the proof the bench section pins.
  * trainer probes embed the trainer's own account of its start: the
    `startup.*` scalars of the run's first log record (per-phase
    compile/restore/input wall, the join, jax's own seconds, programs
    and cache hits; docs/OBSERVABILITY.md, "Start-up").

Probe topology (same for `--tiny`, just smaller nets):

  setup  — seeds a checkpoint (trainer: 2 train steps + save; serving:
           one params checkpoint), cache DISABLED, untimed.
  probe  — resumes/restores from that checkpoint with the given cache
           dir and reports the marker. Run it twice with the same
           cache dir: run 1 is the cold measurement (and populates the
           cache), run 2 is the warm one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

SETUP_STEPS = 2
PROBE_STEPS = 2  # the resumed run trains SETUP_STEPS → SETUP_STEPS+2


def _build_trainer_model(tiny: bool):
  if tiny:
    from tensor2robot_tpu.utils.mocks import MockT2RModel
    return MockT2RModel(), 8
  # The QT-Opt grasping critic with a deepened torso: a conv stack
  # whose XLA compile is the realistic multi-second cold-start cost
  # the cache is meant to erase. f32 device dtype: the probe must run
  # wherever the fleet restarts, including host CPU, where bf16 is
  # emulated so slowly that step-execution noise would swamp the
  # compile-time signal the cold/warm ratio measures; compile cost is
  # dtype-comparable. Batch 8 for the same reason — the measured
  # quantity is startup, not throughput.
  import jax.numpy as jnp

  from tensor2robot_tpu.research.qtopt.t2r_models import GraspingQModel
  return GraspingQModel(torso_filters=(64, 96, 96),
                        head_filters=(96, 96),
                        dense_sizes=(96, 96),
                        device_dtype=jnp.float32), 8


def _build_serving_model(tiny: bool):
  if tiny:
    from tensor2robot_tpu.utils.mocks import MockT2RModel
    return MockT2RModel()
  from tensor2robot_tpu.research.qtopt.t2r_models import GraspingQModel
  return GraspingQModel()


def trainer_setup(model_dir: str, tiny: bool) -> dict:
  """Seeds `model_dir` with a checkpoint at SETUP_STEPS (no cache)."""
  from tensor2robot_tpu import train_eval
  from tensor2robot_tpu.data import RandomInputGenerator

  model, batch_size = _build_trainer_model(tiny)
  train_eval.train_eval_model(
      model=model,
      model_dir=model_dir,
      input_generator_train=RandomInputGenerator(batch_size=batch_size,
                                                 seed=3),
      max_train_steps=SETUP_STEPS,
      save_checkpoints_steps=SETUP_STEPS,
      log_every_steps=SETUP_STEPS,
  )
  return {"setup": "ok", "steps": SETUP_STEPS}


def trainer_probe(model_dir: str, cache_dir: str, tiny: bool) -> dict:
  """Restart: resume from the seeded checkpoint, time the first step."""
  import jax
  import numpy as np

  from tensor2robot_tpu import train_eval
  from tensor2robot_tpu.data import RandomInputGenerator
  from tensor2robot_tpu.hooks import Hook
  from tensor2robot_tpu.startup import (
      CompileWatch,
      cache_entry_count,
      configure_compilation_cache,
  )
  from tensor2robot_tpu.telemetry import records

  configure_compilation_cache(cache_dir=cache_dir)
  t0 = time.perf_counter()

  class FirstStepTimer(Hook):
    ttfs = None

    def after_step(self, step, metrics):
      if self.ttfs is None:
        # D2H read of a metric: the step has genuinely finished.
        float(np.asarray(jax.device_get(
            next(iter(metrics.values())))))
        self.ttfs = time.perf_counter() - t0

  timer = FirstStepTimer()
  model, batch_size = _build_trainer_model(tiny)
  with CompileWatch() as watch:
    train_eval.train_eval_model(
        model=model,
        model_dir=model_dir,
        input_generator_train=RandomInputGenerator(batch_size=batch_size,
                                                   seed=3),
        max_train_steps=SETUP_STEPS + PROBE_STEPS,
        save_checkpoints_steps=SETUP_STEPS + PROBE_STEPS,
        log_every_steps=SETUP_STEPS + PROBE_STEPS,
        hooks=[timer],
    )
  # The probe's run is the last in the file to have written a first
  # record (the set-up run wrote one of its own).
  startup = None
  for record in records.read_records(
      os.path.join(model_dir, "metrics_train.jsonl")):
    if "startup.to_first_metrics_s" in record:
      startup = {key: value for key, value in record.items()
                 if key.startswith("startup.")}
  return {
      "probe": "trainer",
      "tiny": tiny,
      "device_kind": jax.devices()[0].device_kind,
      "time_to_first_step_secs": round(timer.ttfs, 3),
      "startup": startup,
      "compile_watch": watch.counts(),
      "cache_entries_after": cache_entry_count(cache_dir),
  }


def serving_setup(ckpt_dir: str, tiny: bool) -> dict:
  """Seeds one params checkpoint a predictor can restore (no cache)."""
  import jax

  from tensor2robot_tpu.utils import checkpoints as ckpt_lib

  model = _build_serving_model(tiny)
  state = model.create_inference_state(jax.random.PRNGKey(0))
  writer = ckpt_lib.CheckpointWriter(ckpt_dir, max_to_keep=None)
  writer.save(1, state)
  writer.close()
  return {"setup": "ok", "step": 1}


def serving_probe(ckpt_dir: str, cache_dir: str, tiny: bool) -> dict:
  """Restart: restore ∥ compile-ahead, then time the first prediction."""
  import jax
  import numpy as np

  from tensor2robot_tpu.predictors import CheckpointPredictor
  from tensor2robot_tpu.specs import make_random_tensors
  from tensor2robot_tpu.startup import (
      CompileWatch,
      cache_entry_count,
      configure_compilation_cache,
  )

  configure_compilation_cache(cache_dir=cache_dir)
  t0 = time.perf_counter()
  model = _build_serving_model(tiny)
  max_batch = 2 if tiny else 4
  with CompileWatch() as watch:
    predictor = CheckpointPredictor(
        model, checkpoint_dir=ckpt_dir, max_batch=max_batch,
        warmup=True, overlap_startup=True)
    restored = predictor.restore(timeout_secs=0)
    restore_done = time.perf_counter() - t0
    batch = make_random_tensors(
        predictor.feature_specification, batch_size=1, seed=0)
    outputs = predictor.predict(
        {k: np.asarray(v) for k, v in batch.to_flat_dict().items()})
    float(np.asarray(next(iter(outputs.values()))).ravel()[0])
    ttfp = time.perf_counter() - t0
  result = {
      "probe": "serving",
      "tiny": tiny,
      "device_kind": jax.devices()[0].device_kind,
      "restored": bool(restored),
      "time_to_first_prediction_secs": round(ttfp, 3),
      "restore_and_warmup_secs": round(restore_done, 3),
      "engine_warmup_secs": round(predictor.warmup_seconds, 3),
      "compiled_buckets": list(predictor.serving_engine.compiled_buckets),
      "compile_watch": watch.counts(),
      "cache_entries_after": cache_entry_count(cache_dir),
  }
  predictor.close()
  return result


def main(argv=None) -> int:
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument("probe", choices=("trainer", "serving"))
  parser.add_argument("--model-dir", required=True,
                      help="trainer model_dir / serving checkpoint dir")
  parser.add_argument("--cache-dir", default=None,
                      help="persistent compilation cache dir "
                           "(required unless --setup)")
  parser.add_argument("--tiny", action="store_true",
                      help="mock-model variant (the tier-1 smoke)")
  parser.add_argument("--setup", action="store_true",
                      help="seed the checkpoint instead of probing")
  args = parser.parse_args(argv)

  if args.probe == "trainer":
    if args.setup:
      result = trainer_setup(args.model_dir, args.tiny)
    else:
      result = trainer_probe(args.model_dir, args.cache_dir, args.tiny)
  else:
    if args.setup:
      result = serving_setup(args.model_dir, args.tiny)
    else:
      result = serving_probe(args.model_dir, args.cache_dir, args.tiny)
  print("COLDSTART_JSON " + json.dumps(result))
  return 0


if __name__ == "__main__":
  sys.exit(main())
