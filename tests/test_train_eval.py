"""End-to-end trainer tests (reference: train_eval_test.py pattern —
MockT2RModel + random input generators, then assert on-disk artifacts)."""

import glob
import json
import os
import threading

import jax
import numpy as np
import pytest

from tensor2robot_tpu import train_eval
from tensor2robot_tpu.data import Mode, RandomInputGenerator
from tensor2robot_tpu.hooks import Hook
from tensor2robot_tpu.utils import checkpoints as ckpt_lib
from tensor2robot_tpu.utils.mocks import MockT2RModel
from tensor2robot_tpu.telemetry.records import read_records


class RecordingHook(Hook):

  def __init__(self):
    self.began = False
    self.steps = []
    self.checkpoints = []
    self.ended = False

  def begin(self, model, model_dir):
    self.began = True

  def after_step(self, step, metrics):
    self.steps.append(step)

  def after_checkpoint(self, step, state, model_dir):
    self.checkpoints.append(step)

  def end(self, step, state, model_dir):
    self.ended = True


def test_train_eval_end_to_end(tmp_path):
  model_dir = str(tmp_path / "m")
  hook = RecordingHook()
  state = train_eval.train_eval_model(
      model=MockT2RModel(),
      model_dir=model_dir,
      input_generator_train=RandomInputGenerator(batch_size=16),
      input_generator_eval=RandomInputGenerator(batch_size=16),
      max_train_steps=20,
      eval_steps=3,
      save_checkpoints_steps=10,
      log_every_steps=5,
      hooks=[hook],
  )
  assert int(np.asarray(jax.device_get(state.step))) == 20
  # Checkpoints at 10 and 20.
  assert ckpt_lib.list_steps(model_dir) == [10, 20]
  # Hooks fired.
  assert hook.began and hook.ended
  assert hook.checkpoints == [10, 20]
  assert len(hook.steps) == 20
  # Metrics written.
  records = read_records(
      os.path.join(model_dir, "metrics_train.jsonl"))
  assert records[-1]["step"] == 20
  assert "loss" in records[-1] and "steps_per_sec" in records[-1]
  # The feed-boundness signal rides every train log record: the share
  # of the interval's wall spent blocked in the prefetcher.
  for record in records:
    assert 0.0 <= record["input_wait_fraction"] <= 1.0
  assert "stall_fraction" in records[-1]
  eval_lines = open(
      os.path.join(model_dir, "metrics_eval.jsonl")).readlines()
  assert len(eval_lines) >= 1


def test_resume_from_checkpoint(tmp_path):
  model_dir = str(tmp_path / "m")
  common = dict(
      model=MockT2RModel(),
      model_dir=model_dir,
      input_generator_train=RandomInputGenerator(batch_size=8),
      max_train_steps=10,
      save_checkpoints_steps=5,
      log_every_steps=5,
  )
  train_eval.train_eval_model(**common)
  assert ckpt_lib.latest_step(model_dir) == 10
  # Second call with a higher cap resumes at 10, trains to 15.
  common["max_train_steps"] = 15
  state = train_eval.train_eval_model(**common)
  assert int(np.asarray(jax.device_get(state.step))) == 15
  assert 15 in ckpt_lib.list_steps(model_dir)


def test_eval_only(tmp_path):
  model_dir = str(tmp_path / "m")
  state = train_eval.train_eval_model(
      model=MockT2RModel(),
      model_dir=model_dir,
      input_generator_eval=RandomInputGenerator(batch_size=8),
      max_train_steps=0,
      eval_steps=2,
  )
  eval_lines = open(
      os.path.join(model_dir, "metrics_eval.jsonl")).readlines()
  assert len(eval_lines) == 1


def test_train_loss_decreases(tmp_path):
  model_dir = str(tmp_path / "m")
  train_eval.train_eval_model(
      model=MockT2RModel(),
      model_dir=model_dir,
      input_generator_train=RandomInputGenerator(batch_size=32, seed=3),
      max_train_steps=200,
      save_checkpoints_steps=200,
      log_every_steps=10,
  )
  records = read_records(
      os.path.join(model_dir, "metrics_train.jsonl"))
  # Random targets: loss should shrink toward the target variance floor.
  assert records[-1]["loss"] < records[0]["loss"]


def test_continuous_eval(tmp_path):
  model_dir = str(tmp_path / "m")
  model = MockT2RModel()
  # Produce two checkpoints first.
  train_eval.train_eval_model(
      model=model,
      model_dir=model_dir,
      input_generator_train=RandomInputGenerator(batch_size=8),
      max_train_steps=10,
      save_checkpoints_steps=5,
  )
  results = train_eval.continuous_eval(
      model=model,
      model_dir=model_dir,
      input_generator_eval=RandomInputGenerator(batch_size=8),
      eval_steps=2,
      timeout_secs=0.5,
      poll_interval_secs=0.1,
      max_evals=5,
  )
  # Latest checkpoint evaluated; then timeout ends the loop.
  assert 10 in results
  assert "loss" in results[10]


def test_mesh_sharded_training_runs_on_8_devices(tmp_path):
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  mesh = mesh_lib.create_mesh({"data": 8})
  model_dir = str(tmp_path / "m")
  state = train_eval.train_eval_model(
      model=MockT2RModel(),
      model_dir=model_dir,
      input_generator_train=RandomInputGenerator(batch_size=16),
      max_train_steps=5,
      save_checkpoints_steps=5,
      mesh=mesh,
  )
  # Params replicated over all 8 devices.
  leaf = jax.tree_util.tree_leaves(state.params)[0]
  assert len(leaf.sharding.device_set) == 8


def test_fsdp_strategy_trains_and_resumes(tmp_path):
  """sharding_strategy='fsdp' through the MAIN trainer: params land
  sharded over the fsdp axis, training runs, and resume restores onto
  the same layout."""
  from jax.sharding import PartitionSpec as P

  from tensor2robot_tpu.parallel import FSDP_AXIS
  from tensor2robot_tpu.parallel import mesh as mesh_lib

  mesh = mesh_lib.create_mesh({"data": 4, "fsdp": 2})
  model_dir = str(tmp_path / "m")
  # Wide enough that the hidden kernel crosses min_size_to_shard.
  kwargs = dict(
      model=MockT2RModel(hidden_sizes=(64,)),
      model_dir=model_dir,
      input_generator_train=RandomInputGenerator(batch_size=16),
      save_checkpoints_steps=5,
      mesh=mesh,
      sharding_strategy="fsdp",
      min_size_to_shard=64,
  )
  state = train_eval.train_eval_model(max_train_steps=5, **kwargs)
  sharded_leaves = [
      leaf for leaf in jax.tree_util.tree_leaves(state.params)
      if any(axis == FSDP_AXIS
             for axis in (leaf.sharding.spec or P()))]
  assert sharded_leaves, {  # at least one param actually fsdp-sharded
      jax.tree_util.keystr(path): leaf.sharding for path, leaf in
      jax.tree_util.tree_leaves_with_path(state.params)}
  # Resume: second call picks up the checkpoint and continues sharded.
  state = train_eval.train_eval_model(max_train_steps=8, **kwargs)
  assert int(np.asarray(jax.device_get(state.step))) == 8


def test_fsdp_trained_model_exports_and_serves(tmp_path):
  """Pod-style training hands off to robot-style serving: a model
  trained with fsdp-sharded state exports a SavedModel (the exporter
  gathers shards host-side) and the predictor round-trips it."""
  from tensor2robot_tpu.export import (
      SavedModelExportGenerator,
      latest_export_dir,
  )
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.predictors import SavedModelPredictor
  from tensor2robot_tpu.specs import make_random_tensors

  mesh = mesh_lib.create_mesh({"data": 4, "fsdp": 2})
  model = MockT2RModel(hidden_sizes=(64,))
  model_dir = str(tmp_path / "m")
  train_eval.train_eval_model(
      model=model,
      model_dir=model_dir,
      input_generator_train=RandomInputGenerator(batch_size=16),
      max_train_steps=5,
      save_checkpoints_steps=5,
      mesh=mesh,
      sharding_strategy="fsdp",
      min_size_to_shard=64,
      create_exporters_fn=lambda m: [SavedModelExportGenerator()],
  )
  export_base = SavedModelExportGenerator().export_dir_base(model_dir)
  assert latest_export_dir(export_base) is not None
  predictor = SavedModelPredictor(export_base)
  assert predictor.restore(timeout_secs=0)
  batch = make_random_tensors(
      model.preprocessor.get_in_feature_specification(Mode.PREDICT),
      batch_size=3, seed=7)
  out = predictor.predict(
      {k: np.asarray(v) for k, v in batch.to_flat_dict().items()})
  values = np.asarray(list(out.values())[0])
  assert values.shape[0] == 3
  assert np.isfinite(values).all()


def test_mesh_and_strategy_configurable_from_gin():
  """The full sharded-training surface is reachable from .gin files:
  mesh layout AND strategy are bindings, no Python required."""
  from tensor2robot_tpu import config as gin
  import tensor2robot_tpu.parallel  # noqa: F401 — registers create_mesh

  gin.clear_config()
  try:
    gin.parse_config_files_and_bindings([], [
        'train_eval_model.mesh = @create_mesh()',
        'create_mesh.axis_shapes = {"data": 4, "fsdp": 2}',
        'train_eval_model.sharding_strategy = "fsdp"',
    ])
    mesh = gin.query_parameter("train_eval_model.mesh").resolve()
    assert dict(mesh.shape) == {"data": 4, "fsdp": 2}
    assert gin.query_parameter(
        "train_eval_model.sharding_strategy") == "fsdp"
  finally:
    gin.clear_config()


def test_distributed_init_noops_single_process():
  """Single-process launches must not try to form a cluster."""
  from tensor2robot_tpu.parallel import maybe_initialize_distributed
  from tensor2robot_tpu.parallel import distributed as dist_mod
  assert not dist_mod._INITIALIZED
  assert maybe_initialize_distributed() is False
  assert not dist_mod._INITIALIZED


def test_tensor_parallel_rules_compile_on_mesh():
  """The TP sharding rules must produce an executable program.

  The driver's dryrun covers the full learner; this is the in-suite
  guard that `tensor_parallel_sharding` stays compilable: a dense
  kernel splits its output dim over `model`, its input dim over
  `fsdp`, and matmul against a data-sharded batch executes.
  """
  import jax
  import jax.numpy as jnp
  from jax.sharding import NamedSharding, PartitionSpec as P
  from tensor2robot_tpu.parallel import (
      DATA_AXIS,
      FSDP_AXIS,
      MODEL_AXIS,
      batch_sharding,
      create_mesh,
      tensor_parallel_sharding,
  )

  mesh = create_mesh({DATA_AXIS: 2, FSDP_AXIS: 2, MODEL_AXIS: 2})
  params = {"kernel": jnp.ones((64, 128)), "bias": jnp.ones((128,))}
  shardings = tensor_parallel_sharding(mesh, params,
                                       min_size_to_shard=2 ** 6)
  assert shardings["kernel"].spec == P(FSDP_AXIS, MODEL_AXIS)
  params = jax.device_put(params, shardings)
  batch = jax.device_put(jnp.ones((8, 64)), batch_sharding(mesh))

  @jax.jit
  def forward(params, x):
    return jnp.mean(x @ params["kernel"] + params["bias"])

  with mesh:
    out = forward(params, batch)
  assert bool(jnp.isfinite(out))


def test_steps_per_dispatch_matches_per_step_training(tmp_path):
  """K-scanned dispatches (the reference's iterations_per_loop) must
  be numerically identical to per-step dispatch: same deterministic
  generator stream, same per-step PRNG folding."""
  def run(k, name):
    return train_eval.train_eval_model(
        model=MockT2RModel(),
        model_dir=str(tmp_path / name),
        input_generator_train=RandomInputGenerator(batch_size=8,
                                                   seed=5),
        max_train_steps=6,
        save_checkpoints_steps=6,
        log_every_steps=3,
        steps_per_dispatch=k,
    )

  base = run(1, "k1")
  scanned = run(3, "k3")
  assert int(np.asarray(jax.device_get(scanned.step))) == 6
  for (path, a), b in zip(
      jax.tree_util.tree_leaves_with_path(
          jax.device_get(base.params)),
      jax.tree_util.tree_leaves(jax.device_get(scanned.params))):
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-6,
        err_msg=str(path))


def test_steps_per_dispatch_rejects_misaligned_cadence(tmp_path):
  with pytest.raises(ValueError, match="multiple of"):
    train_eval.train_eval_model(
        model=MockT2RModel(),
        model_dir=str(tmp_path / "bad"),
        input_generator_train=RandomInputGenerator(batch_size=8),
        max_train_steps=10,
        save_checkpoints_steps=5,
        log_every_steps=5,
        steps_per_dispatch=4,
    )


def test_completed_run_reinvoked_with_k_noops(tmp_path):
  """Re-invoking a finished run with steps_per_dispatch>1 must no-op
  (resume sees step >= max_train_steps), not raise on alignment."""
  kwargs = dict(
      model=MockT2RModel(),
      model_dir=str(tmp_path / "m"),
      input_generator_train=RandomInputGenerator(batch_size=8),
  )
  train_eval.train_eval_model(
      max_train_steps=5, save_checkpoints_steps=5, log_every_steps=5,
      **kwargs)
  # Resume step 5 is NOT a multiple of K=4, but the run is already
  # complete at max_train_steps=4: the alignment check must not fire
  # for a no-op invocation (cadences here are K-aligned, so only the
  # resume-alignment guard is exercised).
  state = train_eval.train_eval_model(
      max_train_steps=4, save_checkpoints_steps=4, log_every_steps=4,
      steps_per_dispatch=4, **kwargs)
  assert int(np.asarray(jax.device_get(state.step))) == 5


def test_a_loop_ended_by_an_exception_leaves_no_state_on_the_device(
    tmp_path):
  """A hook ends the loop and the caller handles that in a
  generator-based context manager, as the benchmark's harness does:
  exception, traceback and the trainer's frame then stand in a reference
  cycle until a full collection. The state must be gone without one
  (ISSUE 35: 7.6 GB of it stood on the chip while the harness's check
  asked for memory)."""
  import contextlib
  import gc

  class Stop(Exception):
    pass

  class StopAt(Hook):

    def after_step(self, step, metrics):
      if step == 4:
        raise Stop()

  @contextlib.contextmanager
  def until_stopped():
    try:
      yield
    except Stop:
      pass

  def run(name, hooks):
    return train_eval.train_eval_model(
        model=MockT2RModel(), model_dir=str(tmp_path / name),
        input_generator_train=RandomInputGenerator(batch_size=8),
        max_train_steps=8, save_checkpoints_steps=8, log_every_steps=2,
        hooks=hooks)

  def live_bytes():
    return sum(a.nbytes for a in jax.live_arrays())

  state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
      run("whole", [])))
  gc.collect()
  gc.disable()
  try:
    before = live_bytes()
    with until_stopped():
      run("stopped", [StopAt()])
    held = live_bytes() - before
  finally:
    gc.enable()
  # A batch and a record of metrics are still some frame's; no state.
  assert held < state_bytes, (held, state_bytes)
