"""Cold-start subsystem tests (startup/ + trainer/predictor wiring).

Pins the contracts docs/STARTUP.md promises:
  * persistent-cache round-trip: a second process with the same cache
    dir performs ZERO XLA compilations (cache_misses == 0, every
    program a cache hit) — counted via jax.monitoring, not wall clock;
  * overlap correctness: a resume with overlapped
    restore/compile/input is bitwise-identical to the serial path;
  * startup phase timings are in the run's first log record (the
    benchmark's `startup_*` metrics read their gauges);
  * `CheckpointWriter.save()` stays async once the retention window is
    full (finished saves are pruned by completion, not only by wait());
  * the trainer's split metrics: pure train-loop steps_per_sec +
    stall_fraction;
  * `continuous_eval` reports per-checkpoint restore+eval wall time;
  * predictor restore ∥ engine compile-ahead overlap.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax

from tensor2robot_tpu import train_eval
from tensor2robot_tpu.data import Mode, RandomInputGenerator
from tensor2robot_tpu.predictors import CheckpointPredictor
from tensor2robot_tpu.serving import BucketedServingEngine
from tensor2robot_tpu.specs import make_random_tensors
from tensor2robot_tpu.startup import compile_cache
from tensor2robot_tpu.startup import orchestrator
from tensor2robot_tpu.utils import checkpoints as ckpt_lib
from tensor2robot_tpu.utils.mocks import MockT2RModel
from tensor2robot_tpu.telemetry.records import read_records

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _subprocess_env():
  env = dict(os.environ)
  env["JAX_PLATFORMS"] = "cpu"
  env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
  return env


class TestCompileCache:

  def test_configure_writes_entries_and_is_idempotent(self, tmp_path):
    cache_dir = str(tmp_path / "cache")
    try:
      resolved = compile_cache.configure_compilation_cache(
          cache_dir=cache_dir)
      assert resolved == os.path.abspath(cache_dir)
      # Second call with the same dir: no-op, same answer.
      assert compile_cache.configure_compilation_cache(
          cache_dir=cache_dir) == resolved
      with compile_cache.CompileWatch() as watch:
        out = jax.jit(lambda x: (x * 3.0).sum() + 1.0)(
            np.ones((33, 33), np.float32))
        out.block_until_ready()
      assert watch.cache_misses >= 1
      assert compile_cache.cache_entry_count(cache_dir) >= 1
    finally:
      compile_cache.reset_compilation_cache_config()

  def test_placement_without_the_env_var(self, tmp_path):
    """JAX_COMPILATION_CACHE_DIR unset (conftest): no argument places
    the cache at the fixed default; an explicit dir overrides it and
    stays in force for later no-arg callers; a reset goes back."""
    default = compile_cache.DEFAULT_CACHE_DIR  # conftest's session dir
    explicit = str(tmp_path / "explicit")
    try:
      assert compile_cache.configure_compilation_cache() == default
      assert jax.config.jax_compilation_cache_dir == default
      assert compile_cache.configure_compilation_cache(
          cache_dir=explicit) == explicit
      assert os.path.isdir(explicit)
      # A library entry point's no-arg call keeps the explicit dir.
      assert compile_cache.configure_compilation_cache() == explicit
      assert jax.config.jax_compilation_cache_dir == explicit
    finally:
      compile_cache.reset_compilation_cache_config()
    assert compile_cache.configure_compilation_cache() == default

  def test_env_var_placement_never_touches_the_flag(self, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself; the program
    creates the directory, ignores every explicit dir, never updates
    `jax_compilation_cache_dir`, and the executables land there. The
    same child pins the unset-case default to the in-checkout path."""
    env_dir = str(tmp_path / "from_env")
    other = str(tmp_path / "explicit")
    code = (
        "import os\n"
        "import numpy as np\n"
        "import jax\n"
        "updates = []\n"
        "real_update = jax.config.update\n"
        "jax.config.update = lambda name, value: (\n"
        "    updates.append(name), real_update(name, value))[1]\n"
        "from tensor2robot_tpu.startup import compile_cache\n"
        f"assert compile_cache.DEFAULT_CACHE_DIR == {os.path.join(REPO_ROOT, '.jax_cache')!r}\n"
        f"env_dir, other = {env_dir!r}, {other!r}\n"
        "assert not os.path.exists(env_dir)\n"
        "for arg in (None, other):\n"
        "  assert compile_cache.configure_compilation_cache(\n"
        "      cache_dir=arg) == env_dir\n"
        "assert os.path.isdir(env_dir) and not os.path.exists(other)\n"
        "compile_cache.reset_compilation_cache_config()\n"
        "assert 'jax_compilation_cache_dir' not in updates, updates\n"
        "assert jax.config.jax_compilation_cache_dir == env_dir\n"
        "jax.jit(lambda x: (x * 3.0).sum() + 1.0)(\n"
        "    np.ones((33, 33), np.float32)).block_until_ready()\n"
        "assert compile_cache.cache_entry_count(env_dir) >= 1\n")
    env = _subprocess_env()
    env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    subprocess.run([sys.executable, "-c", code], env=env, timeout=600,
                   check=True)

  def test_persistent_cache_roundtrip_across_processes(self, tmp_path):
    """THE warm-restart contract: the second process with the same
    cache dir compiles 0 programs — every compile request is served
    from the persistent cache."""
    cache_dir = str(tmp_path / "cache")
    code = (
        "import numpy as np\n"
        "import jax, jax.numpy as jnp\n"
        "from tensor2robot_tpu.startup import (CompileWatch,\n"
        "    configure_compilation_cache)\n"
        f"configure_compilation_cache(cache_dir={cache_dir!r})\n"
        "with CompileWatch() as w:\n"
        "  out = jax.jit(lambda x: jnp.sin(x) @ x + 2.0)(\n"
        "      np.ones((48, 48), np.float32))\n"
        "  out.block_until_ready()\n"
        "print('WATCH', w.cache_hits, w.cache_misses)\n")
    results = []
    for _ in range(2):
      out = subprocess.run(
          [sys.executable, "-c", code], env=_subprocess_env(),
          capture_output=True, text=True, timeout=600, check=True)
      line = [l for l in out.stdout.splitlines()
              if l.startswith("WATCH ")][-1]
      hits, misses = map(int, line.split()[1:])
      results.append((hits, misses))
    (first_hits, first_misses), (second_hits, second_misses) = results
    assert first_misses >= 1            # cold: really compiled
    assert second_misses == 0           # warm: zero XLA compilations
    assert second_hits >= first_misses  # every program deserialized


class TestOverlappedStartup:

  def _run(self, model_dir, max_steps, overlap, hidden=(8,)):
    return train_eval.train_eval_model(
        model=MockT2RModel(hidden_sizes=hidden),
        model_dir=model_dir,
        input_generator_train=RandomInputGenerator(batch_size=8, seed=5),
        input_generator_eval=RandomInputGenerator(batch_size=8, seed=6),
        max_train_steps=max_steps,
        eval_steps=2,
        save_checkpoints_steps=3,
        log_every_steps=3,
        overlap_startup=overlap,
    )

  def test_resume_overlap_matches_serial_bitwise(self, tmp_path):
    """Overlapped restore + AOT-compiled step == the serial path,
    bitwise: same checkpoint, same generator stream, same PRNG."""
    base = str(tmp_path / "base")
    self._run(base, max_steps=3, overlap=False)
    fork = str(tmp_path / "fork")
    shutil.copytree(base, fork)
    serial = self._run(base, max_steps=6, overlap=False)
    overlapped = self._run(fork, max_steps=6, overlap=True)
    assert int(np.asarray(jax.device_get(overlapped.step))) == 6
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(jax.device_get(
            serial.params)),
        jax.tree_util.tree_leaves(jax.device_get(overlapped.params))):
      np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                    err_msg=str(path))

  def test_fresh_start_overlap_matches_serial_bitwise(self, tmp_path):
    serial = self._run(str(tmp_path / "s"), max_steps=6, overlap=False)
    overlapped = self._run(str(tmp_path / "o"), max_steps=6,
                           overlap=True)
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(serial.params)),
        jax.tree_util.tree_leaves(jax.device_get(overlapped.params))):
      np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

  def test_startup_timings_written(self, tmp_path):
    """The first log record of a run holds its start's account: one
    exporter of start-up, the registry's (`startup_timings.json` is
    gone)."""
    model_dir = str(tmp_path / "m")
    self._run(model_dir, max_steps=3, overlap=True)
    self._run(model_dir, max_steps=6, overlap=True)  # resume
    assert not os.path.exists(
        os.path.join(model_dir, "startup_timings.json"))
    records = read_records(os.path.join(model_dir,
                                        "metrics_train.jsonl"))
    assert [r["step"] for r in records] == [3, 6]
    timings = {key: value for key, value in records[1].items()
               if key.startswith("startup.")}
    # The resume run overlapped all three phases, under one join.
    assert all(timings[f"startup.{phase}_s"] > 0
               for phase in ("compile", "restore", "input"))
    assert timings["startup.join_s"] >= max(
        timings[f"startup.{phase}_s"]
        for phase in ("compile", "restore", "input"))
    assert timings["startup.to_first_metrics_s"] \
        > timings["startup.join_s"]
    # The fresh run restored nothing.
    assert records[0]["startup.restore_s"] == 0

  def test_run_overlapped_surfaces_errors_after_join(self):
    def ok():
      return 42

    def boom():
      raise RuntimeError("phase failed")

    report = orchestrator.run_overlapped({"a": ok, "b": boom})
    assert report.results["a"] == 42
    assert "b" in report.errors
    with pytest.raises(RuntimeError, match="phase failed"):
      report.raise_first()

  def test_stall_fraction_and_pure_steps_per_sec(self, tmp_path):
    model_dir = str(tmp_path / "m")
    train_eval.train_eval_model(
        model=MockT2RModel(),
        model_dir=model_dir,
        input_generator_train=RandomInputGenerator(batch_size=8, seed=1),
        input_generator_eval=RandomInputGenerator(batch_size=8, seed=2),
        max_train_steps=20,
        eval_steps=2,
        eval_every_steps=10,
        save_checkpoints_steps=10,
        log_every_steps=5,
    )
    records = read_records(
        os.path.join(model_dir, "metrics_train.jsonl"))
    assert len(records) >= 3
    for record in records:
      assert record["steps_per_sec"] > 0
      assert 0.0 <= record["stall_fraction"] <= 1.0
    # Intervals containing a save and an eval must see a nonzero
    # stall; step 15's interval (no save, no eval) only pays the
    # previous metric write.
    stalled = [r["stall_fraction"] for r in records
               if r["step"] in (15, 20)]
    assert any(s > 0 for s in stalled)

  def test_continuous_eval_reports_restore_eval_walltime(self, tmp_path):
    model_dir = str(tmp_path / "m")
    model = MockT2RModel()
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
    metrics = results[10]
    assert metrics["restore_secs"] > 0
    assert metrics["eval_secs"] > 0
    assert metrics["restore_and_eval_secs"] == pytest.approx(
        metrics["restore_secs"] + metrics["eval_secs"])


class TestCheckpointWriterAsyncGC:

  def _tiny_state(self, value):
    return {"w": np.full((4,), value, np.float32)}

  def _wait_finalized(self, writer, model_dir, step, timeout=30.0):
    import time
    deadline = time.time() + timeout
    path = os.path.join(model_dir, ckpt_lib.CKPT_SUBDIR, str(step),
                        "state")
    while time.time() < deadline:
      if os.path.isdir(path):
        return
      time.sleep(0.01)
    raise AssertionError(f"save {step} never finalized")

  def test_save_does_not_block_after_retention_window_fills(
      self, tmp_path, monkeypatch):
    """THE steady-state contract: once prior saves have finished,
    save() must never fall back to a full synchronous wait() even
    with the retention window full (the pre-fix behavior: every
    GC victim looked 'pending' forever, silently degrading async
    checkpointing to synchronous)."""
    model_dir = str(tmp_path / "m")
    writer = ckpt_lib.CheckpointWriter(model_dir, max_to_keep=2)
    waits = []
    real_wait = writer.wait
    monkeypatch.setattr(
        writer, "wait", lambda: (waits.append(1), real_wait())[1])
    try:
      for i, step in enumerate((1, 2, 3, 4, 5)):
        # Steady state: the PREVIOUS save has long finished when the
        # next one arrives (poll its atomic-rename finalization).
        writer.save(step, self._tiny_state(i))
        self._wait_finalized(writer, model_dir, step)
      assert not waits, (
          "save() blocked on a full wait() despite every prior save "
          "having finished")
      # Retention still enforced.
      assert ckpt_lib.list_steps(model_dir) == [4, 5]
    finally:
      monkeypatch.setattr(writer, "wait", real_wait)
      writer.close()

  def test_inflight_victim_still_waits(self, tmp_path):
    """The pathological case (max_to_keep < save cadence) keeps its
    correctness blocking: a victim genuinely in flight forces a
    wait, never a delete-under-write."""
    model_dir = str(tmp_path / "m")
    writer = ckpt_lib.CheckpointWriter(model_dir, max_to_keep=1)
    try:
      for step in (1, 2, 3):
        writer.save(step, self._tiny_state(step))
      writer.wait()
      assert ckpt_lib.list_steps(model_dir) == [3]
    finally:
      writer.close()


class TestPredictorOverlap:

  def _seed_checkpoint(self, model, ckpt_dir):
    state = model.create_inference_state(jax.random.PRNGKey(0))
    writer = ckpt_lib.CheckpointWriter(ckpt_dir, max_to_keep=None)
    writer.save(1, state)
    writer.close()

  def test_restore_overlaps_compile_ahead(self, tmp_path):
    model = MockT2RModel()
    ckpt_dir = str(tmp_path / "ckpt")
    self._seed_checkpoint(model, ckpt_dir)
    predictor = CheckpointPredictor(
        model, checkpoint_dir=ckpt_dir, max_batch=4,
        warmup=True, overlap_startup=True)
    try:
      assert predictor.restore(timeout_secs=0)
      # After restore() the compile-ahead has been joined: every
      # bucket is a finished executable.
      assert predictor.serving_engine.compiled_buckets == (1, 2, 4)
      assert predictor.warmup_seconds > 0
      spec = predictor.feature_specification
      batch = make_random_tensors(spec, batch_size=3, seed=0)
      out = predictor.predict(
          {k: np.asarray(v) for k, v in batch.to_flat_dict().items()})
      values = np.asarray(list(out.values())[0])
      assert values.shape[0] == 3
      assert np.isfinite(values).all()
    finally:
      predictor.close()

  def test_engine_warmup_async_idempotent_and_race_safe(self):
    model = MockT2RModel()
    state = model.create_inference_state(jax.random.PRNGKey(0))
    spec = model.preprocessor.get_in_feature_specification(Mode.PREDICT)
    from tensor2robot_tpu import specs as specs_lib
    example = make_random_tensors(
        specs_lib.flatten_spec_structure(spec), batch_size=1, seed=0)
    engine = BucketedServingEngine(model.predict_step, state, example,
                                   max_batch=4)
    thread = engine.warmup_async()
    assert engine.warmup_async() is thread  # idempotent
    # A request racing the warmup thread is serialized by the compile
    # lock and must return a correct result immediately.
    out = engine.predict(example)
    assert np.isfinite(
        np.asarray(jax.tree_util.tree_leaves(out)[0])).all()
    engine.wait_warmup()
    assert engine.compiled_buckets == (1, 2, 4)
