"""Tests for the low-latency serving path (serving/ + predictors).

Pins the contracts docs/SERVING.md promises:
  * bucket table / padding math;
  * the micro-batcher coalesces N concurrent callers into fewer
    dispatches and every caller gets exactly its own rows;
  * bucket padding never changes real rows' outputs (bitwise, within
    one compiled program);
  * zero recompiles on the hot path after AOT warmup (engine compile
    counter AND jax.monitoring compile events);
  * checkpoint hot-swap mid-traffic serves only fully-restored params
    (old or new tree per dispatch, never a mix);
  * the CEM policy server answers direct requests of one row and of
    a full bucket after its warmup with no compile.

Numerics note: XLA specializes code per batch shape, so outputs of
DIFFERENT bucket programs may differ by float-associativity ulps;
cross-program comparisons use a 1e-5 tolerance while same-program
comparisons (the padding-invariance pin) are exact.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensor2robot_tpu import specs
from tensor2robot_tpu.data.abstract_input_generator import Mode
from tensor2robot_tpu.predictors import CheckpointPredictor
from tensor2robot_tpu.serving import (
    BucketedServingEngine,
    MicroBatcher,
    bucket_for,
    bucket_table,
    pad_batch,
)
from tensor2robot_tpu.serving import engine as engine_lib
from tensor2robot_tpu.utils.mocks import MockT2RModel


def _wire_spec(model):
  return specs.flatten_spec_structure(
      model.preprocessor.get_in_feature_specification(Mode.PREDICT))


def _make_engine(max_batch=8, warmed=True):
  model = MockT2RModel()
  state = model.create_inference_state(jax.random.PRNGKey(0))
  example = specs.make_random_tensors(_wire_spec(model), batch_size=1,
                                      seed=0)
  engine = BucketedServingEngine(model.predict_step, state, example,
                                 max_batch=max_batch)
  if warmed:
    engine.warmup()
  return model, engine


class TestBucketing:

  def test_bucket_table_powers_of_two(self):
    assert bucket_table(1) == (1,)
    assert bucket_table(8) == (1, 2, 4, 8)
    assert bucket_table(6) == (1, 2, 4, 8)  # covers max_batch

  def test_bucket_for_picks_smallest_cover(self):
    table = bucket_table(8)
    assert bucket_for(1, table) == 1
    assert bucket_for(3, table) == 4
    assert bucket_for(8, table) == 8

  def test_bucket_for_overflow_raises(self):
    with pytest.raises(ValueError, match="exceeds"):
      bucket_for(9, bucket_table(8))

  def test_pad_batch_replicates_last_row(self):
    tree = {"x": np.arange(6, dtype=np.float32).reshape(3, 2)}
    padded = pad_batch(tree, 4)
    assert padded["x"].shape == (4, 2)
    np.testing.assert_array_equal(padded["x"][3], tree["x"][2])


class TestEngine:

  def test_outputs_match_plain_predict_step(self):
    model, engine = _make_engine()
    batch = specs.make_random_tensors(_wire_spec(model), batch_size=3,
                                      seed=1)
    state = model.create_inference_state(jax.random.PRNGKey(0))
    want = jax.jit(model.predict_step)(state, batch)
    got = engine.predict(batch)
    np.testing.assert_allclose(
        jax.tree_util.tree_leaves(got)[0],
        np.asarray(jax.tree_util.tree_leaves(want)[0])[:3], atol=1e-5)

  def test_padding_never_changes_outputs(self):
    """Bitwise pin, same compiled program: a 3-row request (padded
    3→4) and a 4-row request whose first 3 rows are identical must
    produce identical leading rows — pad rows cannot leak."""
    model, engine = _make_engine()
    three = specs.make_random_tensors(_wire_spec(model), batch_size=3,
                                      seed=2)
    flat3 = three.to_flat_dict()
    flat4 = {k: np.concatenate(
        [v, np.full_like(v[-1:], 7.25)]) for k, v in flat3.items()}
    out3 = engine.predict(specs.TensorSpecStruct.from_flat_dict(flat3))
    out4 = engine.predict(specs.TensorSpecStruct.from_flat_dict(flat4))
    np.testing.assert_array_equal(
        jax.tree_util.tree_leaves(out3)[0],
        jax.tree_util.tree_leaves(out4)[0][:3])

  def test_zero_recompiles_after_warmup(self):
    """THE perf contract: after warmup, no request size ≤ max_batch
    may trigger a compile — counted by the engine AND by
    jax.monitoring compile events."""
    import jax.monitoring as monitoring

    model, engine = _make_engine(max_batch=8)
    before = engine_lib.compile_count()
    events = []
    watching = {"on": True}

    def _listener(event, **kwargs):
      if watching["on"] and "compile" in event.lower():
        events.append(event)

    monitoring.register_event_listener(_listener)
    try:
      for n in (1, 2, 3, 4, 5, 7, 8, 1, 6):
        batch = specs.make_random_tensors(_wire_spec(model),
                                          batch_size=n, seed=n)
        out = engine.predict(batch)
        assert jax.tree_util.tree_leaves(out)[0].shape[0] == n
    finally:
      watching["on"] = False
    assert engine_lib.compile_count() == before
    assert not events, events
    assert engine.compiled_buckets == (1, 2, 4, 8)

  def test_hot_swap_serves_only_full_trees(self):
    """Mid-traffic checkpoint refresh: every dispatch must see an
    entirely-old or entirely-new params tree. Params are constant
    trees (c and c+1000), so a mixed tree would produce outputs in
    neither program's value band."""
    model, engine = _make_engine(max_batch=2)
    spec = _wire_spec(model)
    state = model.create_inference_state(jax.random.PRNGKey(0))

    def constant_state(c):
      return state.replace(params=jax.tree_util.tree_map(
          lambda a: jnp.full_like(a, c), state.params))

    batch = specs.make_random_tensors(spec, batch_size=1, seed=3)
    engine.swap_state(constant_state(1.0))
    want_old = jax.tree_util.tree_leaves(engine.predict(batch))[0]
    engine.swap_state(constant_state(1001.0))
    want_new = jax.tree_util.tree_leaves(engine.predict(batch))[0]
    engine.swap_state(constant_state(1.0))

    stop = threading.Event()
    bad = []

    def traffic():
      while not stop.is_set():
        got = jax.tree_util.tree_leaves(engine.predict(batch))[0]
        if not (np.array_equal(got, want_old)
                or np.array_equal(got, want_new)):
          bad.append(got)

    threads = [threading.Thread(target=traffic) for _ in range(2)]
    for t in threads:
      t.start()
    for c in (1001.0, 1.0, 1001.0, 1.0, 1001.0):
      engine.swap_state(constant_state(c))
    time.sleep(0.05)
    stop.set()
    for t in threads:
      t.join(timeout=30)
    assert not bad, bad[:1]
    assert engine.swap_count >= 7


class TestMicroBatcher:

  def test_concurrent_callers_coalesce_into_fewer_dispatches(self):
    model, engine = _make_engine(max_batch=8)
    spec = _wire_spec(model)
    batcher = MicroBatcher(engine, max_wait_us=100_000)
    barrier = threading.Barrier(6)
    results = {}

    def caller(i):
      batch = specs.make_random_tensors(spec, batch_size=1, seed=50 + i)
      barrier.wait()
      results[i] = batcher.predict(batch)

    threads = [threading.Thread(target=caller, args=(i,))
               for i in range(6)]
    for t in threads:
      t.start()
    for t in threads:
      t.join(timeout=60)
    batcher.close()
    assert len(results) == 6
    # Coalescing: 6 single-row callers in strictly fewer dispatches
    # (the first dispatch may race ahead with fewer rows queued).
    assert batcher.dispatches < 6, batcher.batch_sizes
    assert sum(batcher.batch_sizes) == 6
    # Per-caller results equal the unbatched predict of the same rows
    # (1e-5: coalesced rows may run a different bucket's program).
    for i in range(6):
      batch = specs.make_random_tensors(spec, batch_size=1, seed=50 + i)
      direct = engine.predict(batch)
      np.testing.assert_allclose(
          jax.tree_util.tree_leaves(results[i])[0],
          jax.tree_util.tree_leaves(direct)[0], atol=1e-5)

  def test_single_request_fallback_no_deadline_hold(self):
    """max_wait_us=0: a lone request dispatches immediately (the
    graceful degradation to the classic one-request path)."""
    model, engine = _make_engine(max_batch=8)
    spec = _wire_spec(model)
    with MicroBatcher(engine, max_wait_us=0) as batcher:
      batch = specs.make_random_tensors(spec, batch_size=1, seed=9)
      out = batcher.predict(batch)
      assert jax.tree_util.tree_leaves(out)[0].shape[0] == 1
      assert batcher.dispatches == 1

  def test_oversized_request_rejected(self):
    model, engine = _make_engine(max_batch=4)
    spec = _wire_spec(model)
    with MicroBatcher(engine, max_wait_us=0) as batcher:
      batch = specs.make_random_tensors(spec, batch_size=5, seed=4)
      with pytest.raises(ValueError, match="max_batch"):
        batcher.predict(batch)

  def test_submit_after_close_fails_fast(self):
    """ISSUE 13 satellite: a submit after close() must raise a clear
    error immediately — never enqueue into the dead dispatcher and
    strand its caller on a future that will never resolve."""
    model, engine = _make_engine(max_batch=4)
    spec = _wire_spec(model)
    batcher = MicroBatcher(engine, max_wait_us=0)
    batch = specs.make_random_tensors(spec, batch_size=1, seed=11)
    assert jax.tree_util.tree_leaves(
        batcher.predict(batch))[0].shape[0] == 1
    batcher.close()
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="closed"):
      batcher.submit(batch)
    assert time.perf_counter() - t0 < 1.0  # fail FAST, not a timeout
    # Idempotent close keeps the contract.
    batcher.close()
    with pytest.raises(RuntimeError, match="closed"):
      batcher.predict(batch)

  def test_dispatch_errors_propagate_to_callers(self):
    model, engine = _make_engine(max_batch=4)
    with MicroBatcher(engine, max_wait_us=0) as batcher:
      # Wrong feature structure dies inside the dispatch; the caller
      # must receive the exception, not hang.
      with pytest.raises(Exception):
        batcher.predict({"not_the_spec": np.zeros((1, 3), np.float32)})


class TestServingCheckpointPredictor:

  def test_serving_mode_matches_classic_path(self):
    model = MockT2RModel()
    serving = CheckpointPredictor(model, max_batch=4)
    classic = CheckpointPredictor(model)
    serving.init_randomly()
    classic.init_randomly()
    batch = specs.make_random_tensors(
        serving.feature_specification, batch_size=3, seed=6)
    flat = batch.to_flat_dict()
    got = serving.predict(flat)
    want = classic.predict(flat)
    assert set(got) == set(want)
    for k in got:
      np.testing.assert_allclose(got[k], want[k], atol=1e-5)
    assert serving.serving_engine.dispatch_count == 1
    serving.close()

  def test_restore_hot_swaps_serving_engine(self, tmp_path):
    from tensor2robot_tpu.data.random_input_generator import (
        RandomInputGenerator,
    )
    from tensor2robot_tpu import train_eval

    model_dir = str(tmp_path / "m")
    model = MockT2RModel()
    train_eval.train_eval_model(
        model=model,
        model_dir=model_dir,
        input_generator_train=RandomInputGenerator(batch_size=8),
        max_train_steps=2,
        save_checkpoints_steps=2,
        log_every_steps=2,
    )
    predictor = CheckpointPredictor(model, checkpoint_dir=model_dir,
                                    max_batch=2)
    swaps_before = predictor.serving_engine.swap_count
    assert predictor.restore(timeout_secs=0)
    assert predictor.serving_engine.swap_count == swaps_before + 1
    batch = specs.make_random_tensors(
        predictor.feature_specification, batch_size=2, seed=8)
    out = predictor.predict(batch.to_flat_dict())
    assert next(iter(out.values())).shape[0] == 2
    predictor.close()


class TestCEMPolicyServer:

  @pytest.fixture(scope="class")
  def server(self):
    from tensor2robot_tpu.research.qtopt import (
        GraspingQModel,
        QTOptLearner,
    )
    from tensor2robot_tpu.serving import CEMPolicyServer

    model = GraspingQModel(image_size=16, torso_filters=(8,),
                           head_filters=(8,), dense_sizes=(16,),
                           action_dim=2, device_dtype=jnp.float32)
    learner = QTOptLearner(model, cem_population=8, cem_iterations=1,
                           cem_elites=2)
    state = learner.create_state(jax.random.PRNGKey(0), batch_size=2)
    server = CEMPolicyServer(learner, state.train_state, max_batch=8,
                             max_wait_us=10_000, seed=0)
    yield learner, server
    server.close()

  def test_action_shapes_and_bounds(self, server):
    learner, srv = server
    obs = specs.make_random_tensors(
        learner.observation_specification(), batch_size=3, seed=1)
    actions = srv.select_actions(obs.to_flat_dict())
    assert actions.shape == (3, 2)
    assert np.all(actions >= -1.0) and np.all(actions <= 1.0)

  def test_concurrent_robots_coalesce(self, server):
    learner, srv = server
    obs_spec = learner.observation_specification()
    barrier = threading.Barrier(4)
    results = {}

    def robot(i):
      obs = specs.make_random_tensors(obs_spec, batch_size=1,
                                      seed=20 + i)
      barrier.wait()
      results[i] = srv.select_actions(obs.to_flat_dict())

    d0 = srv.batcher.dispatches
    threads = [threading.Thread(target=robot, args=(i,))
               for i in range(4)]
    for t in threads:
      t.start()
    for t in threads:
      t.join(timeout=120)
    assert len(results) == 4
    assert all(results[i].shape == (1, 2) for i in results)
    assert srv.batcher.dispatches - d0 < 4

  @pytest.mark.parametrize("rows", [1, 8])
  def test_direct_requests_compile_nothing_after_warmup(self, server,
                                                         rows):
    """What the control loop pays after a restart: the server's warmup
    compiled the CEM program of every bucket, so a direct request of
    one row, or of a full bucket, finds its program."""
    from tensor2robot_tpu.startup import CompileWatch

    learner, srv = server
    obs = specs.make_random_tensors(
        learner.observation_specification(), batch_size=rows, seed=rows)
    keys = list(jax.random.split(jax.random.PRNGKey(11), 3))
    assert srv.engine.compiled_buckets == (1, 2, 4, 8)
    before = engine_lib.compile_count()
    with CompileWatch() as watch:
      actions = [srv.select_actions_direct(obs, key) for key in keys]
    assert engine_lib.compile_count() == before
    assert watch.backend_compiles == 0 and watch.cache_requests == 0
    assert all(a.shape == (rows, 2) for a in actions)
    assert all(np.all(np.abs(a) <= 1.0) for a in actions)
    # The request's key decides the draw: the server holds no hidden
    # state between direct requests.
    assert not np.array_equal(actions[0], actions[1])


class TestServingAssets:
  """The export→fleet serving contract: the exporter ships its
  recommended bucket table in the asset payload; the SavedModel
  predictor surfaces it."""

  def test_serving_metadata_round_trips_through_export(self, tmp_path):
    from tensor2robot_tpu.export import SavedModelExportGenerator
    from tensor2robot_tpu.predictors import SavedModelPredictor

    model = MockT2RModel()
    state = model.create_inference_state(jax.random.PRNGKey(0))
    model_dir = str(tmp_path)
    SavedModelExportGenerator(serving_max_batch=8).export(
        model, jax.device_get(state), model_dir)
    predictor = SavedModelPredictor(
        str(tmp_path / "export"))
    assert predictor.restore(timeout_secs=0)
    meta = predictor.serving_metadata
    assert meta == {"max_batch": 8, "bucket_sizes": [1, 2, 4, 8],
                    "max_wait_us": 200}

  def test_no_metadata_without_opt_in(self, tmp_path):
    from tensor2robot_tpu.export import SavedModelExportGenerator
    from tensor2robot_tpu.predictors import SavedModelPredictor

    model = MockT2RModel()
    state = model.create_inference_state(jax.random.PRNGKey(0))
    SavedModelExportGenerator().export(
        model, jax.device_get(state), str(tmp_path))
    predictor = SavedModelPredictor(str(tmp_path / "export"))
    assert predictor.restore(timeout_secs=0)
    assert predictor.serving_metadata is None
