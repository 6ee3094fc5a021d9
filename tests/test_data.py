"""Tests for input generators, tf.Example codec, and device prefetch."""

import numpy as np
import pytest

import jax

from tensor2robot_tpu import specs
from tensor2robot_tpu.data import (
    Mode,
    RandomInputGenerator,
    ShardedPrefetcher,
    TFRecordInputGenerator,
    make_data_sharding,
    prefetch_to_mesh,
    write_tfrecord,
)
from tensor2robot_tpu.data import tfexample
from tensor2robot_tpu.specs import ExtendedTensorSpec, TensorSpecStruct


def feature_spec():
  st = TensorSpecStruct()
  st.image = ExtendedTensorSpec(shape=(12, 10, 3), dtype=np.uint8,
                                name="img", data_format="jpeg")
  st.pose = ExtendedTensorSpec(shape=(6,), dtype=np.float32, name="pose")
  st.count = ExtendedTensorSpec(shape=(1,), dtype=np.int64, name="count")
  return st


def label_spec():
  st = TensorSpecStruct()
  st.target = ExtendedTensorSpec(shape=(2,), dtype=np.float32,
                                 name="target")
  return st


class FakeModel:
  preprocessor = None

  def get_feature_specification(self, mode):
    return feature_spec()

  def get_label_specification(self, mode):
    return label_spec()


class TestRandomInputGenerator:

  def test_yields_conforming_batches(self):
    gen = RandomInputGenerator(batch_size=4)
    gen.set_specification_from_model(FakeModel(), Mode.TRAIN)
    it = gen.create_dataset(Mode.TRAIN)
    features, labels = next(it)
    packed = specs.validate_and_pack(feature_spec(), features)
    assert packed["image"].shape == (4, 12, 10, 3)
    assert labels["target"].shape == (4, 2)

  def test_batches_differ_across_steps(self):
    gen = RandomInputGenerator(batch_size=2)
    gen.set_specification(feature_spec(), label_spec())
    it = gen.create_dataset(Mode.TRAIN)
    (f1, _), (f2, _) = next(it), next(it)
    assert not np.array_equal(f1["pose"], f2["pose"])

  def test_requires_specs(self):
    gen = RandomInputGenerator(batch_size=2)
    with pytest.raises(ValueError, match="set_specification"):
      next(gen.create_dataset(Mode.TRAIN))


class TestTFExampleCodec:

  def test_roundtrip(self):
    fs = feature_spec()
    rng = np.random.default_rng(0)
    # A smooth gradient image: jpeg-friendly, so the round-trip is tight.
    yy, xx = np.mgrid[0:12, 0:10]
    image = np.stack([yy * 20, xx * 25, (yy + xx) * 10],
                     axis=-1).astype(np.uint8)
    example = {
        "image": image,
        "pose": rng.standard_normal(6).astype(np.float32),
        "count": np.array([3], np.int64),
    }
    serialized = tfexample.encode_example(example, fs)
    batch = tfexample.parse_example_batch(
        np.array([serialized, serialized]), fs)
    assert batch["image"].shape == (2, 12, 10, 3)
    # jpeg is lossy; require close-ish pixels.
    assert np.abs(batch["image"][0].astype(int) - image.astype(int)).mean() < 8
    np.testing.assert_allclose(batch["pose"][0], example["pose"], rtol=1e-6)
    np.testing.assert_array_equal(batch["count"][1], example["count"])

  def test_png_lossless(self):
    st = TensorSpecStruct()
    st.img = ExtendedTensorSpec(shape=(8, 8, 3), dtype=np.uint8,
                                name="i", data_format="png")
    image = np.random.default_rng(1).integers(
        0, 255, (8, 8, 3), dtype=np.uint8)
    serialized = tfexample.encode_example({"img": image}, st)
    batch = tfexample.parse_example_batch(np.array([serialized]), st)
    np.testing.assert_array_equal(batch["img"][0], image)

  def test_varlen_pad_and_truncate(self):
    st = TensorSpecStruct()
    st.x = ExtendedTensorSpec(shape=(4,), dtype=np.float32, name="x",
                              varlen=True)
    import tensorflow as tf
    short = tf.train.Example(features=tf.train.Features(feature={
        "x": tf.train.Feature(float_list=tf.train.FloatList(
            value=[1.0, 2.0]))})).SerializeToString()
    long = tf.train.Example(features=tf.train.Features(feature={
        "x": tf.train.Feature(float_list=tf.train.FloatList(
            value=[1, 2, 3, 4, 5, 6]))})).SerializeToString()
    batch = tfexample.parse_example_batch(np.array([short, long]), st)
    np.testing.assert_array_equal(batch["x"][0], [1, 2, 0, 0])
    np.testing.assert_array_equal(batch["x"][1], [1, 2, 3, 4])

  def test_raw_wire_lossless_roundtrip(self):
    """data_format='raw': tensors ride as C-order bytes — exact for
    any dtype, no codec. The decode-CPU escape hatch for hosts that
    can't jpeg-decode at chip rate."""
    st = TensorSpecStruct()
    st.img = ExtendedTensorSpec(shape=(8, 8, 3), dtype=np.uint8,
                                name="i", data_format="raw")
    st.depth = ExtendedTensorSpec(shape=(4, 4), dtype=np.float32,
                                  name="d", data_format="raw")
    rng = np.random.default_rng(2)
    img = rng.integers(0, 255, (8, 8, 3), dtype=np.uint8)
    depth = rng.standard_normal((4, 4)).astype(np.float32)
    serialized = tfexample.encode_example({"img": img, "depth": depth},
                                          st)
    batch = tfexample.parse_example_batch(
        np.array([serialized, serialized]), st)
    np.testing.assert_array_equal(batch["img"][1], img)
    np.testing.assert_array_equal(batch["depth"][0], depth)

  def test_raw_wire_graph_matches_eager(self):
    import tensorflow as tf

    st = TensorSpecStruct()
    st.img = ExtendedTensorSpec(shape=(6, 5, 3), dtype=np.uint8,
                                name="i", data_format="raw")
    rng = np.random.default_rng(3)
    img = rng.integers(0, 255, (6, 5, 3), dtype=np.uint8)
    serialized = tfexample.encode_example({"img": img}, st)
    eager = tfexample.parse_example_batch(np.array([serialized]), st)
    graph = tfexample.graph_parse_example(
        tf.constant([serialized]), st)
    np.testing.assert_array_equal(np.asarray(graph["img"]),
                                  eager["img"])
    np.testing.assert_array_equal(eager["img"][0], img)

  def test_sequence_spec_rejected(self):
    st = TensorSpecStruct()
    st.x = ExtendedTensorSpec(shape=(4,), dtype=np.float32, name="x",
                              is_sequence=True)
    with pytest.raises(ValueError, match="add_sequence_length"):
      tfexample.build_feature_map(st)

  @pytest.mark.parametrize("data_format", ["raw", "png"])
  def test_sequence_spec_rejected_for_bytes_formats(self, data_format):
    """Raw/image SEQUENCE specs must hit the same SequenceExample
    error — binding one byte string per example would silently fuse
    the time axis into the wire blob."""
    st = TensorSpecStruct()
    st.x = ExtendedTensorSpec(shape=(4, 4, 3), dtype=np.uint8,
                              name="x", is_sequence=True,
                              data_format=data_format)
    with pytest.raises(ValueError, match="add_sequence_length"):
      tfexample.build_feature_map(st)

  def test_raw_wire_length_mismatch_raises_eager_and_graph(self):
    """A record written against a different raw shape must ERROR in
    both parsers — the graph path would otherwise silently fuse
    examples across the batch dim (reshape absorbs the bytes)."""
    import tensorflow as tf

    written = TensorSpecStruct()
    written.x = ExtendedTensorSpec(shape=(4,), dtype=np.uint8,
                                   name="x", data_format="raw")
    declared = TensorSpecStruct()
    declared.x = ExtendedTensorSpec(shape=(8,), dtype=np.uint8,
                                    name="x", data_format="raw")
    serialized = tfexample.encode_example(
        {"x": np.arange(4, dtype=np.uint8)}, written)
    with pytest.raises(ValueError, match="wire holds 4 bytes"):
      tfexample.parse_example_batch(
          np.array([serialized, serialized]), declared)
    with pytest.raises(Exception, match="byte lengths"):
      tfexample.graph_parse_example(
          tf.constant([serialized, serialized]), declared)

  def test_missing_required_feature_raises(self):
    with pytest.raises(ValueError, match="pose"):
      tfexample.encode_example({"image": np.zeros((12, 10, 3), np.uint8),
                                "count": np.zeros((1,), np.int64)},
                               feature_spec())


def episode_spec():
  st = TensorSpecStruct()
  st.image = ExtendedTensorSpec(shape=(8, 8, 3), dtype=np.uint8,
                                name="frame", data_format="png",
                                is_sequence=True)
  st.state = ExtendedTensorSpec(shape=(3,), dtype=np.float32,
                                name="state", is_sequence=True)
  st.task_id = ExtendedTensorSpec(shape=(1,), dtype=np.int64,
                                  name="task_id")
  return st


def episode_label_spec():
  st = TensorSpecStruct()
  st.action = ExtendedTensorSpec(shape=(2,), dtype=np.float32,
                                 name="action", is_sequence=True)
  return st


def make_episode(rng, t):
  return {
      "image": rng.integers(0, 255, (t, 8, 8, 3), dtype=np.uint8),
      "state": rng.standard_normal((t, 3)).astype(np.float32),
      "task_id": np.array([7], np.int64),
      "action": rng.standard_normal((t, 2)).astype(np.float32),
  }


class TestGraphParsers:
  """The tf.data-graph parsers must match the eager parsers exactly.

  These are the production path (parse + image decode inside
  `dataset.map(num_parallel_calls=AUTOTUNE)`, SURVEY §4.3) and the body
  of the exported parse_tf_example signature; the eager parsers are the
  contract they are tested against.
  """

  def _example_batch(self):
    fs = feature_spec()
    rng = np.random.default_rng(0)
    yy, xx = np.mgrid[0:12, 0:10]
    examples = []
    for i in range(3):
      examples.append({
          "image": np.stack([yy * 2 * (i + 1), xx * 3, (yy + xx) * i],
                            axis=-1).astype(np.uint8),
          "pose": rng.standard_normal(6).astype(np.float32),
          "count": np.array([i], np.int64),
      })
    serialized = np.array(
        [tfexample.encode_example(e, fs) for e in examples],
        dtype=object)
    return fs, serialized

  def test_example_graph_matches_eager(self):
    import tensorflow as tf
    fs, serialized = self._example_batch()
    eager = tfexample.parse_example_batch(serialized, fs)
    graph = tf.function(
        lambda s: tfexample.graph_parse_example(s, fs))(
            tf.convert_to_tensor(serialized))
    for key, value in eager.to_flat_dict().items():
      got = np.asarray(graph[key])
      assert got.dtype == value.dtype, key
      np.testing.assert_array_equal(got, value, err_msg=key)

  def test_example_graph_varlen(self):
    import tensorflow as tf
    st = TensorSpecStruct()
    st.x = ExtendedTensorSpec(shape=(4,), dtype=np.float32, name="x",
                              varlen=True)
    short = tf.train.Example(features=tf.train.Features(feature={
        "x": tf.train.Feature(float_list=tf.train.FloatList(
            value=[1.0, 2.0]))})).SerializeToString()
    long = tf.train.Example(features=tf.train.Features(feature={
        "x": tf.train.Feature(float_list=tf.train.FloatList(
            value=[1, 2, 3, 4, 5, 6]))})).SerializeToString()
    graph = tf.function(
        lambda s: tfexample.graph_parse_example(s, st))(
            tf.convert_to_tensor(np.array([short, long])))
    np.testing.assert_array_equal(np.asarray(graph["x"]),
                                  [[1, 2, 0, 0], [1, 2, 3, 4]])

  def test_sequence_graph_matches_eager(self):
    import tensorflow as tf
    st = TensorSpecStruct()
    st.frames = ExtendedTensorSpec(
        shape=(6, 5, 3), dtype=np.uint8, name="frames",
        data_format="png", is_sequence=True)
    st.action = ExtendedTensorSpec(
        shape=(2,), dtype=np.float32, name="act", is_sequence=True)
    st.task_id = ExtendedTensorSpec(shape=(1,), dtype=np.int64,
                                    name="task")
    rng = np.random.default_rng(2)
    episodes = []
    for t in (2, 5):  # ragged: one under, one over sequence_length=4
      episodes.append({
          "frames": rng.integers(0, 255, (t, 6, 5, 3)).astype(np.uint8),
          "action": rng.standard_normal((t, 2)).astype(np.float32),
          "task_id": np.array([t], np.int64),
      })
    serialized = np.array([
        tfexample.encode_sequence_example(e, st) for e in episodes],
        dtype=object)
    eager = tfexample.parse_sequence_example_batch(serialized, st, 4)
    graph = tf.function(
        lambda s: tfexample.graph_parse_sequence_example(s, st, 4))(
            tf.convert_to_tensor(serialized))
    for key, value in eager.to_flat_dict().items():
      got = np.asarray(graph[key])
      assert got.shape == value.shape, key
      np.testing.assert_array_equal(got, value, err_msg=key)
    np.testing.assert_array_equal(
        np.asarray(graph[tfexample.SEQUENCE_LENGTH_KEY]), [2, 4])

  def test_pipeline_feeds_faster_than_chip(self, tmp_path):
    """Throughput microbench: host pipeline vs the measured step rate.

    A single-host tf.data pipeline can't match a chip's
    64-image-per-example rate on shared CI hardware, so the assertion
    here is a sanity floor and no device metric — the number is
    printed for the record. Run on a production host, the
    AUTOTUNE-parallel decode path is the one that scales with cores;
    the old eager path was single-threaded.
    """
    import time
    fs = feature_spec()
    rng = np.random.default_rng(0)
    examples = [{
        "image": rng.integers(0, 255, (12, 10, 3)).astype(np.uint8),
        "pose": rng.standard_normal(6).astype(np.float32),
        "count": np.array([1], np.int64),
        "target": rng.standard_normal(2).astype(np.float32),
    } for _ in range(256)]
    path = str(tmp_path / "bench.tfrecord")
    write_tfrecord(path, examples, fs, label_spec())
    gen = TFRecordInputGenerator(file_patterns=path, batch_size=64,
                                 shuffle_buffer_size=256, seed=0)
    gen.set_specification(fs, label_spec())
    it = gen.create_dataset(Mode.TRAIN)
    next(it)  # warm the pipeline
    n = 30
    t0 = time.perf_counter()
    for _ in range(n):
      next(it)
    rate = n / (time.perf_counter() - t0)
    print(f"\npipeline: {rate:.1f} batches/s (batch=64, jpeg decode)")
    assert rate > 5.0  # sanity floor; single-threaded eager was ~this


class TestSequenceExampleCodec:

  def test_roundtrip_pads_and_reports_lengths(self):
    fs = episode_spec()
    rng = np.random.default_rng(0)
    ep_short = make_episode(rng, 3)
    ep_long = make_episode(rng, 6)
    serialized = np.array([
        tfexample.encode_sequence_example(ep_short, fs),
        tfexample.encode_sequence_example(ep_long, fs),
    ])
    batch = tfexample.parse_sequence_example_batch(
        serialized, fs, sequence_length=4)
    # Static [B, T, ...] shapes with zero padding / truncation.
    assert batch["image"].shape == (2, 4, 8, 8, 3)
    assert batch["state"].shape == (2, 4, 3)
    assert batch["task_id"].shape == (2, 1)
    np.testing.assert_array_equal(
        batch[tfexample.SEQUENCE_LENGTH_KEY], [3, 4])
    # png is lossless: frames round-trip exactly; padding is zeros.
    np.testing.assert_array_equal(batch["image"][0, :3],
                                  ep_short["image"])
    np.testing.assert_array_equal(batch["image"][0, 3],
                                  np.zeros((8, 8, 3), np.uint8))
    np.testing.assert_allclose(batch["state"][1], ep_long["state"][:4],
                               rtol=1e-6)
    np.testing.assert_array_equal(batch["task_id"][1], [7])

  def test_raw_sequence_roundtrip_eager_and_graph(self):
    """Raw frames in episodes: exact round-trip, zero time padding,
    and graph/eager parity (the graph path zero-fills '' padding via
    decode_raw's fixed_length)."""
    import tensorflow as tf

    st = TensorSpecStruct()
    st.image = ExtendedTensorSpec(shape=(8, 8, 3), dtype=np.uint8,
                                  name="frame", data_format="raw",
                                  is_sequence=True)
    st.goal = ExtendedTensorSpec(shape=(2,), dtype=np.float32,
                                 name="goal", data_format="raw")
    rng = np.random.default_rng(5)
    ep = {
        "image": rng.integers(0, 255, (3, 8, 8, 3), dtype=np.uint8),
        "goal": rng.standard_normal(2).astype(np.float32),
    }
    serialized = np.array([tfexample.encode_sequence_example(ep, st)])
    eager = tfexample.parse_sequence_example_batch(
        serialized, st, sequence_length=4)
    np.testing.assert_array_equal(eager["image"][0, :3], ep["image"])
    np.testing.assert_array_equal(eager["image"][0, 3],
                                  np.zeros((8, 8, 3), np.uint8))
    np.testing.assert_array_equal(eager["goal"][0], ep["goal"])
    graph = tfexample.graph_parse_sequence_example(
        tf.constant(serialized), st, sequence_length=4)
    np.testing.assert_array_equal(np.asarray(graph["image"]),
                                  eager["image"])
    np.testing.assert_array_equal(np.asarray(graph["goal"]),
                                  eager["goal"])

  def test_raw_sequence_frame_length_mismatch_raises_in_graph(self):
    """Mismatched raw frames must error in the graph parser too —
    fixed_length would otherwise zero-fill/truncate them into
    plausible garbage ('' time padding stays allowed)."""
    import tensorflow as tf

    written = TensorSpecStruct()
    written.f = ExtendedTensorSpec(shape=(4,), dtype=np.uint8,
                                   name="f", data_format="raw",
                                   is_sequence=True)
    declared = TensorSpecStruct()
    declared.f = ExtendedTensorSpec(shape=(8,), dtype=np.uint8,
                                    name="f", data_format="raw",
                                    is_sequence=True)
    serialized = np.array([tfexample.encode_sequence_example(
        {"f": np.arange(8, dtype=np.uint8).reshape(2, 4)}, written)])
    with pytest.raises(Exception, match="byte lengths"):
      np.asarray(tfexample.graph_parse_sequence_example(
          tf.constant(serialized), declared, sequence_length=3)["f"])
    with pytest.raises(ValueError, match="wire holds 4 bytes"):
      tfexample.parse_sequence_example_batch(serialized, declared,
                                             sequence_length=3)

  def test_mismatched_sequence_lengths_rejected(self):
    fs = episode_spec()
    rng = np.random.default_rng(1)
    ep = make_episode(rng, 3)
    ep["state"] = ep["state"][:2]
    with pytest.raises(ValueError, match="share a length"):
      tfexample.encode_sequence_example(ep, fs)

  def test_missing_required_sequence_feature_raises(self):
    fs = episode_spec()
    with pytest.raises(ValueError, match="state"):
      tfexample.encode_sequence_example(
          {"image": np.zeros((2, 8, 8, 3), np.uint8),
           "task_id": np.array([0], np.int64)}, fs)


class TestEpisodeGenerator:

  def test_end_to_end(self, tmp_path):
    from tensor2robot_tpu.data import (
        TFRecordEpisodeInputGenerator,
        write_episode_tfrecord,
    )
    fs, ls = episode_spec(), episode_label_spec()
    rng = np.random.default_rng(0)
    episodes = [make_episode(rng, t) for t in [3, 5, 4, 6]]
    path = str(tmp_path / "episodes.tfrecord")
    write_episode_tfrecord(path, episodes, fs, ls)

    gen = TFRecordEpisodeInputGenerator(
        file_patterns=path, batch_size=2, sequence_length=5,
        shuffle=False)
    gen.set_specification(fs, ls)
    features, labels = next(gen.create_dataset(Mode.TRAIN))
    assert features["image"].shape == (2, 5, 8, 8, 3)
    assert features["state"].shape == (2, 5, 3)
    assert features["task_id"].shape == (2, 1)
    np.testing.assert_array_equal(features["sequence_length"], [3, 5])
    assert labels["action"].shape == (2, 5, 2)

  def test_meta_batch_from_episodes(self):
    from tensor2robot_tpu.meta_learning import meta_batch_from_episodes
    rng = np.random.default_rng(0)
    features = TensorSpecStruct.from_flat_dict({
        "state": rng.standard_normal((2, 6, 3)).astype(np.float32),
        "sequence_length": np.array([6, 6], np.int32),
    })
    labels = TensorSpecStruct.from_flat_dict({
        "action": rng.standard_normal((2, 6, 2)).astype(np.float32)})
    mf, ml = meta_batch_from_episodes(features, labels,
                                      num_condition=4, num_inference=2)
    assert mf["condition/state"].shape == (2, 4, 3)
    assert mf["inference/state"].shape == (2, 2, 3)
    assert "sequence_length" not in mf
    assert ml["condition/action"].shape == (2, 4, 2)
    np.testing.assert_array_equal(
        mf["inference/state"],
        np.asarray(features["state"])[:, 4:6])

  def test_too_short_episode_raises(self):
    from tensor2robot_tpu.meta_learning import meta_batch_from_episodes
    features = TensorSpecStruct.from_flat_dict({
        "state": np.zeros((2, 3, 3), np.float32)})
    with pytest.raises(ValueError, match="time"):
      meta_batch_from_episodes(features, None, num_condition=4,
                               num_inference=2)

  def test_padded_short_episode_dropped_via_true_lengths(self):
    # A zero-padded [B, 16, ...] batch LOOKS long enough; the true
    # lengths say otherwise: short episodes are dropped (ragged real
    # datasets must not abort the iterator), all-short raises.
    from tensor2robot_tpu.meta_learning import meta_batch_from_episodes
    state = np.zeros((2, 16, 3), np.float32)
    state[1] = 7.0
    features = TensorSpecStruct.from_flat_dict({
        "state": state,
        "sequence_length": np.array([3, 16], np.int32)})
    mf, _ = meta_batch_from_episodes(features, None, num_condition=4,
                                     num_inference=4)
    assert mf["condition/state"].shape == (1, 4, 3)
    np.testing.assert_array_equal(mf["condition/state"],
                                  state[1:2, :4])
    all_short = TensorSpecStruct.from_flat_dict({
        "state": np.zeros((2, 16, 3), np.float32),
        "sequence_length": np.array([3, 5], np.int32)})
    with pytest.raises(ValueError, match="zero padding"):
      meta_batch_from_episodes(all_short, None, num_condition=4,
                               num_inference=4)

  def test_meta_generator_constant_task_dim_under_raggedness(self):
    # Ragged datasets must not shrink the task dim (every distinct task
    # count would retrace the jitted step) nor abort on an all-short
    # batch: the generator buffers surviving episodes across batches.
    from tensor2robot_tpu.meta_learning import EpisodeMetaInputGenerator
    from tensor2robot_tpu.data.abstract_input_generator import (
        AbstractInputGenerator,
    )

    spec = TensorSpecStruct.from_flat_dict({
        "state": ExtendedTensorSpec(shape=(3,), dtype=np.float32,
                                    name="state", is_sequence=True)})

    class RaggedEpisodes(AbstractInputGenerator):
      # Batches of 2 episodes with true lengths cycling through a
      # pattern that includes an ALL-short batch.
      lengths = [(8, 3), (2, 2), (8, 8), (3, 8)]

      def _create_dataset(self, mode, batch_size):
        i = 0
        while True:
          lens = self.lengths[i % len(self.lengths)]
          i += 1
          yield (TensorSpecStruct.from_flat_dict({
              "state": np.full((2, 8, 3), i, np.float32),
              "sequence_length": np.array(lens, np.int32)}), None)

    inner = RaggedEpisodes()
    inner.set_specification(spec)
    gen = EpisodeMetaInputGenerator(
        inner, num_condition_samples_per_task=4,
        num_inference_samples_per_task=4, batch_size=2)
    gen.set_specification(spec)
    it = gen.create_dataset(Mode.TRAIN, batch_size=2)
    shapes = [next(it)[0]["condition/state"].shape for _ in range(4)]
    assert shapes == [(2, 4, 3)] * 4

  def test_context_keys_tiled_not_sliced(self):
    from tensor2robot_tpu.meta_learning import meta_batch_from_episodes
    goal = np.arange(20, dtype=np.float32).reshape(2, 10)
    features = TensorSpecStruct.from_flat_dict({
        "state": np.zeros((2, 8, 3), np.float32),
        "goal": goal})
    mf, _ = meta_batch_from_episodes(features, None, num_condition=4,
                                     num_inference=2,
                                     context_keys=("goal",))
    assert mf["condition/goal"].shape == (2, 4, 10)
    assert mf["inference/goal"].shape == (2, 2, 10)
    np.testing.assert_array_equal(mf["condition/goal"][:, 0], goal)
    np.testing.assert_array_equal(mf["condition/goal"][:, 3], goal)

  def test_reserved_sequence_length_spec_key_rejected(self):
    st = TensorSpecStruct()
    st.x = ExtendedTensorSpec(shape=(2,), dtype=np.float32, name="x",
                              is_sequence=True)
    st.sequence_length = ExtendedTensorSpec(shape=(1,), dtype=np.int64,
                                            name="seq_len")
    with pytest.raises(ValueError, match="reserved"):
      tfexample.parse_sequence_example_batch(
          np.array([b""]), st, sequence_length=2)


class TestTFRecordGenerator:

  def test_end_to_end(self, tmp_path):
    fs, ls = feature_spec(), label_spec()
    rng = np.random.default_rng(0)
    examples = []
    for _ in range(8):
      examples.append({
          "image": rng.integers(0, 255, (12, 10, 3), dtype=np.uint8),
          "pose": rng.standard_normal(6).astype(np.float32),
          "count": np.array([1], np.int64),
          "target": rng.standard_normal(2).astype(np.float32),
      })
    path = str(tmp_path / "data.tfrecord")
    write_tfrecord(path, examples, fs, ls)

    gen = TFRecordInputGenerator(file_patterns=path, batch_size=4,
                                 shuffle=False, seed=0)
    gen.set_specification(fs, ls)
    features, labels = next(gen.create_dataset(Mode.TRAIN))
    assert features["image"].shape == (4, 12, 10, 3)
    assert labels["target"].shape == (4, 2)
    specs.validate_and_pack(fs, features)

  def test_eval_mode_finite(self, tmp_path):
    fs = TensorSpecStruct()
    fs.x = ExtendedTensorSpec(shape=(2,), dtype=np.float32, name="x")
    examples = [{"x": np.ones(2, np.float32)} for _ in range(6)]
    path = str(tmp_path / "d.tfrecord")
    write_tfrecord(path, examples, fs)
    gen = TFRecordInputGenerator(file_patterns=path, batch_size=2,
                                 shuffle=False)
    gen.set_specification(fs)
    batches = list(gen.create_dataset(Mode.EVAL))
    assert len(batches) == 3

  def test_no_files_raises(self):
    gen = TFRecordInputGenerator(file_patterns="/nonexistent/*.tfrecord",
                                 batch_size=2)
    gen.set_specification(feature_spec())
    with pytest.raises(ValueError, match="No TFRecord files"):
      next(gen.create_dataset(Mode.TRAIN))


class TestPrefetch:

  def test_sharded_prefetch_over_mesh(self):
    devices = jax.devices()
    assert len(devices) == 8, "conftest must provide 8 virtual devices"
    mesh = jax.sharding.Mesh(np.array(devices), ("data",))
    gen = RandomInputGenerator(batch_size=16)
    gen.set_specification(feature_spec(), label_spec())
    prefetcher = prefetch_to_mesh(
        gen.create_dataset(Mode.TRAIN), mesh, buffer_size=2)
    features, labels = next(iter(prefetcher))
    assert isinstance(features["pose"], jax.Array)
    assert features["pose"].shape == (16, 6)
    # Batch axis is sharded 8 ways.
    assert len(features["pose"].sharding.device_set) == 8
    shard_shapes = {s.data.shape for s in features["pose"].addressable_shards}
    assert shard_shapes == {(2, 6)}
    assert labels["target"].shape == (16, 2)

  def test_error_propagates(self):
    def bad_iterator():
      yield {"x": np.zeros((8, 2), np.float32)}
      raise RuntimeError("boom")

    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    prefetcher = ShardedPrefetcher(
        bad_iterator(), make_data_sharding(mesh), buffer_size=1)
    it = iter(prefetcher)
    next(it)
    with pytest.raises(RuntimeError, match="boom"):
      next(it)

  def test_finite_iterator_stops(self):
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    data = iter([{"x": np.zeros((8, 2), np.float32)}] * 3)
    prefetcher = ShardedPrefetcher(data, make_data_sharding(mesh))
    assert len(list(prefetcher)) == 3

  def test_slow_consumer_still_sees_all_items_and_sentinel(self):
    # Regression: the done-sentinel must not be dropped when the queue
    # is full at iterator exhaustion (deadlocked the consumer).
    import time
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    data = iter([{"x": np.zeros((8, 2), np.float32)}] * 5)
    prefetcher = ShardedPrefetcher(data, make_data_sharding(mesh),
                                   buffer_size=1)
    time.sleep(0.5)  # let the worker fill the queue and finish
    assert len(list(prefetcher)) == 5

  def test_close_unblocks_abandoned_stream(self):
    # Infinite generator; consumer abandons after 1 batch; close() must
    # terminate the worker thread.
    def infinite():
      while True:
        yield {"x": np.zeros((8, 2), np.float32)}

    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    prefetcher = ShardedPrefetcher(infinite(), make_data_sharding(mesh),
                                   buffer_size=2)
    next(iter(prefetcher))
    prefetcher.close()
    assert not prefetcher._thread.is_alive()


class TestStackBatches:
  """The steps_per_dispatch host-side stacker (data/prefetch.py)."""

  def test_groups_k_batches(self):
    from tensor2robot_tpu.data.prefetch import stack_batches

    stream = ({"x": np.full((2, 3), i, np.float32)} for i in range(6))
    stacks = list(stack_batches(stream, 3))
    assert len(stacks) == 2
    assert stacks[0]["x"].shape == (3, 2, 3)
    np.testing.assert_array_equal(stacks[1]["x"][:, 0, 0], [3, 4, 5])

  def test_finite_stream_ends_cleanly_mid_stack(self):
    """PEP 479 guard: the inner StopIteration must NOT surface as a
    RuntimeError — a finite input stream ends the run cleanly (the
    trainer's final off-interval checkpoint depends on it)."""
    from tensor2robot_tpu.data.prefetch import stack_batches

    stream = ({"x": np.zeros((2,), np.float32)} for _ in range(5))
    stacks = list(stack_batches(stream, 2))  # 5 = 2 stacks + 1 dropped
    assert len(stacks) == 2
