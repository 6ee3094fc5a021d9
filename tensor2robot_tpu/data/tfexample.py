"""Spec-derived tf.Example encoding/decoding (the TFExampleDecoder role).

Reference parity: tensor2robot derived `tf.parse_example` feature maps
mechanically from `ExtendedTensorSpec`s, including jpeg-encoded image
decode (SURVEY.md §3 "TFExampleDecoding"; file:line unavailable).

TensorFlow is used host-side only, purely as a record/proto parsing
library — the parsed output is numpy, which then flows into the JAX
device pipeline. All TF imports are lazy so the core framework works
without TF (TFRecord IO is then unavailable, random generators still
work).
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from tensor2robot_tpu import specs
from tensor2robot_tpu.specs import ExtendedTensorSpec, TensorSpecStruct


def _tf():
  import tensorflow as tf  # lazy: host-side IO only
  return tf


def wire_key(key: str, spec: ExtendedTensorSpec) -> str:
  """The on-disk feature key for a spec: explicit name, else flat path."""
  return spec.name or key


def _is_raw(spec: ExtendedTensorSpec) -> bool:
  """Raw-bytes wire: one bytes feature holding the C-order array.

  `data_format="raw"` trades disk for host CPU — parse is a near-memcpy
  `decode_raw` instead of a jpeg/png codec, which is what lets a
  few-core host keep up with a chip (the decode path is the feed's
  bottleneck on such a host; no cell of BENCHMARK.json reads files).
  Byte order is little-endian (every supported platform; decode_raw's
  default).
  """
  return spec.data_format == "raw"


def build_feature_map(feature_spec: Any) -> Dict[str, Any]:
  """Derives the tf.io.parse_example feature map from a spec structure."""
  tf = _tf()
  flat = specs.flatten_spec_structure(feature_spec).to_flat_dict()
  feature_map: Dict[str, Any] = {}
  for key, spec in flat.items():
    name = wire_key(key, spec)
    # The sequence guard comes FIRST: image/raw sequence specs must
    # hit the clear SequenceExample error too, not silently bind one
    # byte string per example (which would fuse the time axis into
    # the wire blob).
    if spec.is_sequence:
      raise ValueError(
          f"Sequence spec {name!r} cannot be bound to a tf.Example wire "
          f"directly; episode data travels as tf.SequenceExample — use "
          f"parse_sequence_example_batch / encode_sequence_example — or "
          f"materialize a fixed length first via "
          f"specs.add_sequence_length (XLA needs static shapes).")
    if spec.is_image or _is_raw(spec):
      # Encoded images / raw array bytes travel as one byte string.
      feature_map[name] = tf.io.FixedLenFeature([], tf.string)
      continue
    dtype = np.dtype(spec.dtype)
    if dtype.kind == "f" or spec.dtype.name == "bfloat16":
      tf_dtype = tf.float32
    elif dtype.kind in ("i", "u", "b"):
      tf_dtype = tf.int64
    else:
      raise ValueError(f"Unsupported spec dtype for tf.Example: {dtype}")
    if spec.varlen:
      # Ragged on the wire; padded/truncated to the static shape at parse
      # time.
      feature_map[name] = tf.io.VarLenFeature(tf_dtype)
    else:
      feature_map[name] = tf.io.FixedLenFeature(
          [int(np.prod(spec.shape))], tf_dtype)
  return feature_map


def decode_image_bytes(data: bytes) -> np.ndarray:
  """Decodes a jpeg/png byte string to an HWC uint8 numpy array."""
  tf = _tf()
  return tf.io.decode_image(data, expand_animations=False).numpy()


def parse_example_batch(
    serialized: Any,
    feature_spec: Any,
) -> TensorSpecStruct:
  """Parses a batch of serialized tf.Example protos into numpy arrays.

  Returns a flat TensorSpecStruct keyed like the spec structure, each
  leaf a [batch] + spec.shape array of spec.dtype. Encoded images are
  decoded and shape-checked; varlen features are zero-padded/truncated
  to the declared static shape (XLA requires static shapes).
  """
  tf = _tf()
  flat = specs.flatten_spec_structure(feature_spec).to_flat_dict()
  feature_map = build_feature_map(feature_spec)
  try:
    parsed = tf.io.parse_example(serialized, feature_map)
  except Exception as e:  # surface the spec contract, not TF internals
    raise ValueError(
        f"tf.Example parse failed against the declared specs "
        f"(wire keys: {sorted(feature_map)}). Most often a record is "
        f"missing a required key or has the wrong length. "
        f"Underlying error: {e}") from e
  batch_size = int(np.asarray(serialized).shape[0])

  out: Dict[str, np.ndarray] = {}
  for key, spec in flat.items():
    name = wire_key(key, spec)
    value = parsed[name]
    if spec.is_image:
      images = np.stack([
          _fit_image(decode_image_bytes(b), spec)
          for b in value.numpy()])
      out[key] = images.astype(spec.dtype)
      continue
    if _is_raw(spec):
      out[key] = np.stack([
          _fit_raw(b, spec, key) for b in value.numpy()])
      continue
    if spec.varlen:
      dense = tf.sparse.to_dense(value).numpy()
      out[key] = _pad_or_truncate(dense, spec, batch_size)
      continue
    arr = value.numpy().reshape((batch_size,) + tuple(spec.shape))
    out[key] = arr.astype(spec.dtype)
  return TensorSpecStruct.from_flat_dict(out)


def _fit_raw(data: bytes, spec: ExtendedTensorSpec,
             key: str) -> np.ndarray:
  """Decodes one raw-wire byte string, naming the spec on mismatch."""
  dtype = np.dtype(spec.dtype)
  expected = int(np.prod(spec.shape)) * dtype.itemsize
  if len(data) != expected:
    raise ValueError(
        f"Raw feature {key!r}: wire holds {len(data)} bytes but spec "
        f"{tuple(spec.shape)} {dtype.name} needs {expected}. The "
        f"record was written against a different shape/dtype.")
  return np.frombuffer(data, dtype).reshape(spec.shape)


def _fit_image(image: np.ndarray, spec: ExtendedTensorSpec) -> np.ndarray:
  expected = tuple(spec.shape)
  if image.shape == expected:
    return image
  if image.ndim == 2 and len(expected) == 3 and expected[-1] == 1:
    image = image[..., None]
  if image.shape != expected:
    raise ValueError(
        f"Decoded image shape {image.shape} does not match spec "
        f"{expected} for {spec.name!r}. Resize at dataset-build time or "
        f"declare the true decoded shape.")
  return image


def _pad_or_truncate(
    dense: np.ndarray, spec: ExtendedTensorSpec, batch_size: int,
) -> np.ndarray:
  """Pads/truncates the ragged-densified axis to the declared shape."""
  target = (batch_size,) + tuple(spec.shape)
  flat_len = int(np.prod(spec.shape))
  if dense.ndim != 2:
    dense = dense.reshape(batch_size, -1)
  cur = dense.shape[1]
  if cur < flat_len:
    dense = np.pad(dense, ((0, 0), (0, flat_len - cur)))
  elif cur > flat_len:
    dense = dense[:, :flat_len]
  return dense.reshape(target).astype(spec.dtype)


def _graph_dtype(tf, spec):
  name = ("bfloat16" if str(spec.dtype) == "bfloat16"
          else np.dtype(spec.dtype).name)
  return getattr(tf, name)


def _graph_decode_raw(tf, value, spec, key, allow_empty=False):
  """decode_raw with the eager parser's byte-length contract in-graph.

  Without the assert, a size-mismatched record would silently fuse
  examples across the batch dimension (reshape absorbs the extra
  bytes) or, under fixed_length, be truncated/zero-filled into
  plausible-looking garbage. `allow_empty` admits the "" time padding
  of SequenceExample frames (zero-filled via fixed_length).
  """
  nbytes = int(np.prod(spec.shape)) * np.dtype(spec.dtype).itemsize
  lengths = tf.strings.length(value)
  ok = tf.equal(lengths, nbytes)
  if allow_empty:
    ok = tf.logical_or(ok, tf.equal(lengths, 0))
  with tf.control_dependencies([
      tf.debugging.Assert(tf.reduce_all(ok), [
          f"Raw feature {key!r}: wire byte lengths do not match spec "
          f"{tuple(spec.shape)} {np.dtype(spec.dtype).name} "
          f"({nbytes} bytes). Lengths seen:", lengths])]):
    return tf.io.decode_raw(value, _graph_dtype(tf, spec),
                            fixed_length=nbytes)


def _graph_decode_image(tf, encoded, spec):
  """Decodes a [N] string tensor of encoded frames inside the TF graph.

  Empty strings (SequenceExample padding) decode to zeros, matching the
  eager parser's zero-padded frames.
  """
  height, width, channels = spec.shape[-3], spec.shape[-2], spec.shape[-1]

  def decode_one(data):
    def real():
      image = tf.io.decode_image(data, channels=channels,
                                 expand_animations=False)
      return tf.reshape(image, [height, width, channels])
    return tf.cond(
        tf.strings.length(data) > 0, real,
        lambda: tf.zeros([height, width, channels], tf.uint8))

  return tf.map_fn(decode_one, encoded, fn_output_signature=tf.uint8)


def graph_parse_example(serialized, feature_spec) -> Dict[str, Any]:
  """Parses a [B] string tensor of tf.Examples ENTIRELY in TF graph ops.

  The graph twin of `parse_example_batch`: same spec contract (image
  decode, varlen pad/truncate, static shapes), but traceable — so
  `dataset.map(parse_fn, num_parallel_calls=AUTOTUNE)` runs parse AND
  image decode in tf.data's parallel threadpool (the reference's
  hot-loop shape, SURVEY.md §4.3) instead of single-threaded eager
  python. Also the body of the exported `parse_tf_example` signature,
  keeping training-side and serving-side parsers one implementation.
  """
  tf = _tf()
  flat = specs.flatten_spec_structure(feature_spec).to_flat_dict()
  feature_map = build_feature_map(feature_spec)
  parsed = tf.io.parse_example(serialized, feature_map)
  out: Dict[str, Any] = {}
  for key, spec in flat.items():
    name = wire_key(key, spec)
    value = parsed[name]
    if spec.is_image:
      images = _graph_decode_image(tf, value, spec)
      out[key] = tf.cast(images, _graph_dtype(tf, spec))
      continue
    if _is_raw(spec):
      decoded = _graph_decode_raw(tf, value, spec, key)
      out[key] = tf.reshape(decoded, [-1] + list(spec.shape))
      continue
    if isinstance(value, tf.sparse.SparseTensor):
      value = tf.sparse.to_dense(value)
    if spec.varlen:
      # Parity with the eager parser's _pad_or_truncate: ragged wire
      # data is zero-padded / truncated to the declared static length.
      flat_len = int(np.prod(spec.shape))
      value = tf.reshape(value, [tf.shape(value)[0], -1])
      cur = tf.shape(value)[1]
      value = tf.cond(
          cur < flat_len,
          lambda: tf.pad(value, [[0, 0], [0, flat_len - cur]]),
          lambda: value[:, :flat_len])
    value = tf.reshape(value, [-1] + list(spec.shape))
    out[key] = tf.cast(value, _graph_dtype(tf, spec))
  return out


def graph_parse_sequence_example(serialized, feature_spec,
                                 sequence_length: int) -> Dict[str, Any]:
  """Graph twin of `parse_sequence_example_batch` (same contract).

  Sequence keys come back [B, sequence_length, ...] zero-padded /
  truncated, context keys [B, ...], true pre-pad lengths (clipped)
  under SEQUENCE_LENGTH_KEY — all as TF ops, so episode pipelines
  (per-frame image decode included) parallelize under tf.data.
  """
  tf = _tf()
  flat = specs.flatten_spec_structure(feature_spec).to_flat_dict()
  if SEQUENCE_LENGTH_KEY in flat:
    raise ValueError(
        f"Spec key {SEQUENCE_LENGTH_KEY!r} is reserved: the parser "
        f"emits the true episode lengths under it. Rename the feature.")
  context_map, sequence_map = build_sequence_feature_maps(feature_spec)
  context, parsed_seq, seq_lengths = tf.io.parse_sequence_example(
      serialized, context_features=context_map or None,
      sequence_features=sequence_map)
  batch = tf.shape(serialized)[0]

  def fit_time(value):
    """Pads/truncates the time axis (axis 1) to sequence_length."""
    t = tf.shape(value)[1]
    value = value[:, :sequence_length]
    pad = [[0, 0], [0, tf.maximum(0, sequence_length - t)]] + \
        [[0, 0]] * (value.shape.ndims - 2)
    return tf.pad(value, pad)

  out: Dict[str, Any] = {}
  true_lengths = tf.zeros([batch], tf.int32)
  for key, spec in flat.items():
    name = wire_key(key, spec)
    if not spec.is_sequence:
      value = context[name]
      if isinstance(value, tf.sparse.SparseTensor):
        value = tf.sparse.to_dense(value)
      if spec.is_image:
        out[key] = tf.cast(
            _graph_decode_image(tf, value, spec),
            _graph_dtype(tf, spec))
      elif _is_raw(spec):
        out[key] = tf.reshape(
            _graph_decode_raw(tf, value, spec, key),
            [-1] + list(spec.shape))
      elif spec.varlen:
        flat_len = int(np.prod(spec.shape))
        value = tf.reshape(value, [batch, -1])
        cur = tf.shape(value)[1]
        value = tf.cond(
            cur < flat_len,
            lambda: tf.pad(value, [[0, 0], [0, flat_len - cur]]),
            lambda: value[:, :flat_len])
        out[key] = tf.cast(
            tf.reshape(value, [-1] + list(spec.shape)),
            _graph_dtype(tf, spec))
      else:
        out[key] = tf.cast(
            tf.reshape(value, [-1] + list(spec.shape)),
            _graph_dtype(tf, spec))
      continue

    value = parsed_seq[name]
    if isinstance(value, tf.RaggedTensor):
      value = value.to_tensor()
    if isinstance(value, tf.sparse.SparseTensor):
      value = tf.sparse.to_dense(value)
    lengths = tf.cast(tf.reshape(seq_lengths[name], [batch]), tf.int32)
    true_lengths = tf.maximum(
        true_lengths, tf.minimum(lengths, sequence_length))
    if spec.is_image:
      # [B, T] encoded strings -> pad/trunc T -> decode all frames in
      # one flattened map_fn ("" pads decode to zero frames).
      frames = fit_time(value)
      flat_frames = tf.reshape(frames, [-1])
      decoded = _graph_decode_image(tf, flat_frames, spec)
      decoded = tf.reshape(
          decoded, [-1, sequence_length] + list(spec.shape))
      out[key] = tf.cast(decoded, _graph_dtype(tf, spec))
      continue
    if _is_raw(spec):
      # [B, T] byte strings; "" time padding (fit_time pads strings
      # with "") zero-fills via fixed_length; real frames must match
      # the spec's byte count exactly (asserted in-graph).
      frames = tf.reshape(fit_time(value), [-1])
      decoded = _graph_decode_raw(tf, frames, spec, key,
                                  allow_empty=True)
      out[key] = tf.reshape(
          decoded, [-1, sequence_length] + list(spec.shape))
      continue
    dense = fit_time(value)  # [B, T, prod(shape)]
    out[key] = tf.cast(
        tf.reshape(dense, [-1, sequence_length] + list(spec.shape)),
        _graph_dtype(tf, spec))

  out[SEQUENCE_LENGTH_KEY] = true_lengths
  return out


def _encode_feature(value: Any, spec: ExtendedTensorSpec) -> Any:
  """Encodes ONE unbatched value as a tf.train.Feature per its spec."""
  tf = _tf()
  if _is_raw(spec):
    if isinstance(value, (bytes, np.bytes_)):
      data = bytes(value)
    else:
      data = np.ascontiguousarray(
          np.asarray(value, dtype=np.dtype(spec.dtype))).tobytes()
    return tf.train.Feature(bytes_list=tf.train.BytesList(value=[data]))
  if spec.is_image:
    if isinstance(value, (bytes, np.bytes_)):
      data = bytes(value)
    else:
      arr = np.ascontiguousarray(np.asarray(value, dtype=np.uint8))
      if spec.data_format == "png":
        data = tf.io.encode_png(arr).numpy()
      else:
        data = tf.io.encode_jpeg(arr).numpy()
    return tf.train.Feature(bytes_list=tf.train.BytesList(value=[data]))
  arr = np.asarray(value).reshape(-1)
  dtype = np.dtype(spec.dtype)
  if dtype.kind == "f" or spec.dtype.name == "bfloat16":
    return tf.train.Feature(
        float_list=tf.train.FloatList(value=arr.astype(np.float32)))
  return tf.train.Feature(
      int64_list=tf.train.Int64List(value=arr.astype(np.int64)))


def encode_example(
    flat_tensors: Dict[str, np.ndarray],
    feature_spec: Any,
) -> bytes:
  """Encodes ONE example (unbatched) as a serialized tf.Example.

  Inverse of `parse_example_batch`; used by dataset writers and tests.
  Image specs accept either raw uint8 arrays (encoded to the declared
  format here) or pre-encoded bytes.
  """
  tf = _tf()
  flat = specs.flatten_spec_structure(feature_spec).to_flat_dict()
  feature = {}
  for key, spec in flat.items():
    name = wire_key(key, spec)
    if key not in flat_tensors:
      if spec.is_optional:
        continue
      raise ValueError(f"Missing required feature {key!r}")
    feature[name] = _encode_feature(flat_tensors[key], spec)
  example = tf.train.Example(
      features=tf.train.Features(feature=feature))
  return example.SerializeToString()


# ---- episode wire format: tf.SequenceExample ----
#
# Reference parity: the reference parsed robot episodes (short per-task
# demonstration/trial sequences; SURVEY.md §3 `meta_tfdata.py`, §6
# "sequences are short robot episodes"). Per-episode data splits into
# context (is_sequence=False: task ids, goals) and per-timestep
# feature_lists (is_sequence=True: observations, actions). Episodes are
# ragged on the wire; parse pads/truncates every sequence to a caller-
# fixed length — XLA needs static shapes — and reports true lengths.


def split_sequence_specs(feature_spec: Any):
  """Splits a spec structure into (context, sequence) flat dicts."""
  flat = specs.flatten_spec_structure(feature_spec).to_flat_dict()
  context = {k: s for k, s in flat.items() if not s.is_sequence}
  sequence = {k: s for k, s in flat.items() if s.is_sequence}
  return context, sequence


def build_sequence_feature_maps(feature_spec: Any):
  """(context_map, sequence_map) for tf.io.parse_sequence_example."""
  tf = _tf()
  context_specs, sequence_specs = split_sequence_specs(feature_spec)
  context_map = build_feature_map(
      TensorSpecStruct.from_flat_dict(context_specs)) if context_specs \
      else {}
  sequence_map = {}
  for key, spec in sequence_specs.items():
    name = wire_key(key, spec)
    if spec.is_image or _is_raw(spec):
      sequence_map[name] = tf.io.FixedLenSequenceFeature([], tf.string)
      continue
    dtype = np.dtype(spec.dtype)
    if dtype.kind == "f" or spec.dtype.name == "bfloat16":
      tf_dtype = tf.float32
    elif dtype.kind in ("i", "u", "b"):
      tf_dtype = tf.int64
    else:
      raise ValueError(
          f"Unsupported sequence spec dtype for tf.SequenceExample: "
          f"{dtype}")
    sequence_map[name] = tf.io.FixedLenSequenceFeature(
        [int(np.prod(spec.shape))], tf_dtype)
  return context_map, sequence_map


def encode_sequence_example(
    flat_tensors: Dict[str, np.ndarray],
    feature_spec: Any,
) -> bytes:
  """Encodes ONE episode as a serialized tf.SequenceExample.

  Sequence specs expect [T, ...] arrays (T may differ per episode —
  ragged on the wire); image sequence specs accept [T, H, W, C] uint8
  (each frame encoded) or a list of pre-encoded byte strings. Context
  specs expect unbatched arrays, as in `encode_example`.
  """
  tf = _tf()
  context_specs, sequence_specs = split_sequence_specs(feature_spec)
  if not sequence_specs:
    raise ValueError(
        "encode_sequence_example needs at least one is_sequence spec; "
        "use encode_example for flat records.")

  context = {}
  for key, spec in context_specs.items():
    name = wire_key(key, spec)
    if key not in flat_tensors:
      if spec.is_optional:
        continue
      raise ValueError(f"Missing required context feature {key!r}")
    context[name] = _encode_feature(flat_tensors[key], spec)

  lengths = set()
  feature_lists = {}
  for key, spec in sequence_specs.items():
    name = wire_key(key, spec)
    if key not in flat_tensors:
      if spec.is_optional:
        continue
      raise ValueError(f"Missing required sequence feature {key!r}")
    steps = flat_tensors[key]
    lengths.add(len(steps))
    step_spec = spec.replace(is_sequence=False)
    feature_lists[name] = tf.train.FeatureList(
        feature=[_encode_feature(step, step_spec) for step in steps])
  if len(lengths) > 1:
    raise ValueError(
        f"All sequence features of one episode must share a length; "
        f"got lengths {sorted(lengths)}.")

  example = tf.train.SequenceExample(
      context=tf.train.Features(feature=context),
      feature_lists=tf.train.FeatureLists(feature_list=feature_lists))
  return example.SerializeToString()


SEQUENCE_LENGTH_KEY = "sequence_length"


def parse_sequence_example_batch(
    serialized: Any,
    feature_spec: Any,
    sequence_length: int,
) -> TensorSpecStruct:
  """Parses serialized tf.SequenceExamples into static-shape numpy.

  Returns a flat TensorSpecStruct where sequence keys hold
  [batch, sequence_length] + spec.shape arrays (zero-padded / truncated
  — episodes are ragged on the wire, XLA shapes are static), context
  keys hold [batch] + spec.shape arrays, and `SEQUENCE_LENGTH_KEY`
  holds the TRUE pre-pad episode lengths [batch] (clipped to
  `sequence_length`) so models can mask padding.
  """
  tf = _tf()
  flat = specs.flatten_spec_structure(feature_spec).to_flat_dict()
  if SEQUENCE_LENGTH_KEY in flat:
    raise ValueError(
        f"Spec key {SEQUENCE_LENGTH_KEY!r} is reserved: the parser "
        f"emits the true episode lengths under it. Rename the feature.")
  context_map, sequence_map = build_sequence_feature_maps(feature_spec)
  serialized = np.asarray(serialized)
  batch_size = int(serialized.shape[0])
  try:
    context, parsed_seq, seq_lengths = tf.io.parse_sequence_example(
        serialized, context_features=context_map or None,
        sequence_features=sequence_map)
  except Exception as e:  # surface the spec contract, not TF internals
    raise ValueError(
        f"tf.SequenceExample parse failed against the declared specs "
        f"(context keys: {sorted(context_map)}, sequence keys: "
        f"{sorted(sequence_map)}). Underlying error: {e}") from e

  out: Dict[str, np.ndarray] = {}
  true_lengths = np.zeros((batch_size,), np.int32)
  for key, spec in flat.items():
    name = wire_key(key, spec)
    if not spec.is_sequence:
      value = context[name]
      if isinstance(value, tf.sparse.SparseTensor):
        value = tf.sparse.to_dense(value)
      if spec.is_image:
        out[key] = np.stack([
            _fit_image(decode_image_bytes(b), spec)
            for b in value.numpy()]).astype(spec.dtype)
      elif _is_raw(spec):
        out[key] = np.stack([
            _fit_raw(b, spec, key) for b in value.numpy()])
      elif spec.varlen:
        out[key] = _pad_or_truncate(np.asarray(value), spec, batch_size)
      else:
        out[key] = np.asarray(value).reshape(
            (batch_size,) + tuple(spec.shape)).astype(spec.dtype)
      continue

    value = parsed_seq[name]
    if isinstance(value, tf.RaggedTensor):
      value = value.to_tensor()
    if isinstance(value, tf.sparse.SparseTensor):
      value = tf.sparse.to_dense(value)
    lengths = np.asarray(seq_lengths[name]).reshape(batch_size)
    true_lengths = np.maximum(true_lengths,
                              np.minimum(lengths, sequence_length))
    if spec.is_image:
      frames = value.numpy()  # [B, T_max] of encoded bytes
      decoded = np.zeros(
          (batch_size, sequence_length) + tuple(spec.shape), spec.dtype)
      for b in range(batch_size):
        for t in range(min(int(lengths[b]), sequence_length)):
          decoded[b, t] = _fit_image(decode_image_bytes(frames[b, t]),
                                     spec)
      out[key] = decoded
      continue
    if _is_raw(spec):
      frames = value.numpy()  # [B, T_max] of raw bytes
      decoded = np.zeros(
          (batch_size, sequence_length) + tuple(spec.shape), spec.dtype)
      for b in range(batch_size):
        for t in range(min(int(lengths[b]), sequence_length)):
          decoded[b, t] = _fit_raw(frames[b, t], spec, key)
      out[key] = decoded
      continue
    dense = np.asarray(value)  # [B, T_max, prod(shape)]
    t_max = dense.shape[1] if dense.ndim > 1 else 0
    if t_max < sequence_length:
      pad = [(0, 0), (0, sequence_length - t_max)] + \
          [(0, 0)] * (dense.ndim - 2)
      dense = np.pad(dense, pad)
    else:
      dense = dense[:, :sequence_length]
    out[key] = dense.reshape(
        (batch_size, sequence_length) + tuple(spec.shape)
    ).astype(spec.dtype)

  out[SEQUENCE_LENGTH_KEY] = true_lengths
  return TensorSpecStruct.from_flat_dict(out)
