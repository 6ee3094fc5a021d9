"""The windowed-attention language model
(`WindowedAttentionLanguageModel` of `models/language_model.py`; ISSUE
43) and what it forced, at small sizes: the flash kernel's band and its
grouped key-value heads in interpret mode against materialised
attention, forward and all three gradients; YaRN's frequencies against
the closed form at the published numbers; `GatedAttention` with a
window against the plain reference `benchmark/reference/laguna_xs2.py`;
the whole model's loss and gradients; the shares of an expert-parallel
deployment adding up; the shipped gin file at the published widths; the
benchmark's cell of it rehearsed on the CPU through `benchmark/run.py`
(the program's K steps against `follow`'s), `correct` for the shipped
step and not with a part of the mathematics taken out; the FLOP
count's cases and the readers; the banded programs compiled for a v5e
at the cell's widths."""

import functools
import importlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as run_lib  # noqa: E402
from benchmark.harness import program  # noqa: E402
from benchmark.harness import weights as weights_lib  # noqa: E402
from benchmark.reference import laguna_xs2 as ref  # noqa: E402
from benchmark.reference import laguna_xs2_weights  # noqa: E402
from benchmark.tests.test_swa_flops import *  # noqa: E402,F401,F403
from tensor2robot_tpu import config as gin  # noqa: E402
from tensor2robot_tpu import ops  # noqa: E402
from tensor2robot_tpu.data.abstract_input_generator import (  # noqa: E402
    Mode)
from tensor2robot_tpu.layers import transformer  # noqa: E402
from tensor2robot_tpu.models.language_model import (  # noqa: E402
    WindowedAttentionLanguageModel)
from tensor2robot_tpu.parallel import attention_reference, moe  # noqa: E402
from tensor2robot_tpu.specs import TensorSpecStruct  # noqa: E402
from tensor2robot_tpu.telemetry import metrics as tmetrics  # noqa: E402

flash = importlib.import_module("tensor2robot_tpu.ops.flash_attention")

CELL = "laguna_xs2_ep16.train_eval"
FULL, SLIDING = "full_attention", "sliding_attention"
ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 4,
           "original_max_position_embeddings": 32, "beta_slow": 1,
           "beta_fast": 4, "attention_factor": 1.1386294361119891,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1},
    "original_max_position_embeddings": 32}
# One period and the leading dense layer, as the cell: full, sliding x
# 3, full; 4 / 6 query heads over 2; a band of 24; experts 2-5 of 8.
TINY = dict(
    vocab_size=50, hidden_size=16, num_hidden_layers=5,
    layer_types=[FULL, SLIDING, SLIDING, SLIDING, FULL],
    num_attention_heads_per_layer=[4, 6, 6, 6, 4],
    num_key_value_heads=2, head_dim=8, sliding_window=24,
    rope_parameters=ROPE,
    mlp_layer_types=["dense", "sparse", "sparse", "sparse", "sparse"],
    intermediate_size=24, num_experts=8, experts_held=4, first_expert=2,
    num_experts_per_tok=3, norm_topk_prob=True,
    moe_routed_scaling_factor=2.5, moe_intermediate_size=8,
    shared_expert_intermediate_size=8, rms_norm_eps=1e-6)
CONFIG = {"model": TINY}


@pytest.fixture(autouse=True)
def _fresh_gin_and_counters():
  gin.clear_config()
  tmetrics.registry().reset()
  yield
  gin.clear_config()
  tmetrics.registry().reset()


def _params(seed=5, model=TINY):
  return laguna_xs2_weights.make_weights(seed, {"model": model})[0]


def _tree(flat):
  """A flat dict by path as the nested dict flax takes."""
  tree = {}
  for path, leaf in flat.items():
    node = tree
    *parents, last = path.split("/")
    for part in parents:
      node = node.setdefault(part, {})
    node[last] = leaf
  return tree


# --- the kernel: a band, and key-value heads read where they lie ------

@pytest.mark.parametrize("heads,kv,dk,dv,window,block,t", [
    (4, 4, 16, 16, 20, 32, 128),    # window < block
    (4, 4, 16, 16, 32, 32, 128),    # window = block
    (4, 2, 16, 16, 40, 32, 128),    # no multiple of the block
    (4, 2, 16, 16, 100, 16, 128),   # seven blocks behind the diagonal
    (4, 2, 16, 16, 128, 32, 128),   # window >= T: the causal programs
    (4, 2, 16, 16, 500, 32, 128),
    (12, 2, 16, 16, 24, 32, 96),    # 48 over 8: six query heads a group
    (16, 2, 16, 16, 24, 32, 96),    # 64 over 8: eight
    (12, 2, 16, 16, None, 32, 96),  # the full layers: groups, no window
    (6, 3, 24, 8, 24, 32, 96),      # keys wider than values, still
    (4, 4, 24, 8, None, 32, 96),
    (4, 1, 16, 16, 1, 32, 64),      # a position sees itself alone
])
def test_banded_grouped_kernel_equals_materialised_attention(
    heads, kv, dk, dv, window, block, t):
  """Output and the gradients of q, k and v, the Pallas programs
  interpreted; dk and dv come back with the key-value heads' shape, a
  group's sum made inside the kernel."""
  keys = jax.random.split(jax.random.PRNGKey(heads + (window or 0)), 4)
  q = jax.random.normal(keys[0], (2, t, heads, dk))
  k = jax.random.normal(keys[1], (2, t, kv, dk))
  v = jax.random.normal(keys[2], (2, t, kv, dv))
  probe = jax.random.normal(keys[3], (2, t, heads, dv))

  def kernel(q, k, v):
    return jnp.sum(probe * flash.flash_attention(
        q, k, v, causal=True, window=window, block_q=block,
        block_k=2 * block, interpret=True))

  def materialised(q, k, v):
    k, v = (jnp.repeat(x, heads // kv, axis=2) for x in (k, v))
    return jnp.sum(probe * attention_reference(
        q, k, v, causal=True, window=window))

  got, got_grads = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
  want, want_grads = jax.value_and_grad(materialised, (0, 1, 2))(q, k, v)
  np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
  for a, b in zip(got_grads, want_grads):
    assert a.shape == b.shape
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)


def test_a_window_of_the_whole_sequence_is_the_causal_program():
  """`window >= T` and `window=None` trace the same programs; a window
  without `causal` and a group that does not divide are refused."""
  q = jnp.ones((1, 64, 4, 8))
  kv = jnp.ones((1, 64, 2, 8))
  text = lambda **kw: str(jax.make_jaxpr(functools.partial(  # noqa: E731
      flash.flash_attention, causal=True, interpret=True, **kw))(
          q, kv, kv))
  assert text(window=64) == text(window=None) == text(window=999)
  assert text(window=63) != text(window=None)
  with pytest.raises(ValueError, match="causal"):
    flash.flash_attention(q, kv, kv, window=8, interpret=True)
  with pytest.raises(ValueError, match="whole group"):
    flash.flash_attention(q, jnp.ones((1, 64, 3, 8)),
                          jnp.ones((1, 64, 3, 8)), interpret=True)


@pytest.mark.parametrize("t,window,block,visited,tiles", [
    (8192, 512, 512, 2, 31),     # the cell's: a diagonal and one behind
    (8192, 512, 256, 3, 93),     # an unmasked tile between two edges
    (8192, 512, 128, 5, 310),
    (8192, 500, 256, 3, 93),     # the power of two under the window
    (128, 20, 16, 3, 21),
])
def test_window_tiling_counts_the_bands_tiles(t, window, block, visited,
                                              tiles):
  """The grid of a banded program walks `visited` key blocks a query
  block, whatever T; the band's pairs over the tiles' pairs is what
  `lm_swa_band_over_tile_pairs` reports (50 % at the cell's blocks of
  512, 67 at 256, 80 at 128)."""
  got = flash.window_tiling(t, window, block, block)
  assert got[:2] == (block, visited)
  assert got[2] == sum(min(i + 1, window) for i in range(t))
  assert got[3] == tiles * block * block
  # The default blocks (1024 x 2048) are wider than this window: both
  # come down to the power of two at or under it.
  assert flash.window_tiling(8192, 512)[0] == 512
  assert flash.window_tiling(8192, 500)[0] == 256
  mask = np.tril(np.ones((t, t), bool)) & ~np.tril(
      np.ones((t, t), bool), -window)
  seen = mask.reshape(t // block, block, t // block, block).any((1, 3))
  assert seen.sum() == tiles and seen.sum(1).max() == visited


# --- rotary embeddings by layer type ----------------------------------

def test_yarn_frequencies_at_the_published_numbers():
  """The full layers' `rope_parameters` of Laguna-XS.2 over the 64
  rotary dims of a head: low 5, high 16 (the correction dims 5.66 and
  15.80, floor and ceil), pairs under 5 keep theta^(-2 i / 64), pairs
  from 16 on turn 64 times slower, a linear blend between; cos and sin
  times 0.1 ln 64 + 1; the program's and the reference's agree."""
  config = _cell_config()
  rope = config["rope_parameters"][FULL]
  yarn = transformer.YarnRope(
      factor=rope["factor"], beta_fast=rope["beta_fast"],
      beta_slow=rope["beta_slow"],
      original_max_position_embeddings=rope[
          "original_max_position_embeddings"],
      attention_factor=rope["attention_factor"])
  d, theta = 64, float(rope["rope_theta"])
  assert transformer.yarn_correction_range(d, theta, yarn) == (5, 16)
  pair = lambda beta: d * math.log(4096 / (2 * math.pi * beta)) / (  # noqa: E731
      2 * math.log(theta))
  assert pair(64) == pytest.approx(5.66, abs=0.01)
  assert pair(1) == pytest.approx(15.80, abs=0.01)
  freq, amplitude = transformer.rotary_frequencies(d, theta, yarn)
  plain = theta ** (-2 * np.arange(32) / d)
  ramp = np.clip((np.arange(32) - 5) / 11, 0, 1)
  np.testing.assert_allclose(
      freq, (1 - ramp) * plain + ramp * plain / 64, rtol=1e-6)
  np.testing.assert_allclose(freq[:6], plain[:6], rtol=1e-6)
  np.testing.assert_allclose(freq[16:], plain[16:] / 64, rtol=1e-6)
  assert amplitude == pytest.approx(0.1 * math.log(64) + 1, abs=1e-9)
  want, want_amplitude = ref.rotary_frequencies(d, rope)
  np.testing.assert_allclose(freq, want, rtol=1e-6)
  assert want_amplitude == amplitude
  # The sliding layers': plain frequencies at their own base, all 128.
  sliding = config["rope_parameters"][SLIDING]
  freq, amplitude = transformer.rotary_frequencies(
      128, float(sliding["rope_theta"]))
  np.testing.assert_allclose(
      freq, 1e4 ** (-2 * np.arange(64) / 128), rtol=1e-6)
  assert amplitude == 1.0
  np.testing.assert_allclose(ref.rotary_frequencies(128, sliding)[0],
                             freq, rtol=1e-6)


def test_rotary_with_yarn_scales_and_turns():
  """Position 0 is scaled by the amplitude and not turned; without
  YaRN the function is what it was."""
  x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 3, 8))
  yarn = transformer.YarnRope(4.0, 32, 4.0, 1.0, 1.25)
  got = transformer.rotary(x, 4, 5e5, yarn=yarn)
  np.testing.assert_allclose(got[:, 0, :, :4], 1.25 * x[:, 0, :, :4],
                             rtol=1e-6)
  np.testing.assert_allclose(got[..., 4:], x[..., 4:])
  want = jax.vmap(lambda row: ref._rotary(row, 4, dict(
      ROPE[FULL], attention_factor=1.25)))(x)
  np.testing.assert_allclose(got, want, atol=1e-6)


# --- the mixer against the reference ----------------------------------

def _mixer(layer, impl="reference"):
  rope = ROPE[TINY["layer_types"][layer]]
  yarn = None
  if rope["rope_type"] == "yarn":
    yarn = transformer.YarnRope(**{
        name: rope[name] for name in transformer.YarnRope._fields})
  return transformer.GatedAttention(
      num_heads=TINY["num_attention_heads_per_layer"][layer],
      num_kv_heads=2, head_dim=8,
      rotary_dim=int(8 * rope["partial_rotary_factor"]),
      rope_theta=float(rope["rope_theta"]), yarn=yarn,
      window=24 if TINY["layer_types"][layer] == SLIDING else None,
      grouped_kv=True, attention_impl=impl, dtype=jnp.float32)


@pytest.mark.parametrize("layer,impl", [
    (0, "reference"), (0, "flash"), (1, "reference"), (1, "flash")])
def test_gated_attention_equals_the_reference(monkeypatch, layer, impl):
  """Layer 0 (full, YaRN over half a head, 4 heads over 2) and layer 1
  (a band of 24, plain rotary over the whole head, 6 over 2): output
  and gradients; `flash` is the Pallas kernel (interpreted) reading
  the key-value heads unrepeated. 100 positions: the reference's
  blocks of queries would need 256, the kernel's 32 do not divide it
  (blocks of 4)."""
  monkeypatch.setattr(ops, "flash_attention", functools.partial(
      ops.flash_attention, block_q=32, block_k=64, interpret=True))
  params = ref._sub(_params(), f"trunk/blocks_{layer}/mixer/")
  x = jax.random.normal(jax.random.PRNGKey(0), (2, 96, 16))
  probe = jax.random.normal(jax.random.PRNGKey(1), (2, 96, 16))

  def program_out(params, x):
    return jnp.sum(_mixer(layer, impl).apply(
        {"params": _tree(params)}, x) * probe)

  def reference_out(params, x):
    return jnp.sum(jax.vmap(
        lambda row: ref._attention(row, params, layer, TINY, False))(x)
                   * probe)

  got, got_grads = jax.value_and_grad(program_out, (0, 1))(params, x)
  want, want_grads = jax.value_and_grad(reference_out, (0, 1))(params, x)
  np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)
  for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                  jax.tree_util.tree_leaves(want_grads)):
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-4)
  counts = tmetrics.registry().scalars("attention.")
  if layer == 0:  # no window: only a backend that repeats counts
    assert counts == ({} if impl == "flash" else
                      {"attention.kv_repeat_traces": 1.0})
  elif impl == "flash":
    _, _, band, tiles = flash.window_tiling(96, 24)
    assert counts == {"attention.window.kernel_traces": 1.0,
                      "attention.window.band_pairs": 2 * 6 * band,
                      "attention.window.tile_pairs": 2 * 6 * tiles}
  else:
    assert counts == {"attention.window.materialised_traces": 1.0,
                      "attention.kv_repeat_traces": 1.0}


@pytest.mark.parametrize("layer", [0, 1])
def test_without_grouped_kv_the_heads_are_repeated_first(monkeypatch,
                                                         layer):
  """`grouped_kv` off (the hybrid model's call, as it was before the
  kernel took groups): the key-value heads are repeated to the query
  heads before the flash kernel, the call is counted, and the output
  is the grouped path's."""
  seen, real = [], ops.flash_attention

  def kernel(q, k, v, **kwargs):
    seen.append((q.shape[2], k.shape[2], v.shape[2]))
    return real(q, k, v, block_q=32, block_k=64, interpret=True,
                **kwargs)

  monkeypatch.setattr(ops, "flash_attention", kernel)
  params = ref._sub(_params(), f"trunk/blocks_{layer}/mixer/")
  x = jax.random.normal(jax.random.PRNGKey(0), (2, 96, 16))
  grouped = _mixer(layer, "flash")
  want = grouped.apply({"params": _tree(params)}, x)
  assert "attention.kv_repeat_traces" not in tmetrics.registry().scalars(
      "attention.")
  got = grouped.clone(grouped_kv=False).apply(
      {"params": _tree(params)}, x)
  heads = grouped.num_heads
  assert seen == [(heads, 2, 2), (heads, heads, heads)]
  assert tmetrics.registry().scalars("attention.")[
      "attention.kv_repeat_traces"] == 1.0
  np.testing.assert_allclose(got, want, atol=1e-6)


def test_a_window_changes_the_output_and_a_wide_one_does_not():
  params = ref._sub(_params(), "trunk/blocks_1/mixer/")
  x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 16))
  apply = lambda window: _mixer(1).clone(window=window).apply(  # noqa: E731
      {"params": _tree(params)}, x)
  causal = apply(None)
  np.testing.assert_allclose(apply(40), causal, atol=1e-6)
  np.testing.assert_allclose(apply(24)[:, :24], causal[:, :24],
                             atol=1e-6)
  assert float(jnp.abs(apply(24)[:, 24:] - causal[:, 24:]).max()) > 1e-3


def test_reference_attends_in_blocks_of_queries(monkeypatch):
  """70 positions in blocks of 32 queries (the last one padded) equal
  all at once, under the band and under the causal mask."""
  x = jax.random.normal(jax.random.PRNGKey(2), (70, 16))
  for layer in (0, 1):
    params = ref._sub(_params(), f"trunk/blocks_{layer}/mixer/")
    monkeypatch.setattr(ref, "QUERY_BLOCK", 256)
    whole = ref._attention(x, params, layer, TINY, False)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
    np.testing.assert_allclose(
        ref._attention(x, params, layer, TINY, False), whole, atol=1e-6)


# --- the expert layer: sigmoid scores, no bias, shares of 16 chips ----

def _expert_layer(held=16, first=0, shared=8):
  return moe.SparseMoE(
      num_experts=16, experts_held=held, first_expert=first, k=3,
      expert_width=8, shared_width=shared, scoring="sigmoid",
      selection_bias=False, routed_scaling_factor=2.5,
      shared_gated=False, dtype=jnp.float32)


def _apply(layer, params, x):
  out, sown = layer.apply({"params": _tree(params)}, x,
                          mutable=["moe_counters"])
  return out, {name: float(value[0])
               for name, value in sown["moe_counters"].items()}


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
  """Sixteen chips of one expert each (model-configs guide, section
  4): the routed parts of all shares, with the shared expert that
  every chip computes alike counted once, equal the uncut reference
  layer; the layer has no selection bias."""
  model = dict(TINY, num_experts=16, experts_held=16, first_expert=0)
  params = ref._sub(_params(model=model), "trunk/blocks_1/ffn/")
  assert "router_bias" not in params
  x = jax.random.normal(jax.random.PRNGKey(7), (2, 60, 16))
  no_shared = {k: v for k, v in params.items()
               if not k.startswith("shared")}
  whole, _ = _apply(_expert_layer(), params, x)
  shared_only = whole - _apply(_expert_layer(shared=0), no_shared, x)[0]

  def share(first):
    part = dict(no_shared)
    for name in ("experts_gate", "experts_up", "experts_down"):
      part[name] = no_shared[name][first:first + 1]
    return _apply(_expert_layer(1, first, shared=0), part, x)

  parts = [share(first) for first in range(16)]
  want = ref._expert_ffn(x.reshape(-1, 16), params, model, False)
  np.testing.assert_allclose(
      (sum(out for out, _ in parts) + shared_only).reshape(-1, 16),
      want, atol=3e-5, rtol=1e-4)
  np.testing.assert_allclose(whole.reshape(-1, 16), want, atol=3e-5,
                             rtol=1e-4)
  assert abs(sum(counters["assignments_here_share"]
                 for _, counters in parts) - 1.0) < 1e-6


# --- the whole model ----------------------------------------------------

@pytest.mark.parametrize("remat_policy", ["save_attention", None])
def test_loss_and_gradients_equal_the_references(monkeypatch,
                                                 remat_policy):
  """The loss and every gradient. 150 positions: three blocks of the
  reference's queries (the third padded), no multiple of the loss's
  block, more than six bands long."""
  t = 150
  monkeypatch.setattr(ref, "QUERY_BLOCK", 64)
  model = WindowedAttentionLanguageModel(
      sequence_length=t, device_dtype=jnp.float32, loss_block=64,
      attention_impl="reference", remat_policy=remat_policy, **TINY)
  params = _params()
  shapes = jax.eval_shape(lambda: model.create_inference_state(
      jax.random.PRNGKey(0), batch_size=2))
  tree = weights_lib.place(shapes.params, params)
  ids = jax.random.randint(jax.random.PRNGKey(1), (3, t + 1), 0, 50)
  batch = {"features": {"token_ids": ids}}

  def program_loss(tree):
    loss, (scalars, _) = model.loss_fn(
        tree, {}, {"token_ids": ids}, TensorSpecStruct(), None,
        Mode.TRAIN)
    return loss, scalars

  (got, scalars), got_grads = jax.value_and_grad(
      program_loss, has_aux=True)(tree)
  want, want_grads = jax.value_and_grad(
      lambda p: ref.loss(CONFIG, p, {}, batch, None)[0])(params)
  np.testing.assert_allclose(got, want, rtol=1e-5)
  got_grads = weights_lib.flatten(got_grads)
  assert set(got_grads) == set(want_grads)
  for name, grad in want_grads.items():
    err = float(jnp.linalg.norm(got_grads[name] - grad)
                / (jnp.linalg.norm(grad) + 1e-12))
    assert err < 5e-4, (name, err)
  assert float(scalars["moe.dropped_assignments"]) == 0.0
  assert 0.3 < float(scalars["moe.assignments_here_share"]) < 0.7
  assert "moe.bias_moved_choice_share" not in scalars
  # One precision lower is another number, as a whole and in parts.
  for control in (True, "attention", "router"):
    lowered = ref.loss(CONFIG, params, {}, batch, None,
                       control=control)[0]
    assert abs(float(lowered) - float(want)) > 1e-5, control


def test_the_model_refuses_what_it_does_not_build():
  for wrong, match in (
      (dict(layer_types=[FULL, "chunked_attention"] * 3), "layer type"),
      (dict(mlp_layer_types=["dense", "sparse", "moe", "sparse",
                             "sparse"]), "mlp layer types"),
      (dict(layer_types=[FULL] * 3), "entries for 5 layers"),
      (dict(rope_parameters=dict(ROPE, **{SLIDING: dict(
          ROPE[SLIDING], rope_type="llama3")})), "rope_type")):
    with pytest.raises(ValueError, match=match):
      WindowedAttentionLanguageModel(sequence_length=64,
                                     **dict(TINY, **wrong))


def _cell_config():
  _, _, config, _ = run_lib.load_cell(CELL)
  return config


def test_shipped_gin_file_builds_the_cells_565_206_272_parameters():
  """The shipped gin file under the cell's four bindings builds the
  published widths: a full layer with the dense FFN, three sliding
  layers and a full one with 16 experts each, and the vocabulary's
  slice: the count ISSUE 43 reckons, part by part; the benchmark's
  weights have the program's tree; unbound it is the published model."""
  config = _cell_config()
  model = program.build_model(config)
  shapes = jax.eval_shape(lambda: model.create_train_state(
      jax.random.PRNGKey(0), batch_size=1))
  flat = weights_lib.flatten(shapes.params)
  count = lambda prefix: sum(  # noqa: E731
      int(np.prod(leaf.shape)) for name, leaf in flat.items()
      if name.startswith(prefix))
  full = 2048 * 12288 + 2 * 2048 * 1024 + 6144 * 2048 + 2 * 128
  sliding = 2048 * 16384 + 2 * 2048 * 1024 + 8192 * 2048 + 2 * 128
  assert (full, sliding) == (41_943_296, 54_526_208)
  assert count("trunk/blocks_0/mixer") == count(
      "trunk/blocks_4/mixer") == full
  for layer in (1, 2, 3):
    assert count(f"trunk/blocks_{layer}/mixer") == sliding
  assert count("trunk/blocks_0/ffn") == 3 * 2048 * 8192
  assert count("trunk/blocks_4/ffn") == (
      16 * 3_145_728 + 3_145_728 + 2048 * 256)
  assert count("embed_tokens") == count("lm_head") == 25_690_112
  assert count("") == 565_206_272 == config["parameters"]
  want = laguna_xs2_weights.param_shapes(config["model"])
  assert {k: tuple(v.shape) for k, v in flat.items()} == want
  spec = model.get_feature_specification(Mode.TRAIN)
  assert tuple(spec["token_ids"].shape) == (8193,)
  assert model._remat_policy == "save_attention"
  published = program.build_model(dict(config, gin_bindings=[], model={}))
  assert (published._num_hidden_layers, published._vocab_size,
          published._experts_held) == (40, 100352, 256)
  blocks = published.create_network().trunk.blocks
  assert [b.mixer.window for b in blocks[:5]] == [None, 512, 512, 512,
                                                  None]
  assert [b.mixer.num_heads for b in blocks[36:]] == [48, 64, 64, 64]
  assert [b.mixer.rotary_dim for b in blocks[:2]] == [64, 128]
  assert blocks[0].mixer.yarn.factor == 64 and blocks[1].mixer.yarn is None


def test_the_configuration_file_holds_the_published_config():
  """Every key of the catalog's row, at the top level and (where the
  model takes it) in the `model` block; only depth and vocabulary
  differ, and `experts_held` counts the chip's share."""
  config = _cell_config()
  with open(os.path.join(run_lib.HERE, "tests", "data", "widths",
                         "laguna_xs2_ep16.json")) as f:
    pin = json.load(f)
  published = {k: v for k, v in pin.items()
               if k not in ("_note", "model")}
  assert len(published) == 23  # the row has 25 keys
  for key, value in published.items():
    assert config[key] == value, key
    if key in config["model"]:
      assert config["model"][key] == value, key
  assert len(config["layer_types"]) == 40
  assert (config["num_hidden_layers"], config["vocab_size"],
          config["experts_held"]) == (5, 12544, 16)
  assert (config["sliding_window"], config["head_dim"],
          config["num_key_value_heads"], config["num_experts"],
          config["num_experts_per_tok"]) == (512, 128, 8, 256, 8)
  assert sorted(config["reduced"]) == ["experts_held",
                                       "num_hidden_layers", "vocab_size"]
  assert "16 chips" in config["deployment"]
  for key in ("sequence_length", "attention_gate", "router", "qk_norm",
              "window", "norms", "weights", "adam_nu0"):
    assert key in config["assumed"], key
  tiny = config["rehearse_cpu"]["model"]
  assert tiny["sliding_window"] < tiny["sequence_length"]
  assert tiny["experts_held"] < tiny["num_experts"]
  assert set(tiny["layer_types"][:tiny["num_hidden_layers"]]) == {
      FULL, SLIDING}


def _rehearse(capsys, monkeypatch, trace="0"):
  monkeypatch.setattr(sys, "argv", [
      "run.py", "--workload", CELL, "--seed", "2147483659",
      "--seconds", "1", "--trace", trace, "--rehearse-cpu"])
  assert run_lib.main() == 0
  lines = capsys.readouterr().out.strip().splitlines()
  return json.loads(lines[-1]), lines


def test_rehearsed_cell_is_correct(capsys, monkeypatch):
  """The program's K steps of Adam through `train_eval_model` against
  `follow`'s on the reference's loss."""
  result, lines = _rehearse(capsys, monkeypatch, trace="1")
  assert result["correct"] is True, lines
  assert result["failed"] == 0 and result["attempted"] > 0
  assert len(result["check"]) >= 5
  # On a CPU the mixers take materialised attention: the share is 0,
  # and no banded kernel was traced to count pairs for.
  assert result["metric_names"] == ["lm_swa_window_kernel_share"]


def _no_window(monkeypatch):
  monkeypatch.setattr(
      transformer, "attention_reference",
      lambda q, k, v, causal, window=None: attention_reference(
          q, k, v, causal=causal), raising=False)
  import tensor2robot_tpu.parallel as parallel
  monkeypatch.setattr(
      parallel, "attention_reference",
      lambda q, k, v, causal=False, window=None: attention_reference(
          q, k, v, causal=causal))


def _no_yarn(monkeypatch):
  real = transformer.rotary
  monkeypatch.setattr(
      transformer, "rotary",
      lambda x, rotary_dim, theta, interleaved=False, yarn=None: real(
          x, rotary_dim, theta, interleaved))


def _heads_of_another_group(monkeypatch):
  real = jnp.repeat

  def tile(x, repeats, axis=None, **kwargs):
    if axis == 2 and x.ndim == 4:  # head h reads kv head h % KV
      return jnp.tile(x, (1, 1, repeats, 1))
    return real(x, repeats, axis=axis, **kwargs)

  monkeypatch.setattr(transformer.jnp, "repeat", tile)


@pytest.mark.parametrize("take_out", [
    _no_window, _no_yarn, _heads_of_another_group])
def test_rehearsed_cell_with_a_part_taken_out_is_not_correct(
    capsys, monkeypatch, take_out):
  """The timed path broken underneath: the band ignored (the sliding
  layers see every earlier position), the full layers' rotary plain
  and unscaled, a query head reading another group's key-value head."""
  take_out(monkeypatch)
  result, lines = _rehearse(capsys, monkeypatch)
  assert result["correct"] is False
  assert any("FAILED" in line for line in lines)


def test_benchmark_json_has_the_new_entries_and_no_other():
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  # ISSUE 47 appended a fourth family's configuration and cell
  # (tests/test_channel_gated_language_model.py).
  assert bench["configs"][-2]["name"] == "laguna_xs2_ep16"
  assert bench["configs"][-2]["reduced"] == [
      "num_hidden_layers", "experts_held", "vocab_size"]
  assert bench["workloads"][-2] == {
      "name": CELL, "config": "laguna_xs2_ep16",
      "traffic": "train_eval", "chips": 1,
      "why": bench["workloads"][-2]["why"]}
  assert len(bench["workloads"]) == 6
  # Seven of ISSUE 43's nine: a device time of the sliding layers, and
  # the rest that would be reckoned from it, wait for the scope
  # `window_attention` in the reduction's list (PERF.md section 7 (0)).
  new = [m for m in bench["per_layer"] if m["name"].startswith("lm_swa_")]
  assert [m["name"] for m in new] == [
      "lm_swa_step_mfu", "lm_swa_full_attention_device_ms",
      "lm_swa_moe_device_ms", "lm_swa_window_attention_roofline",
      "lm_swa_full_attention_roofline", "lm_swa_window_kernel_share",
      "lm_swa_band_over_tile_pairs"]
  for metric in new:
    assert metric["workloads"] == [CELL]
    assert metric["moves"] == "train_steps_per_s"
    assert metric["better"] == (
        "lower" if metric["name"].endswith("_device_ms") else "higher")
  # No entry accepted before the cell lists it: appending it to their
  # lists is a `benchmark` PR's (PERF.md section 7). An entry born
  # after it may: ISSUE 45's one, of the kernel all three families run.
  first = bench["per_layer"].index(new[0])
  assert bench["per_layer"][first:first + 7] == new
  for metric in bench["per_layer"][:first]:
    assert CELL not in metric["workloads"], metric["name"]
  later = [m["name"] for m in bench["per_layer"][first + 7:]]
  assert later[0] == "lm_flash_backward_fused_share"
  # ISSUE 47's nine, of the fourth family's cell alone.
  assert len(later) == 10
  assert all(name.startswith("lm_kda_") for name in later[1:])


# --- the banded programs compiled for the chip -------------------------

@pytest.fixture(scope="module")
def one_chip():
  from jax.experimental import topologies
  from jax.sharding import SingleDeviceSharding
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # no libtpu, or another process holds it
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
  return SingleDeviceSharding(topo.devices[0])


def _compile_grad_for_the_chip(one_chip, t, heads, kv_heads, dk, dv,
                                dtype, window):
  """(compiled gradient of one row's causal attention, what the
  backward pass counted): the forward program and the backward's, for
  the v5e. Such a compile cannot be read back from the persistent
  cache: keep it out."""
  from jax.experimental.compilation_cache import compilation_cache
  from tensor2robot_tpu.telemetry import metrics as tmetrics

  def aval(heads, width):
    return jax.ShapeDtypeStruct((1, t, heads, width), dtype,
                                sharding=one_chip)

  def loss(q, k, v):
    return jnp.sum(flash.flash_attention(
        q, k, v, causal=True, window=window).astype(jnp.float32))

  enabled = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  tmetrics.reset_for_tests()
  try:
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        aval(heads, dk), aval(kv_heads, dk), aval(kv_heads, dv)).compile()
    counts = tmetrics.registry().scalars("flash_attention.backward.")
  finally:
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()
    tmetrics.reset_for_tests()
  return compiled, counts


# query heads, key-value heads, keys' width, values' width, window: a
# sliding layer's call and a full layer's of this cell, and the JoyAI
# cell's latent attention (ISSUE 45: the backward pass is one program
# with a sequence's dQ, and under a group its dK and dV, in VMEM).
@pytest.mark.parametrize("heads,kv_heads,dk,dv,window", [
    (64, 8, 128, 128, 512), (48, 8, 128, 128, None),
    (32, 32, 192, 128, None)])
def test_kernels_compile_for_a_v5e_at_the_cells_widths(
    one_chip, heads, kv_heads, dk, dv, window):
  """A sliding layer's call (64 heads over 8, a band of 512), a full
  layer's (48 over 8) and a latent-attention layer's (32 heads, keys
  192 over values 128), one row of 8,192 positions in bfloat16, the
  forward program and the fused backward program: what the interpreter
  cannot refuse (tiling, VMEM) is refused here."""
  compiled, counts = _compile_grad_for_the_chip(
      one_chip, 8192, heads, kv_heads, dk, dv, jnp.bfloat16, window)
  assert counts == {"flash_attention.backward.fused_traces": 1.0}
  assert compiled.as_text().count("tpu_custom_call") == 2


# t, query heads, key-value heads, keys' and values' width, dtype: the
# longest sequences whose byte count is still within the fused
# program's budget (41 to 44 of its 44 MiB), one of each kind of
# accumulator: dQ alone at 64 lanes (the SNAIL trunks' and the ring's
# longest), at one lane tile and at two (key blocks of 2048 and of
# 1024), the same in float32 (tiles and streamed blocks of twice the
# bytes), and under a group dK and dV whole too. The least limit under
# which Mosaic compiles the program was bisected at twelve shapes (PR
# 45): at most the count and 14 MiB (bfloat16 at the 1024 x 2048 tile).
@pytest.mark.parametrize("t,heads,kv_heads,width,dtype", [
    (32768, 2, 2, 64, jnp.bfloat16),
    (34816, 2, 2, 128, jnp.bfloat16),
    (17408, 2, 2, 256, jnp.bfloat16),
    (20480, 2, 2, 128, jnp.float32),
    (9216, 2, 2, 256, jnp.float32),
    (13312, 6, 1, 128, jnp.bfloat16),
    (8192, 8, 1, 128, jnp.float32),
])
def test_a_sequence_just_under_the_fused_budget_compiles_for_a_v5e(
    one_chip, t, heads, kv_heads, width, dtype):
  """The byte count that picks the fused program leaves Mosaic the room
  it takes for a tile's arithmetic: a shape the count admits is one the
  compiler admits within the kernels' VMEM limit."""
  blocks = flash._fused_blocks(
      width, flash._auto_block(1024, t), flash._auto_block(2048, t),
      None)
  count = flash.fused_backward_bytes(
      t, width, width, heads // kv_heads, *blocks,
      jnp.dtype(dtype).itemsize)
  assert 0.93 * flash._FUSED_BACKWARD_BUDGET < count
  assert count <= flash._FUSED_BACKWARD_BUDGET
  compiled, counts = _compile_grad_for_the_chip(
      one_chip, t, heads, kv_heads, width, width, dtype, None)
  assert counts == {"flash_attention.backward.fused_traces": 1.0}
  assert compiled.as_text().count("tpu_custom_call") == 2
