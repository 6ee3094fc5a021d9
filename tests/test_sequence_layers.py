"""The sequence trunk's layer kinds (ISSUE 34; docs/SEQUENCE.md) against
the plain reference of `benchmark/reference/qwen3_next.py`, at small
sizes in float32 on seeded random weights: the chunked Gated DeltaNet
against the recurrence over positions, gated grouped-query attention
against attention materialised by blocks, the block as data."""

import collections
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark.layer_metrics import (  # noqa: E402
    lm_attention_saved_share)
from benchmark.reference import qwen3_next as ref  # noqa: E402
from tensor2robot_tpu import ops  # noqa: E402
from tensor2robot_tpu.layers import gated_delta  # noqa: E402
from tensor2robot_tpu.layers.transformer import (  # noqa: E402
    CausalTransformer,
    GatedAttention,
    LatentAttention,
    RMSNorm,
    SequenceTrunk,
    TransformerBlock,
    rotary,
)
from tensor2robot_tpu.telemetry import metrics as tmetrics  # noqa: E402

MODEL = {
    "hidden_size": 32, "rms_norm_eps": 1e-6,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 1e7,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4,
}


def _rule_inputs(seed, t, heads=3, dk=8, dv=8, g_scale=1.0):
  keys = jax.random.split(jax.random.PRNGKey(seed), 5)
  q = gated_delta.l2_normalize(
      jax.random.normal(keys[0], (2, t, heads, dk))) * dk ** -0.5
  k = gated_delta.l2_normalize(
      jax.random.normal(keys[1], (2, t, heads, dk)))
  v = jax.random.normal(keys[2], (2, t, heads, dv))
  g = -g_scale * jax.random.uniform(keys[3], (2, t, heads), minval=0.5,
                                    maxval=4.0)
  beta = jax.nn.sigmoid(jax.random.normal(keys[4], (2, t, heads)))
  return q, k, v, g, beta


def _recurrence(q, k, v, g, beta):
  return jax.vmap(lambda *row: ref._delta_rule(*row, control=False))(
      q, k, v, g, beta)


# 150 is no multiple of the chunk; g down to -4 a position decays a
# chunk of 16 by exp(-64), and g of -40 underflows float32 inside one.
@pytest.mark.parametrize("t,chunk,g_scale", [
    (150, 64, 1.0), (150, 16, 1.0), (64, 64, 1.0), (7, 8, 1.0),
    (40, 16, 10.0), (33, 32, 0.01)])
def test_chunked_delta_rule_equals_the_recurrence(t, chunk, g_scale):
  args = _rule_inputs(t, t, g_scale=g_scale)
  got = gated_delta.gated_delta_rule(*args, chunk=chunk)
  want = _recurrence(*args)
  np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
  assert np.all(np.isfinite(got))


@pytest.mark.parametrize("g_scale", [1.0, 10.0])
def test_chunked_delta_rule_gradients_equal_the_recurrences(g_scale):
  args = _rule_inputs(3, 150, g_scale=g_scale)
  probe = jax.random.normal(jax.random.PRNGKey(9), (2, 150, 3, 8))

  def through(rule):
    return jax.grad(lambda *a: jnp.sum(rule(*a) * probe),
                    argnums=(0, 1, 2, 3, 4))(*args)

  got = through(lambda *a: gated_delta.gated_delta_rule(*a, chunk=64))
  want = through(_recurrence)
  for name, a, b in zip("q k v g beta".split(), got, want):
    assert np.all(np.isfinite(a)), name
    np.testing.assert_allclose(a, b, atol=3e-5, rtol=1e-3, err_msg=name)


def test_unit_lower_inverse_and_its_gradient():
  a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 16, 16)), -1)
  want = jnp.linalg.inv(jnp.eye(16) + a)
  np.testing.assert_allclose(gated_delta._unit_lower_inverse(a), want,
                             atol=1e-4, rtol=1e-4)
  probe = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 16))
  got = jax.grad(lambda a: jnp.sum(
      gated_delta._unit_lower_inverse(a) * probe))(a)
  want = jax.grad(lambda a: jnp.sum(
      jnp.linalg.inv(jnp.eye(16) + a) * probe))(a)
  np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


def test_causal_depthwise_conv_sees_the_last_four_positions():
  x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 5))
  kernel = jax.random.normal(jax.random.PRNGKey(1), (4, 5))
  got = gated_delta.causal_depthwise_conv(x, kernel)
  want = np.zeros((2, 9, 5), np.float32)
  for t in range(9):
    for j in range(4):
      if t - 3 + j >= 0:
        want[:, t] += np.asarray(x[:, t - 3 + j] * kernel[j])
  np.testing.assert_allclose(got, want, atol=1e-6)


def _flat(tree, prefix=""):
  out = {}
  for key, value in tree.items():
    if isinstance(value, dict):
      out.update(_flat(value, f"{prefix}{key}/"))
    else:
      out[f"{prefix}{key}"] = value
  return out


def _randomised(params, seed):
  leaves, treedef = jax.tree_util.tree_flatten(params)
  keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
  return jax.tree_util.tree_unflatten(treedef, [
      leaf + 0.3 * jax.random.normal(key, leaf.shape)
      for leaf, key in zip(leaves, keys)])


@pytest.mark.parametrize("t", [50, 130])
def test_gated_delta_net_layer_equals_the_reference(t):
  layer = gated_delta.GatedDeltaNet(
      num_k_heads=2, num_v_heads=4, head_k_dim=8, head_v_dim=8,
      chunk=64, dtype=jnp.float32)
  x = jax.random.normal(jax.random.PRNGKey(0), (2, t, 32))
  params = _randomised(layer.init(jax.random.PRNGKey(1), x)["params"], 2)
  got = layer.apply({"params": params}, x)
  want = jax.vmap(lambda row: ref._gated_delta_net(
      row, _flat(params), MODEL, False))(x)
  np.testing.assert_allclose(got, want, atol=3e-5, rtol=1e-3)


def _norm_after_the_map(layer, params, x):
  """`GatedDeltaNet` as it stood before ISSUE 41: the rule alone mapped
  over the rows, its float32 output normalised and gated for the whole
  batch, and `out_proj` making the cast to `dtype`."""
  import flax.linen as nn
  b, t, width = x.shape
  hk, hv = layer.num_k_heads, layer.num_v_heads
  dk, dv = layer.head_k_dim, layer.head_v_dim
  key_dim, value_dim = hk * dk, hv * dv

  def dense(name, y, features):
    return nn.Dense(features, use_bias=False, dtype=layer.dtype).apply(
        {"params": params[name]}, y)

  x = x.astype(layer.dtype)
  qkvz = dense("in_proj_qkvz", x, 2 * key_dim + 2 * value_dim)
  ba = dense("in_proj_ba", x, 2 * hv).astype(jnp.float32)
  qkv, z = jnp.split(qkvz, [2 * key_dim + value_dim], axis=-1)
  qkv = nn.silu(gated_delta.causal_depthwise_conv(
      qkv, params["conv"].astype(layer.dtype)))
  q, k, v = jnp.split(qkv, [key_dim, 2 * key_dim], axis=-1)
  beta = jax.nn.sigmoid(ba[..., :hv])
  g = -jnp.exp(params["A_log"]) * jax.nn.softplus(
      ba[..., hv:] + params["dt_bias"])
  q = gated_delta.l2_normalize(
      q.reshape(b, t, hk, dk).astype(jnp.float32), layer.eps) * dk ** -0.5
  k = gated_delta.l2_normalize(
      k.reshape(b, t, hk, dk).astype(jnp.float32), layer.eps)
  q, k = (jnp.repeat(y, hv // hk, axis=2) for y in (q, k))
  out = jax.lax.map(
      lambda row: gated_delta.gated_delta_rule(
          *(y[None] for y in row), chunk=layer.chunk,
          dtype=layer.dtype)[0],
      (q, k, v.reshape(b, t, hv, dv), g, beta))
  out = out * jax.lax.rsqrt(
      jnp.mean(jnp.square(out), -1, keepdims=True) + layer.eps)
  out = params["norm"] * out * nn.silu(
      z.reshape(b, t, hv, dv).astype(jnp.float32))
  return dense("out_proj", out.reshape(b, t, value_dim), width)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_gated_norm_inside_the_mapped_row_is_the_norm_after_the_map(
    dtype):
  """ISSUE 41 moved the gated norm and `out_proj`'s cast into the
  function that is mapped over the rows: the same operations on the
  same values, so the same bits, in the cell's bfloat16 too. Operation
  by operation: compiled, XLA fuses a row's norm with the rule's last
  product on a CPU, which moves float32's last bits."""
  layer = gated_delta.GatedDeltaNet(
      num_k_heads=2, num_v_heads=4, head_k_dim=8, head_v_dim=8,
      chunk=16, dtype=dtype)
  x = jax.random.normal(jax.random.PRNGKey(0), (3, 50, 32))
  params = _randomised(layer.init(jax.random.PRNGKey(1), x)["params"], 2)
  with jax.disable_jit():
    got = layer.apply({"params": params}, x)
    want = _norm_after_the_map(layer, params, x)
  assert got.dtype == want.dtype == dtype
  np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("t", [40, 1024 + 40])
def test_gated_attention_equals_the_reference(t):
  """Partial rotary (4 of 16 dims), two key-value heads under four
  query heads, the sigmoid gate; 1,064 positions is more than one of
  the reference's blocks of queries would hold, so it takes them all
  at once, and 2,048 goes block by block."""
  layer = GatedAttention(num_heads=4, num_kv_heads=2, head_dim=16,
                         rotary_dim=4, attention_impl="reference",
                         dtype=jnp.float32)
  x = jax.random.normal(jax.random.PRNGKey(0), (2, t, 32))
  params = _randomised(layer.init(jax.random.PRNGKey(1), x)["params"], 3)
  got = layer.apply({"params": params}, x)
  want = jax.vmap(lambda row: ref._gated_attention(
      row, _flat(params), MODEL, False))(x)
  np.testing.assert_allclose(got, want, atol=3e-5, rtol=1e-3)


def test_reference_attention_by_blocks_equals_all_at_once(monkeypatch):
  x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
  layer = GatedAttention(num_heads=4, num_kv_heads=2, head_dim=16,
                         rotary_dim=4, attention_impl="reference",
                         dtype=jnp.float32)
  params = _flat(_randomised(
      layer.init(jax.random.PRNGKey(1), x[None])["params"], 4))
  whole = ref._gated_attention(x, params, MODEL, False)
  monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
  np.testing.assert_allclose(
      ref._gated_attention(x, params, MODEL, False), whole, atol=1e-5)


def test_rotary_turns_only_its_share_of_a_head():
  x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 16))
  y = rotary(x, 4, 1e4)
  np.testing.assert_array_equal(y[..., 4:], x[..., 4:])
  np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-6)  # position 0
  np.testing.assert_allclose(
      jnp.linalg.norm(y[..., :4], axis=-1),
      jnp.linalg.norm(x[..., :4], axis=-1), rtol=1e-5)
  assert not np.allclose(y[:, 1:, :, :4], x[:, 1:, :, :4])


def test_rms_norm():
  x = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (4, 8))
  norm = RMSNorm()
  params = norm.init(jax.random.PRNGKey(1), x)
  np.testing.assert_allclose(  # w = 0 is the identity scale
      jnp.sqrt(jnp.mean(jnp.square(norm.apply(params, x)), -1)), 1.0,
      rtol=1e-4)
  weight = jax.random.normal(jax.random.PRNGKey(2), (8,))
  got = norm.apply({"params": {"weight": weight}}, x)
  want = ref._rms_norm(x, weight, 1e-6, False)
  np.testing.assert_allclose(got, want, rtol=1e-5)


def _hybrid_blocks():
  def mixer(i):
    if i == 1:
      return GatedAttention(num_heads=4, num_kv_heads=2, head_dim=8,
                            rotary_dim=4, attention_impl="reference",
                            dtype=jnp.float32)
    return gated_delta.GatedDeltaNet(
        num_k_heads=2, num_v_heads=4, head_k_dim=8, head_v_dim=8,
        chunk=8, dtype=jnp.float32)

  return tuple(TransformerBlock(norm="rms", mixer=mixer(i), mlp_ratio=2,
                                dtype=jnp.float32) for i in range(2))


@pytest.mark.parametrize("policy", ["full", "dots", "dots_no_batch",
                                    "save_attention"])
def test_a_trunk_of_blocks_as_data_remats_to_the_same_numbers(policy):
  x = jax.random.normal(jax.random.PRNGKey(0), (2, 20, 16))
  plain = SequenceTrunk(blocks=_hybrid_blocks())
  params = plain.init(jax.random.PRNGKey(1), x)
  assert set(params["params"]["blocks_0"]["mixer"]) >= {"A_log", "conv"}
  assert set(params["params"]["blocks_1"]["mixer"]) >= {"q_proj",
                                                        "k_norm"}
  remat = SequenceTrunk(blocks=_hybrid_blocks(), remat_policy=policy)

  def loss(trunk, params):
    return jnp.sum(jnp.square(trunk.apply(params, x)))

  want, want_grad = jax.value_and_grad(lambda p: loss(plain, p))(params)
  got, got_grad = jax.value_and_grad(lambda p: loss(remat, p))(params)
  np.testing.assert_allclose(got, want, rtol=1e-6)
  for a, b in zip(jax.tree_util.tree_leaves(got_grad),
                  jax.tree_util.tree_leaves(want_grad)):
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_a_block_without_mixer_or_ffn_is_the_block_it_always_was():
  """`CausalTransformer` still stacks pre-LN attention blocks under
  the names its checkpoints have."""
  model = CausalTransformer(width=16, depth=2, num_heads=2, max_len=8,
                            dtype=jnp.float32)
  x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 5))
  params = model.init(jax.random.PRNGKey(1), x)["params"]
  assert set(params["block0"]) == {"ln_attn", "attn", "ln_mlp",
                                   "mlp_in", "mlp_out"}
  assert set(params["block0"]["ln_attn"]) == {"scale", "bias"}
  with pytest.raises(ValueError, match="Unknown norm"):
    TransformerBlock(num_heads=2, head_dim=8, norm="batch").init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, 16)))


# A block's checkpoint that keeps what the mixer's core returned: the
# flash kernel's two results (ISSUE 37), the delta rule's output after
# its gated norm (ISSUE 41). One block under each policy, its mixer
# through its Pallas kernels (interpreted) or without them
# (materialised attention; the rule's walk as a `lax.scan`).
SAVED = "trunk.checkpoint.attention_saved_blocks"
RECOMPUTED = "trunk.checkpoint.recomputed_blocks"


def _gated(impl):  # keys and values of one width
  return GatedAttention(num_heads=4, num_kv_heads=2, head_dim=8,
                        rotary_dim=4, attention_impl=impl,
                        dtype=jnp.float32)


def _latent(impl):  # keys of 8 + 4 over values of 6: 192 over 128
  return LatentAttention(num_heads=4, q_lora_rank=12, kv_lora_rank=8,
                         qk_nope_head_dim=8, qk_rope_head_dim=4,
                         v_head_dim=6, attention_impl=impl,
                         dtype=jnp.float32)


def _delta_net(impl):
  del impl  # no attention: `_block_gradient` picks the walk by it
  return gated_delta.GatedDeltaNet(
      num_k_heads=2, num_v_heads=4, head_k_dim=8, head_v_dim=8, chunk=8,
      dtype=jnp.float32)


def _block_gradient(monkeypatch, mixer, impl):
  """(policy -> the function that gives one block's trunk's gradient
  and output, its parameters)."""
  monkeypatch.setattr(ops, "flash_attention", functools.partial(
      ops.flash_attention, block_q=32, block_k=32, interpret=True))
  if impl == "flash":  # the rule's walk through its kernel pair too
    monkeypatch.setattr(gated_delta, "gated_delta_rule", functools.partial(
        gated_delta.gated_delta_rule, interpret=True))
  x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 16))

  def trunk(policy):
    return SequenceTrunk(blocks=(TransformerBlock(
        norm="rms", mixer=mixer(impl), mlp_ratio=2,
        dtype=jnp.float32),), remat_policy=policy)

  def gradient(policy):
    def loss(params):
      out = trunk(policy).apply(params, x)
      return jnp.sum(jnp.square(out)), out

    return jax.grad(loss, has_aux=True)

  return gradient, trunk(None).init(jax.random.PRNGKey(1), x)


def _kernel_calls(jaxpr, found=None):
  """The `pallas_call`s of a jaxpr and of every jaxpr inside it,
  counted by the kernel function's name."""
  found = collections.Counter() if found is None else found
  for eqn in jaxpr.eqns:
    if eqn.primitive.name == "pallas_call":
      found[eqn.params["jaxpr"].debug_info.func_name] += 1
    else:
      for inner in jax.core.jaxprs_in_params(eqn.params):
        _kernel_calls(inner, found)
  return found


# The flash kernel's backward pass is one program (ISSUE 45); the pair
# `_dkdv_kernel`, `_dq_kernel` is what a sequence takes whose
# accumulators do not fit in VMEM.
FLASH_BACKWARD = {"_fused_bwd_kernel": 1}


# The delta rule's forward pass is the fused program (ISSUE 42); the
# walk's forward kernel runs once under every policy, in each row's own
# `jax.checkpoint` inside `GatedDeltaNet` (the program that writes the
# states for the backward kernel, under the prepared rule).
@pytest.mark.parametrize("mixer,forward,kept,backward", [
    (_gated, "_flash_kernel", 1, FLASH_BACKWARD),
    (_latent, "_flash_kernel", 1, FLASH_BACKWARD),
    (_delta_net, "_fused_kernel", 1,
     {"_forward_kernel": 1, "_backward_kernel": 1})])
def test_save_attention_runs_the_forward_kernel_once(
    monkeypatch, mixer, forward, kept, backward):
  gradient, params = _block_gradient(monkeypatch, mixer, "flash")
  calls = {policy: _kernel_calls(
      jax.make_jaxpr(gradient(policy))(params).jaxpr)
           for policy in (None, "full", "save_attention")}
  assert calls["full"] == {forward: kept + 1, **backward}
  assert calls["save_attention"] == {forward: kept, **backward}
  assert calls[None] == calls["save_attention"]


@pytest.mark.parametrize("mixer,impl", [
    (_gated, "flash"), (_latent, "flash"), (_delta_net, "flash"),
    (_delta_net, None)])
def test_gradients_under_save_attention_are_those_under_full(
    monkeypatch, mixer, impl):
  """Bit for bit, and the block's output with them: the saved arrays
  are what the second run of the mixer's core gives. Without a
  checkpoint XLA fuses the block otherwise on a CPU, as it does
  against `full`: the last bits."""
  gradient, params = _block_gradient(monkeypatch, mixer, impl)
  (full, out_full), (saved, out_saved), (plain, out_plain) = (
      gradient(policy)(params)
      for policy in ("full", "save_attention", None))
  np.testing.assert_array_equal(out_saved, out_full)
  np.testing.assert_allclose(out_saved, out_plain, rtol=1e-4, atol=1e-5)
  for a, b, c in zip(*map(jax.tree_util.tree_leaves,
                          (saved, full, plain))):
    np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("mixer,impl", [
    (_gated, "reference"), (_latent, "reference")])
def test_save_attention_without_the_kernel_is_full(monkeypatch, mixer,
                                                   impl):
  """Nothing of such a block carries a saved name: the gradient's
  jaxpr is that of `full` but for the policy's own printed name."""
  gradient, params = _block_gradient(monkeypatch, mixer, impl)
  full, saved = (str(jax.make_jaxpr(gradient(policy))(params))
                 for policy in ("full", "save_attention"))
  assert "policy=None" in full and "pallas_call" not in full
  assert "policy=<function" in saved and "policy=<function" not in full
  assert re.sub(r"policy=<function.*", "policy=None", saved) == full


@pytest.mark.parametrize("policies,saved,recomputed,share", [
    ((), 0, 0, None), (("save_attention",) * 3, 3, 0, 100.0),
    (("full",) * 2, 0, 2, 0.0), (("dots", "dots_no_batch"), 0, 2, 0.0),
    ((None, "none"), 0, 0, None),
    (("save_attention", "full", "full", "full"), 1, 3, 25.0)])
def test_checkpointed_blocks_are_counted_by_their_policy(
    policies, saved, recomputed, share):
  """One count a traced block under a checkpoint, none without one;
  `lm_attention_saved_share` reads the two, None where neither is."""
  registry = tmetrics.registry()
  registry.reset()
  try:
    x = jnp.zeros((1, 8, 16))
    for policy in policies:
      SequenceTrunk(blocks=(TransformerBlock(
          num_heads=2, head_dim=8, dtype=jnp.float32),),
                    remat_policy=policy).init(jax.random.PRNGKey(0), x)
    assert registry.scalars("trunk.checkpoint.") == {
        name: float(n) for name, n in ((SAVED, saved),
                                       (RECOMPUTED, recomputed)) if n}
    assert lm_attention_saved_share.read({}) == share
  finally:
    registry.reset()
