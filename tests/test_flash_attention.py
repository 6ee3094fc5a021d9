"""Pallas flash attention: interpret-mode numerics on the CPU suite.

The kernel's compiled path is exercised on real TPU hardware (the
benchmark's cells, `chip_smoke.py`); here the pallas interpreter
verifies the math — exactness against the reference oracle, causal
masking, block-size independence.
"""

import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensor2robot_tpu.ops import flash_attention
from tensor2robot_tpu.parallel import attention_reference
from tensor2robot_tpu.telemetry import metrics as tmetrics

B, T, H, D = 2, 256, 2, 64


def _qkv(seed=0, dtype=jnp.float32, dk=D, dv=D):
  rng = np.random.default_rng(seed)
  return tuple(
      jnp.asarray(rng.standard_normal((B, T, H, d)), dtype)
      for d in (dk, dk, dv))


# Keys and queries of one width over values of another (latent
# attention: 192 over 128), beside the equal widths.
WIDTHS = [(64, 64), (48, 32), (16, 40)]


class TestFlashAttention:

  @pytest.mark.parametrize("causal", [False, True])
  def test_matches_reference(self, causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=64,
                          block_k=64, interpret=True)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)

  @pytest.mark.parametrize("dk,dv", WIDTHS)
  def test_key_width_other_than_value_width(self, dk, dv):
    """Forward and all three gradients against materialised attention;
    the scale is the keys' width's."""
    q, k, v = _qkv(9, dk=dk, dv=dv)
    probe = jnp.asarray(np.random.default_rng(10).standard_normal(
        (B, T, H, dv)), jnp.float32)

    def flash_loss(q, k, v):
      out = flash_attention(q, k, v, causal=True, block_q=64,
                            block_k=128, interpret=True)
      assert out.shape == (B, T, H, dv)
      return jnp.sum(out * probe), out

    def ref_loss(q, k, v):
      out = attention_reference(q, k, v, causal=True)
      return jnp.sum(out * probe), out

    (_, out), got = jax.value_and_grad(
        flash_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, ref), want = jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    for g, w in zip(got, want):
      assert g.shape == w.shape
      np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                 atol=5e-5, rtol=5e-5)

  def test_no_value_is_padded_to_the_keys_width(self):
    """Every operand and result of the two Pallas programs has its
    own width: v, o, dO and dv the values', q, k, dq and dk the keys'
    (P V and dV are then `dv` wide, in VMEM and in HBM)."""
    dk, dv = 48, 32
    q, k, v = _qkv(11, dk=dk, dv=dv)

    def loss(q, k, v):
      return jnp.sum(flash_attention(q, k, v, causal=True, block_q=64,
                                     block_k=64, interpret=True))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    calls = []

    def walk(jaxpr):
      for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
          calls.append((
              [a.aval.shape[-1] for a in eqn.invars
               if a.aval.shape[-1] != 1],
              [a.aval.shape[-1] for a in eqn.outvars
               if a.aval.shape[-1] != 1]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
          walk(sub)

    walk(jaxpr.jaxpr)
    assert calls == [
        ([dk, dk, dv], [dv]),              # forward: q k v -> o
        ([dk, dk, dv, dv], [dk, dk, dv])]  # q k v dO -> dq dk dv

  def test_block_size_independence(self):
    """The online softmax must not depend on the tiling."""
    q, k, v = _qkv(1)
    outs = [
        np.asarray(flash_attention(q, k, v, causal=True, block_q=bq,
                                   block_k=bk, interpret=True))
        for bq, bk in ((256, 256), (64, 128), (32, 32))
    ]
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-6)
    np.testing.assert_allclose(outs[0], outs[2], atol=2e-6)

  def test_odd_length_auto_blocks(self):
    """T not divisible by the requested blocks shrinks them instead of
    failing — exactness is independent of the tiling."""
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 96, 2, 16)),
                           jnp.float32) for _ in range(3))
    out = flash_attention(q, k, v, causal=True, block_q=64,
                          block_k=64, interpret=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)

  @pytest.mark.parametrize("causal", [False, True])
  def test_gradients_match_reference(self, causal):
    """The flash custom VJP (logsumexp recompute) == autodiff oracle."""
    q, k, v = _qkv(5)

    def flash_loss(q, k, v):
      return jnp.sum(flash_attention(
          q, k, v, causal=causal, block_q=64, block_k=64,
          interpret=True) ** 2)

    def ref_loss(q, k, v):
      return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
      np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                 atol=5e-5, rtol=5e-5)

  @pytest.mark.parametrize("causal", [False, True])
  def test_lse_gradients_match_reference(self, causal):
    """Both outputs of `flash_attention_with_lse` carry gradients.

    The lse cotangent folds into the softmax-jacobian diagonal
    (∂lse/∂s = p); the oracle is autodiff through a materialized
    softmax + logsumexp. This is what makes the lse-weighted ring
    combine trainable.
    """
    from tensor2robot_tpu.ops.flash_attention import (
        flash_attention_with_lse,
    )
    q, k, v = _qkv(7)
    scale = 1.0 / np.sqrt(D)

    def flash_loss(q, k, v):
      out, lse = flash_attention_with_lse(
          q, k, v, causal=causal, block_q=64, block_k=64,
          interpret=True)
      # A loss using BOTH outputs, so both cotangents are nonzero.
      return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def ref_loss(q, k, v):
      s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
      if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -1e30)
      lse = jax.scipy.special.logsumexp(s, axis=-1)  # [B, H, T]
      p = jnp.exp(s - lse[..., None])
      out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
      return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
      np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                 atol=5e-5, rtol=5e-5)

  def test_matches_ring_attention_math(self):
    """Within-chip tiling and across-chip ring agree (same algorithm)."""
    from tensor2robot_tpu.parallel import (
        SEQ_AXIS,
        create_mesh,
        ring_attention,
        sequence_sharding,
    )
    q, k, v = _qkv(2)
    mesh = create_mesh({SEQ_AXIS: 8})
    sharding = sequence_sharding(mesh)
    ring = ring_attention(
        *(jax.device_put(x, sharding) for x in (q, k, v)),
        mesh=mesh, causal=True)
    flash = flash_attention(q, k, v, causal=True, block_q=64,
                            block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(flash),
                               atol=2e-5, rtol=2e-5)


def _with_out(q, k, v):
  return flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                         interpret=True)


def _with_lse(q, k, v):
  from tensor2robot_tpu.ops.flash_attention import (
      flash_attention_with_lse)
  return flash_attention_with_lse(q, k, v, causal=True, block_q=64,
                                  block_k=64, interpret=True)


@pytest.mark.parametrize("attend", [_with_out, _with_lse])
@pytest.mark.parametrize("dk,dv", [(64, 64), (48, 32)])
def test_residual_names_are_identities_outside_a_checkpoint(
    monkeypatch, attend, dk, dv):
  """The forward rule names its two results for a checkpoint's policy
  (`SAVED_RESIDUAL_NAMES`); with no such checkpoint around it the
  gradient, which is what traces the rule, lowers to the HLO text it
  lowers to without the names."""
  module = importlib.import_module(
      "tensor2robot_tpu.ops.flash_attention")
  qkv = _qkv(dk=dk, dv=dv)
  seen = []

  def lowered():
    grad = jax.grad(lambda *args: sum(
        jnp.sum(jnp.sin(out)) for out in jax.tree_util.tree_leaves(
            attend(*args))), argnums=(0, 1, 2))
    # MLIR numbers its private functions as they come: not compared.
    return re.sub(r"(@\w+?)_\d+\b", r"\1",
                  jax.jit(grad).lower(*qkv).as_text())

  named = lowered()
  with monkeypatch.context() as patch:
    patch.setattr(module, "checkpoint_name",
                  lambda x, name: seen.append(name) or x)
    jax.clear_caches()  # or the rule's trace with the names is reused
    unnamed = lowered()
  jax.clear_caches()  # no trace made without the names outlives this
  assert unnamed == named
  assert tuple(seen) == module.SAVED_RESIDUAL_NAMES  # the rule ran


FUSED = "flash_attention.backward.fused_traces"
PAIRED = "flash_attention.backward.paired_traces"


def _backward_counts():
  counts = tmetrics.registry().scalars("flash_attention.backward.")
  return counts.get(FUSED, 0.0), counts.get(PAIRED, 0.0)


# t, query heads, key-value heads, keys' width, values' width, causal,
# window, requested block, whether the lse carries a cotangent. The
# window's block is the power of two at or under it: 16 under a window
# of 16 (one block, two visited), 32 at 32, 32 under 48 (three visited);
# a window of T is the causal program; 96 positions halve a block of 64.
FUSED_CASES = {
    "full": (128, 2, 2, 16, 16, False, None, 32, False),
    "causal": (128, 2, 2, 16, 16, True, None, 32, False),
    "causal_unequal_blocks": (128, 2, 2, 16, 16, True, None, (32, 64),
                              False),
    "keys_192_over_values_128": (128, 2, 2, 192, 128, True, None, 64,
                                 False),
    "keys_24_over_values_16": (128, 2, 2, 24, 16, True, None, 32, False),
    "12_heads_over_2": (128, 12, 2, 16, 16, True, None, 32, False),
    "12_heads_over_2_full": (64, 12, 2, 16, 16, False, None, 32, False),
    "window_under_the_block": (128, 2, 2, 16, 16, True, 16, 32, False),
    "window_at_the_block": (128, 2, 2, 16, 16, True, 32, 32, False),
    "window_over_the_block": (128, 2, 2, 16, 16, True, 48, 32, False),
    "window_of_t": (128, 2, 2, 16, 16, True, 128, 32, False),
    "window_over_a_group": (128, 4, 2, 16, 16, True, 40, 32, False),
    "lse_cotangent": (128, 2, 2, 16, 16, True, None, 32, True),
    "lse_cotangent_full_over_a_group": (128, 4, 2, 24, 16, False, None,
                                        32, True),
    "lse_cotangent_under_a_window": (128, 4, 2, 16, 16, True, 24, 32,
                                     True),
    "halved_block": (96, 2, 2, 16, 16, True, None, 64, False),
}


@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_fused_backward_is_the_pair_bit_for_bit(monkeypatch, case):
  """The one backward program makes each visited tile's p and ds once
  and adds every sum's terms in the pair's order: in the interpreter
  at float32 its dq, dk and dv are the pair's to the bit. The pair runs
  where no accumulator fits (a budget of nothing)."""
  module = importlib.import_module(
      "tensor2robot_tpu.ops.flash_attention")
  t, h, kv, dk, dv, causal, window, block, with_lse = FUSED_CASES[case]
  block_q, block_k = block if isinstance(block, tuple) else (block, block)
  rng = np.random.default_rng(45)
  q, k, v, do = (
      jnp.asarray(rng.standard_normal((2, t, heads, d)), jnp.float32)
      for heads, d in ((h, dk), (kv, dk), (kv, dv), (h, dv)))
  dlse = jnp.asarray(rng.standard_normal((2, h, t)), jnp.float32)

  def attend(q, k, v):
    # Not jitted: a jit's cache would hand the second budget the
    # program traced under the first.
    return module.flash_attention_with_lse.__wrapped__(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=True, window=window)

  def gradients(budget):
    tmetrics.reset_for_tests()
    with monkeypatch.context() as patch:
      if budget is not None:
        patch.setattr(module, "_FUSED_BACKWARD_BUDGET", budget)
      _, vjp = jax.vjp(attend, q, k, v)
      got = vjp((do, dlse if with_lse else jnp.zeros_like(dlse)))
    return got, _backward_counts()

  fused, fused_counts = gradients(None)
  paired, paired_counts = gradients(0)
  tmetrics.reset_for_tests()
  assert fused_counts == (1.0, 0.0) and paired_counts == (0.0, 1.0)
  for name, a, b in zip(("dq", "dk", "dv"), fused, paired):
    assert a.shape == b.shape, name
    assert np.abs(np.asarray(b)).max() > 0, name
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)


# rows, t, query heads, key-value heads, keys' and values' width, dtype,
# window -> whether a sequence's accumulators fit the fused program: the
# three language-model cells' layers, the SNAIL trunks' and the ring's
# longest (32,768 at 64 wide, which VMEM pads to 128); then what does
# not fit (the same in float32: compiled for a v5e it wants 80 MiB).
@pytest.mark.parametrize("shape,fused", [
    ((2, 8192, 32, 32, 192, 128, jnp.bfloat16, None), True),
    ((4, 8192, 48, 8, 128, 128, jnp.bfloat16, None), True),
    ((4, 8192, 64, 8, 128, 128, jnp.bfloat16, 512), True),
    ((4, 8192, 16, 16, 256, 256, jnp.bfloat16, None), True),
    ((1, 32768, 8, 8, 64, 64, jnp.bfloat16, None), True),
    ((1, 32768, 8, 8, 64, 64, jnp.float32, None), False),
    ((1, 65536, 8, 8, 128, 128, jnp.bfloat16, None), False),
    ((1, 32768, 8, 2, 128, 128, jnp.bfloat16, None), False),
    ((1, 32768, 8, 8, 256, 256, jnp.float32, None), False),
])
def test_the_byte_count_picks_the_backward_program(shape, fused):
  """What `_flash_bwd_impl` traces is read off the shapes, and counted
  once a traced call; nothing is compiled or run here."""
  b, t, h, kv, dk, dv, dtype, window = shape
  q, k, v = (jax.ShapeDtypeStruct((b, t, heads, d), dtype)
             for heads, d in ((h, dk), (kv, dk), (kv, dv)))
  tmetrics.reset_for_tests()
  grads = jax.eval_shape(
      jax.grad(lambda q, k, v: jnp.sum(flash_attention(
          q, k, v, causal=True, window=window).astype(jnp.float32)),
               argnums=(0, 1, 2)), q, k, v)
  counts = _backward_counts()
  tmetrics.reset_for_tests()
  assert counts == ((1.0, 0.0) if fused else (0.0, 1.0))
  assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]


# t, keys' width, requested blocks, window -> the fused program's
# blocks: the caller's at keys of one 128-lane tile; at wider keys the
# key block halved until it is no more than 1024 (under a window both
# blocks, which are one size), so it divides what the caller's does: a
# T that is its own block (1536, 1544, 2032) is never cut to a 1024
# that leaves its last rows unvisited.
@pytest.mark.parametrize("t,d,blocks,window,fused_blocks", [
    (8192, 64, (1024, 2048), None, (1024, 2048)),
    (8192, 128, (1024, 2048), None, (1024, 2048)),
    (8192, 192, (1024, 2048), None, (1024, 1024)),
    (8192, 256, (1024, 2048), None, (1024, 1024)),
    (8192, 256, (1024, 2048), 512, (512, 512)),
    (8192, 256, (2048, 2048), 4096, (1024, 1024)),
    (128, 192, (64, 64), None, (64, 64)),
    (1536, 192, (1024, 2048), None, (512, 768)),
    (3072, 192, (1024, 1536), None, (1024, 768)),
    (1536, 128, (1024, 2048), None, (512, 1536)),
    (6144, 256, (1024, 2048), None, (1024, 1024)),
    (1544, 192, (1024, 2048), None, (8, 1544)),
    (2032, 256, (1024, 2048), None, (16, 1016)),
    (3072, 256, (1536, 1536), 2000, (1024, 1024)),
])
def test_wide_keys_take_key_blocks_of_1024_in_the_fused_program(
    t, d, blocks, window, fused_blocks):
  """From the blocks `_blocks_and_window` hands the backward pass at
  this T: whatever comes out still divides T."""
  module = importlib.import_module(
      "tensor2robot_tpu.ops.flash_attention")
  q = jax.ShapeDtypeStruct((1, t, 1, d), jnp.float32)
  block_q, block_k, window = module._blocks_and_window(
      q, q, True, *blocks, window)
  got = module._fused_blocks(d, block_q, block_k, window)
  assert got == fused_blocks
  assert t % got[0] == 0 and t % got[1] == 0


# t, requested blocks, window, `_FUSED_WIDE_BLOCK` (None: the module's),
# the fused program's key block: keys of 192 over values of 128
# throughout. At 1,536 positions the default blocks come down to 512 x
# 1536 (T is its own key block) and the fused program's key block to
# 768: a cap of 1024 taken without regard to T would visit one key block
# of 1024 and leave the last 512 keys out of all three gradients. Under
# a window both blocks are halved (32 x 32 under a window of 40: 16 x 16).
@pytest.mark.parametrize("t,blocks,window,wide,fused_k", [
    (128, (64, 64), None, 32, 32),
    (96, (1024, 2048), None, 64, 48),
    (128, (64, 64), 40, 16, 16),
    (1536, (1024, 2048), None, None, 768),
])
def test_fused_backward_at_its_own_key_blocks_is_the_pair_at_them(
    monkeypatch, t, blocks, window, wide, fused_k):
  """Where the fused program halves the key block (keys wider than 128
  lanes), its sums are the pair's at those blocks to the bit (dQ gets
  its terms a narrower key block at a time, in the same ascending
  order), and the gradients of materialised attention."""
  module = importlib.import_module(
      "tensor2robot_tpu.ops.flash_attention")
  rng = np.random.default_rng(46)
  q, k, v, do = (
      jnp.asarray(rng.standard_normal((1, t, 2, d)), jnp.float32)
      for d in (192, 192, 128, 128))
  block_q, block_k, _ = module._blocks_and_window(q, k, True, *blocks,
                                                  window)
  out, lse = module._flash_forward_impl(q, k, v, True, block_q, block_k,
                                        True, window)

  def gradients(block_q, block_k, budget):  # of one forward pass
    with monkeypatch.context() as patch:
      if wide is not None:
        patch.setattr(module, "_FUSED_WIDE_BLOCK", wide)
      if budget is not None:
        patch.setattr(module, "_FUSED_BACKWARD_BUDGET", budget)
      return module._flash_bwd_impl(q, k, v, out, lse, do,
                                    jnp.zeros_like(lse), True, block_q,
                                    block_k, True, window)

  fused = gradients(block_q, block_k, None)
  paired = gradients(block_q if window is None else fused_k, fused_k, 0)
  for a, b in zip(fused, paired):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

  def reference(q, k, v):  # materialised, under the band where one is
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (j <= i) & (window is None or j > i - window)
    return jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.where(seen, s, -1e30), -1), v)

  _, vjp = jax.vjp(reference, q, k, v)
  for a, b in zip(fused, vjp(do)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=5e-5, rtol=5e-5)
