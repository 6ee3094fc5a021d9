"""Pallas flash attention: interpret-mode numerics on the CPU suite.

The kernel's compiled path is exercised on real TPU hardware (bench /
driver); here the pallas interpreter verifies the math — exactness
against the reference oracle, causal masking, block-size independence.
"""

import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensor2robot_tpu.ops import flash_attention
from tensor2robot_tpu.parallel import attention_reference

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
    """Every operand and result of the three Pallas programs has its
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
        ([dk, dk, dv, dv], [dk, dv]),      # q k v dO -> dk dv
        ([dk, dk, dv, dv], [dk])]          # q k v dO -> dq

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
