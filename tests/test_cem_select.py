"""Fused CEM select kernel: interpret-mode parity vs the lax oracle.

The kernel's compiled path is exercised on real TPU hardware by
`chip_smoke.py`; here the pallas interpreter verifies the math —
running-top-k exactness against `cem_select_lax` (which shares the
f32 numerics policy), lax.top_k tie semantics, odd shapes where the
population does not divide the sample block, and block-size
independence.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensor2robot_tpu.ops import cem_select_lax, fused_cem_select


def _inputs(b=4, p=64, c=32, a=4, seed=0, dtype=jnp.float32):
  rng = np.random.default_rng(seed)
  pooled = jnp.asarray(rng.standard_normal((p, b, c)) * 0.3, dtype)
  samples = jnp.asarray(rng.standard_normal((b, p, a)), jnp.float32)
  dense = tuple(
      (jnp.asarray(rng.standard_normal(s) * 0.3, dtype),
       jnp.asarray(rng.standard_normal(s[1]) * 0.3, dtype))
      for s in ((c, 16), (16, 1)))
  return pooled, samples, dense


def _assert_matches(got, want, atol=1e-5):
  for g, w, name in zip(got, want, ("mean", "std", "best_action",
                                    "best_score")):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                               atol=atol, rtol=1e-5, err_msg=name)


class TestFusedCEMSelect:

  @pytest.mark.parametrize("sigmoid", [False, True])
  def test_matches_lax_reference(self, sigmoid):
    pooled, samples, dense = _inputs()
    want = cem_select_lax(pooled, samples, dense, num_elites=6,
                          sigmoid=sigmoid)
    got = fused_cem_select(pooled, samples, dense, num_elites=6,
                           sigmoid=sigmoid, interpret=True)
    _assert_matches(got, want)

  @pytest.mark.parametrize("p,block_p", [(48, 32), (7, 8), (65, 64),
                                         (33, 16)])
  def test_odd_population_vs_block(self, p, block_p):
    """P not a multiple of the sample block: the tail block is masked,
    never selected, and parity holds exactly."""
    pooled, samples, dense = _inputs(p=p, seed=p)
    want = cem_select_lax(pooled, samples, dense, num_elites=5)
    got = fused_cem_select(pooled, samples, dense, num_elites=5,
                           block_p=block_p, interpret=True)
    _assert_matches(got, want)

  def test_elite_ties_match_top_k_order(self):
    """Duplicate scores: selection must break ties toward the lower
    sample index, exactly like lax.top_k — including ties that
    straddle a running-merge block boundary."""
    b, p, c, a = 2, 32, 8, 3
    rng = np.random.default_rng(3)
    # Whole population scores tie in pairs: rows 2k and 2k+1 share
    # identical pooled features (identical scores), and the pairs
    # straddle the block_p=8 boundaries at rows 7/8, 15/16, 23/24.
    base = rng.standard_normal((p // 2, b, c)).astype(np.float32)
    pooled = jnp.asarray(np.repeat(base, 2, axis=0))
    samples = jnp.asarray(rng.standard_normal((b, p, a)), jnp.float32)
    dense = ((jnp.asarray(rng.standard_normal((c, 1)) * 0.5,
                          jnp.float32),
              jnp.zeros((1,), jnp.float32)),)
    want = cem_select_lax(pooled, samples, dense, num_elites=6)
    for block_p in (8, 16, 32):
      got = fused_cem_select(pooled, samples, dense, num_elites=6,
                             block_p=block_p, interpret=True)
      _assert_matches(got, want)

  def test_block_size_independence(self):
    pooled, samples, dense = _inputs(b=6, p=40, seed=9)
    outs = [fused_cem_select(pooled, samples, dense, num_elites=4,
                             block_p=bp, block_b=bb, interpret=True)
            for bp, bb in ((40, 2), (16, 3), (8, 1))]
    for other in outs[1:]:
      _assert_matches(outs[0], other)

  def test_min_std_floor(self):
    """All elites identical → std collapses to the min_std floor."""
    b, p, c, a = 1, 8, 4, 2
    pooled = jnp.ones((p, b, c), jnp.float32)
    samples = jnp.ones((b, p, a), jnp.float32) * 0.5
    dense = ((jnp.ones((c, 1), jnp.float32),
              jnp.zeros((1,), jnp.float32)),)
    mean, std, best, _ = fused_cem_select(
        pooled, samples, dense, num_elites=3, min_std=0.07,
        interpret=True)
    np.testing.assert_allclose(np.asarray(std), 0.07, atol=1e-7)
    np.testing.assert_allclose(np.asarray(mean), 0.5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(best), 0.5, atol=1e-6)

  def test_bf16_operands_accumulate_f32(self):
    """bf16 pooled/params (the production dtype) stay within bf16
    tolerance of the f32 oracle — the f32-accumulation contract."""
    pooled, samples, dense = _inputs(dtype=jnp.bfloat16, seed=5)
    want = cem_select_lax(pooled, samples, dense, num_elites=6)
    got = fused_cem_select(pooled, samples, dense, num_elites=6,
                           interpret=True)
    # Selection may only diverge on genuine bf16 score ties; the
    # statistics must agree to bf16 resolution.
    _assert_matches(got, want, atol=2e-2)

  def test_guards(self):
    pooled, samples, dense = _inputs(p=4)
    with pytest.raises(ValueError, match="num_elites"):
      fused_cem_select(pooled, samples, dense, num_elites=5,
                       interpret=True)
    with pytest.raises(ValueError, match="width 1"):
      bad = ((jnp.ones((32, 2), jnp.float32),
              jnp.zeros((2,), jnp.float32)),)
      fused_cem_select(pooled, samples, bad, num_elites=2,
                       interpret=True)


class TestCEMMaximizeFusedPath:
  """cem_maximize(select_fn=...) must reproduce the default score_fn
  path exactly when the select_fn implements the same contract."""

  def test_select_fn_equals_default_path(self):
    from tensor2robot_tpu.research.qtopt import cem

    b, p, a, c = 3, 16, 2, 8
    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.standard_normal((a, 1)), jnp.float32)

    def score_fn(actions):  # [B, P, A] -> [B, P]
      return (actions @ w)[..., 0] - jnp.sum(actions ** 2, -1)

    def select_fn(actions, min_std):
      scores = score_fn(actions)
      es, ei = jax.lax.top_k(scores, 3)
      elites = jnp.take_along_axis(actions, ei[..., None], axis=1)
      return (jnp.mean(elites, axis=1),
              jnp.maximum(jnp.std(elites, axis=1), min_std),
              elites[:, 0], es[:, 0])

    key = jax.random.PRNGKey(0)
    kwargs = dict(batch_size=b, action_dim=a, iterations=3,
                  population=p, num_elites=3)
    base = cem.cem_maximize(score_fn, key, **kwargs)
    fused = cem.cem_maximize(None, key, select_fn=select_fn, **kwargs)
    for x, y in zip(base, fused):
      np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
