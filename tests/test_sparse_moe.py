"""The dropless expert layer over the experts held (`parallel/moe.py`:
`SparseMoE`, `held_experts_ffn`; ISSUE 34) against the plain reference's
loop with masks, at small sizes in float32: every expert held; a chip's
share; the shares of an expert-parallel deployment adding up to the
layer; nothing dropped whatever the router does."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark.reference import qwen3_next as ref  # noqa: E402
from tensor2robot_tpu.parallel import moe  # noqa: E402

WIDTH, EXPERTS, K, F = 16, 8, 3, 12


def _layer(held=EXPERTS, first=0, shared=F, k=K):
  return moe.SparseMoE(num_experts=EXPERTS, experts_held=held,
                       first_expert=first, k=k, expert_width=F,
                       shared_width=shared, dtype=jnp.float32)


def _params(seed=1, tokens=8):
  """All 8 experts' weights, randomised off flax's zero-free init."""
  x = jnp.zeros((1, tokens, WIDTH))
  params = _layer().init(jax.random.PRNGKey(seed), x)["params"]
  return jax.tree_util.tree_map(lambda p: p * 1.5, params)


def _share(params, first, held):
  """The parameters a chip holding experts first..first+held-1 has."""
  share = dict(params)
  for name in ("experts_gate", "experts_up", "experts_down"):
    share[name] = params[name][first:first + held]
  return share


def _flat(params):
  out = {}
  for key, value in params.items():
    if isinstance(value, dict):
      out.update({f"{key}/{k}": v for k, v in value.items()})
    else:
      out[key] = value
  return out


def _model(held=EXPERTS, first=0, k=K):
  return {"experts_held": held, "first_expert": first,
          "num_experts_per_tok": k, "norm_topk_prob": True}


def _apply(layer, params, x):
  out, sown = layer.apply({"params": params}, x,
                          mutable=["moe_counters"])
  return out, {name: float(value[0])
               for name, value in sown["moe_counters"].items()}


@pytest.mark.parametrize("tokens", [40, 700])
def test_every_expert_held_equals_the_reference(tokens):
  x = jax.random.normal(jax.random.PRNGKey(0), (2, tokens, WIDTH))
  params = _params()
  got, counters = _apply(_layer(), params, x)
  want = ref._ffn(x.reshape(-1, WIDTH), _flat(params), _model(), False)
  np.testing.assert_allclose(got.reshape(-1, WIDTH), want, atol=2e-5,
                             rtol=1e-4)
  assert counters["assignments_here_share"] == 1.0
  assert counters["dropped_assignments"] == 0.0
  assert counters["expert_load_max_over_mean"] >= 1.0


@pytest.mark.parametrize("first,held", [(0, 2), (2, 4), (6, 2)])
def test_a_share_equals_the_reference_given_the_same_share(first, held):
  x = jax.random.normal(jax.random.PRNGKey(2), (3, 50, WIDTH))
  share = _share(_params(), first, held)
  got, counters = _apply(_layer(held, first), share, x)
  want = ref._ffn(x.reshape(-1, WIDTH), _flat(share),
                  _model(held, first), False)
  np.testing.assert_allclose(got.reshape(-1, WIDTH), want, atol=2e-5,
                             rtol=1e-4)
  # About held / 8 of the assignments fall here.
  assert abs(counters["assignments_here_share"] - held / EXPERTS) < 0.15


def test_the_shares_add_up_to_the_uncut_layer():
  """Four chips of two experts each (model-configs guide, section 4):
  the routed parts of all shares, with the shared expert that every
  chip computes alike counted once, equal the layer with all eight
  experts held, and the reference's."""
  x = jax.random.normal(jax.random.PRNGKey(3), (2, 60, WIDTH))
  params = _params()
  whole, _ = _apply(_layer(), params, x)
  no_shared = {k: v for k, v in params.items()
               if not k.startswith("shared")}
  shared_only = whole - _apply(_layer(shared=0), no_shared, x)[0]
  routed = sum(
      _apply(_layer(2, first, shared=0), _share(no_shared, first, 2),
             x)[0]
      for first in (0, 2, 4, 6))
  np.testing.assert_allclose(routed + shared_only, whole, atol=3e-5,
                             rtol=1e-4)
  want = ref._ffn(x.reshape(-1, WIDTH), _flat(params), _model(), False)
  np.testing.assert_allclose((routed + shared_only).reshape(-1, WIDTH),
                             want, atol=3e-5, rtol=1e-4)
  shares = [_apply(_layer(2, first, shared=0),
                   _share(no_shared, first, 2), x)[1]
            ["assignments_here_share"] for first in (0, 2, 4, 6)]
  assert abs(sum(shares) - 1.0) < 1e-6


@pytest.mark.parametrize("first,held", [(0, 2), (0, 8), (4, 2)])
def test_nothing_is_dropped_under_a_router_pushed_onto_one_expert(
    first, held):
  """Every token's first choice is expert 0 and its second expert 1:
  where the chip holds them, their load is a whole token count each
  where uniform routing gives three eighths, several rounds run, and
  every assignment is computed."""
  tokens = 2048
  x = jax.random.normal(jax.random.PRNGKey(4), (1, tokens, WIDTH))
  params = _share(_params(tokens=tokens), first, held)
  # Two router columns far above the others: 0 wins, then 1, always.
  x = jnp.abs(x)  # so that x . ones >> 0
  params["router"] = params["router"].at[:, 0].set(
      100.0 / WIDTH).at[:, 1].set(50.0 / WIDTH)
  got, counters = _apply(_layer(held, first), params, x)
  experts, _ = moe.route_top_k(x.reshape(-1, WIDTH), params["router"], K)
  assert np.all(np.asarray(experts[:, 0]) == 0)
  assert np.all(np.asarray(experts[:, 1]) == 1)
  assert counters["dropped_assignments"] == 0.0
  want = ref._ffn(x.reshape(-1, WIDTH), _flat(params),
                  _model(held, first), False)
  np.testing.assert_allclose(got.reshape(-1, WIDTH), want, atol=5e-5,
                             rtol=2e-4)
  if first == 0:
    rows = moe.round_rows(tokens * K, held, EXPERTS)
    here = counters["assignments_here_share"] * tokens * K
    assert held == EXPERTS or here > rows  # more than one round ran
    assert counters["expert_load_max_over_mean"] >= (
        2.0 if held == EXPERTS else 1.0)


def test_the_counter_reads_what_the_grouped_products_were_given(
    monkeypatch):
  """`dropped_assignments` is the held assignments less the rows of
  the groups that the rounds' grouped products were given: with every
  group of every round cut to 3 rows at most it reads the
  shortfall."""
  sizes = moe._group_sizes
  monkeypatch.setattr(
      moe, "_group_sizes",
      lambda *args: jnp.minimum(sizes(*args), 3))
  x = jax.random.normal(jax.random.PRNGKey(5), (1, 256, WIDTH))
  params = _share(_params(tokens=256), 0, 2)
  _, counters = _apply(_layer(2, 0), params, x)
  experts, _ = moe.route_top_k(x.reshape(-1, WIDTH), params["router"], K)
  load = np.bincount(np.asarray(experts).reshape(-1),
                     minlength=EXPERTS)[:2]
  assert load.min() > 3
  rows = moe.round_rows(256 * K, 2, EXPERTS)
  assert load.sum() <= rows  # one round ran
  assert counters["dropped_assignments"] == float(load.sum() - 6)


def _unwritten_rows_ragged_dot(real):
  """`lax.ragged_dot` as the TPU computes it (a chip run of PR 34): a
  row of `lhs` that belongs to no group is nobody's to write, in the
  result and in the gradient handed back for `lhs`. Here such rows
  read 1e9 where the chip leaves what the memory held."""

  def unwritten(lhs, sizes):
    return (jnp.arange(lhs.shape[0]) >= jnp.sum(sizes))[:, None]

  @jax.custom_vjp
  def dot(lhs, rhs, sizes):
    return jnp.where(unwritten(lhs, sizes), 1e9, real(lhs, rhs, sizes))

  def fwd(lhs, rhs, sizes):
    return dot(lhs, rhs, sizes), (lhs, rhs, sizes)

  def bwd(saved, cotangent):
    lhs, rhs, sizes = saved
    d_lhs, d_rhs = jax.vjp(lambda l, r: real(l, r, sizes), lhs, rhs)[1](
        cotangent)
    return (jnp.where(unwritten(lhs, sizes), 1e9, d_lhs).astype(
        lhs.dtype), d_rhs, None)

  dot.defvjp(fwd, bwd)
  return lambda lhs, rhs, sizes, **kwargs: dot(lhs, rhs, sizes)


@pytest.mark.parametrize("unwritten_rows", [False, True])
def test_gradients_equal_the_references(monkeypatch, unwritten_rows):
  """Also where the grouped product leaves the rows of no group
  unwritten, as the TPU's does: half of a round's rows are such."""
  if unwritten_rows:
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _unwritten_rows_ragged_dot(jax.lax.ragged_dot))
  x = jax.random.normal(jax.random.PRNGKey(5), (2, 300, WIDTH))
  share = _share(_params(), 2, 4)
  probe = jax.random.normal(jax.random.PRNGKey(6), (600, WIDTH))
  layer = _layer(4, 2)

  def program(params, x):
    return jnp.sum(_apply_out(layer, params, x).reshape(-1, WIDTH)
                   * probe)

  def _apply_out(layer, params, x):
    return layer.apply({"params": params}, x,
                       mutable=["moe_counters"])[0]

  def reference(params, x):
    return jnp.sum(ref._ffn(x.reshape(-1, WIDTH), _flat(params),
                            _model(4, 2), False) * probe)

  got = jax.grad(program, argnums=(0, 1))(share, x)
  want = jax.grad(reference, argnums=(0, 1))(share, x)
  for a, b in zip(jax.tree_util.tree_leaves(got),
                  jax.tree_util.tree_leaves(want)):
    np.testing.assert_allclose(a, b, atol=3e-4, rtol=2e-3)


def test_round_rows_is_twice_the_uniform_share_in_whole_tiles():
  assert moe.round_rows(327680, 32, 512) == 40960
  assert moe.round_rows(327680, 512, 512) == 327680
  assert moe.round_rows(100, 2, 8) == 100  # never more than all
  assert moe.round_rows(6144, 2, 8) == 3072


def test_top_k_weights_sum_to_one_over_the_chosen():
  x = jax.random.normal(jax.random.PRNGKey(7), (20, WIDTH))
  router = jax.random.normal(jax.random.PRNGKey(8), (WIDTH, EXPERTS))
  experts, weights = moe.route_top_k(x, router, K)
  np.testing.assert_allclose(jnp.sum(weights, -1), 1.0, rtol=1e-6)
  probs = jax.nn.softmax(x @ router, axis=-1)
  np.testing.assert_array_equal(experts, jax.lax.top_k(probs, K)[1])
  _, raw = moe.route_top_k(x, router, K, normalise=False)
  np.testing.assert_allclose(raw, jax.lax.top_k(probs, K)[0], rtol=1e-5)
