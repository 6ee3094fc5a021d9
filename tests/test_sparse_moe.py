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

from benchmark.layer_metrics import lm_moe_rounds_run  # noqa: E402
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
    assert counters["rounds_run"] == -(-round(here) // rows)
    # What the benchmark's reader makes of such steps' log records.
    records = [{"moe.rounds_run": counters["rounds_run"]}] * 2
    assert lm_moe_rounds_run.read({"records": records}) == (
        1.0 if held == EXPERTS else 2.0)
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


# The loop over rounds alone, 1,500 tokens of 2 choices over 16 experts
# of which this chip holds 4 and 5: rounds of 1,024 rows, which do not
# divide the 3,000 assignments.
ROUNDS = dict(tokens=1500, k=2, experts=16, held=2, first=4)


def _routing(case, seed=9):
  """(experts, weights) [N, k] of one way to route: `uniform` (the top
  k of random scores), `pushed` (every token's first choice is one of
  the two held experts, its second is not held) and `all_here` (both
  choices of every token are the two held experts)."""
  n, k, e = ROUNDS["tokens"], ROUNDS["k"], ROUNDS["experts"]
  first, held = ROUNDS["first"], ROUNDS["held"]
  scores, coin = jax.random.split(jax.random.PRNGKey(seed))
  weights, experts = jax.lax.top_k(
      jax.nn.softmax(jax.random.normal(scores, (n, e))), k)
  heads = first + (jax.random.uniform(coin, (n,)) < 0.6).astype(jnp.int32)
  if case == "pushed":
    away = jnp.where(
        (experts[:, 1] >= first) & (experts[:, 1] < first + held),
        experts[:, 1] + held, experts[:, 1])
    experts = jnp.stack([heads, away], axis=1)
  elif case == "all_here":
    experts = jnp.stack([heads, 2 * first + 1 - heads], axis=1)
  return experts.astype(jnp.int32), weights / jnp.sum(weights, -1,
                                                      keepdims=True)


def _expert_weights(seed=10):
  keys = jax.random.split(jax.random.PRNGKey(seed), 3)
  held = ROUNDS["held"]
  return (jax.random.normal(keys[0], (held, WIDTH, F)) * 0.4,
          jax.random.normal(keys[1], (held, WIDTH, F)) * 0.4,
          jax.random.normal(keys[2], (held, F, WIDTH)) * 0.4)


def _held(x, experts, weights, w_gate, w_up, w_down):
  return moe.held_experts_ffn(
      x, experts, weights, w_gate, w_up, w_down,
      first_expert=ROUNDS["first"], num_experts=ROUNDS["experts"],
      dtype=jnp.float32)


def _plain_held(x, experts, weights, w_gate, w_up, w_down):
  """The held experts one after the other over every token, each
  weighted by what the token's choices give it: the reference's loop
  with masks (`ref._ffn`), the routing handed in."""
  out = jnp.zeros_like(x)
  for e in range(ROUNDS["held"]):
    weight = jnp.sum(
        jnp.where(experts == ROUNDS["first"] + e, weights, 0.0), axis=-1)
    out += weight[:, None] * ref._gated_unit(
        x, w_gate[e], w_up[e], w_down[e], False)
  return out


@pytest.mark.parametrize("unwritten_rows", [False, True])
@pytest.mark.parametrize("case,rounds", [
    ("uniform", 1), ("pushed", 2), ("all_here", 3)])
def test_the_rounds_that_run_equal_the_reference(monkeypatch, case,
                                                 rounds, unwritten_rows):
  """Output and the five gradients, whatever the number of rounds that
  the routing makes run, also where the grouped product leaves the
  rows of no group unwritten, as the TPU's does; `rounds_run` is the
  rounds that hold a held assignment."""
  if unwritten_rows:
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _unwritten_rows_ragged_dot(jax.lax.ragged_dot))
  n, k = ROUNDS["tokens"], ROUNDS["k"]
  x = jax.random.normal(jax.random.PRNGKey(11), (n, WIDTH))
  experts, weights = _routing(case)
  args = (x, weights) + _expert_weights()
  probe = jax.random.normal(jax.random.PRNGKey(12), (n, WIDTH))

  def program(x, weights, *matrices):
    out, counters = _held(x, experts, weights, *matrices)
    return jnp.sum(out * probe), (out, counters)

  def reference(x, weights, *matrices):
    out = _plain_held(x, experts, weights, *matrices)
    return jnp.sum(out * probe), out

  got_grads, (got, counters) = jax.grad(
      program, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
  want_grads, want = jax.grad(
      reference, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
  np.testing.assert_allclose(got, want, atol=3e-5, rtol=2e-4)
  for name, a, b in zip(("x", "weights", "gate", "up", "down"),
                        got_grads, want_grads):
    np.testing.assert_allclose(a, b, atol=3e-4, rtol=2e-3, err_msg=name)
  rows = moe.round_rows(n * k, ROUNDS["held"], ROUNDS["experts"])
  local = np.asarray(experts) - ROUNDS["first"]
  here = int(np.sum((local >= 0) & (local < ROUNDS["held"])))
  assert rows == 1024 and (n * k) % rows
  assert counters["rounds_run"] == -(-here // rows) == rounds
  assert counters["dropped_assignments"] == 0.0
  assert counters["assignments_here_share"] == pytest.approx(
      here / (n * k))


def _equations(jaxpr, into_loops=True):
  """Every equation of a jaxpr and of the jaxprs inside it; without
  `into_loops` a `while` stands for itself, its condition and body
  unopened."""
  for eqn in jaxpr.eqns:
    yield eqn
    if eqn.primitive.name == "while" and not into_loops:
      continue
    for value in eqn.params.values():
      for inner in value if isinstance(value, (tuple, list)) else (value,):
        inner = getattr(inner, "jaxpr", inner)
        if hasattr(inner, "eqns"):
          yield from _equations(inner, into_loops)


def test_the_gradients_program_loops_over_the_rounds_that_run():
  """In the program of the layer's gradient the rounds are two loops
  whose length the routing decides, one forward and one backward: no
  scan over all the rounds there could be, no conditional that skips
  one, and nothing the size of the layer's result made inside a
  loop."""
  n = ROUNDS["tokens"]
  x = jax.random.normal(jax.random.PRNGKey(11), (n, WIDTH))
  experts, weights = _routing("pushed")

  def program(x, weights, *matrices):
    return jnp.sum(_held(x, experts, weights, *matrices)[0] ** 2)

  jaxpr = jax.make_jaxpr(jax.grad(program, argnums=(0, 1, 2, 3, 4)))(
      x, weights, *_expert_weights()).jaxpr
  equations = list(_equations(jaxpr))
  names = [eqn.primitive.name for eqn in equations]
  assert names.count("while") == 2
  assert "scan" not in names and "cond" not in names
  assert names.count("ragged_dot_general") == 3 + 9  # one round each way
  for loop in (eqn for eqn in equations if eqn.primitive.name == "while"):
    # A loop's length is read off the routing, not a constant.
    assert loop.params["cond_nconsts"] > 0
    body = list(_equations(loop.params["body_jaxpr"].jaxpr))
    assert sum(eqn.primitive.name == "ragged_dot_general"
               for eqn in body) in (3, 9)
    made = [eqn for eqn in body
            if eqn.primitive.name in ("broadcast_in_dim", "iota", "full")]
    assert made and not any(
        out.aval.shape == (n, WIDTH) for eqn in made
        for out in eqn.outvars)


def test_the_gradients_program_gathers_and_scatters_inside_its_loops_alone():
  """Outside its two loops over rounds the program of the layer's
  gradient moves no scalar by index: no `gather`, no `scatter`, no
  `scatter-add` (each a serial loop on the TPU, 7-9 ns an element:
  ISSUE 44). What it sorts is the assignments by expert, once, with
  their positions and weights riding, and the weights' cotangent back
  by the order; the counts come from a compare and a sum."""
  n = ROUNDS["tokens"]
  x = jax.random.normal(jax.random.PRNGKey(11), (n, WIDTH))
  experts, weights = _routing("pushed")

  def program(x, weights, *matrices):
    return jnp.sum(_held(x, experts, weights, *matrices)[0] ** 2)

  jaxpr = jax.make_jaxpr(jax.grad(program, argnums=(0, 1, 2, 3, 4)))(
      x, weights, *_expert_weights()).jaxpr
  names = [eqn.primitive.name for eqn in _equations(jaxpr, False)]
  assert names.count("while") == 2
  assert not [name for name in names
              if name.startswith(("gather", "scatter"))]
  # The guard sees a gather where one stands: each loop's body has the
  # round's own.
  inside = [eqn.primitive.name for eqn in _equations(jaxpr)]
  assert inside.count("gather") >= 2 and "scatter-add" in inside
  sorts = [(len(eqn.invars), eqn.params["num_keys"],
            eqn.params["is_stable"])
           for eqn in _equations(jaxpr) if eqn.primitive.name == "sort"]
  # Forward: keys, positions and weights, ties in the order they came.
  # Backward: the order (a permutation: no ties) and the cotangent.
  assert sorted(sorts) == [(2, 1, False), (3, 1, True)]


def _old_sort_by_expert(local, flat):
  """`moe._sort_by_expert` as it stood before ISSUE 44: a stable
  argsort and a gather by its order, whose gradient JAX scatter-adds."""
  order = jnp.argsort(local, stable=True)
  return order, flat[order]


def _old_held_counts(local, held):
  """`moe._held_counts` as it stood before ISSUE 44."""
  return jnp.bincount(local, length=held + 1)[:held].astype(jnp.int32)


def _strained(case):
  """(experts [N, k], first, held, num_experts) of routings that strain
  a sort."""
  key = jax.random.PRNGKey(21)
  if case == "every_expert_held":
    return jax.lax.top_k(jax.random.normal(key, (300, 8)), 3)[1], 0, 8, 8
  if case == "none_of_a_tokens_experts_held":
    # Every second token chooses among experts 8..15 alone.
    chosen = jax.lax.top_k(jax.random.normal(key, (300, 8)), 2)[1]
    return chosen + 8 * (jnp.arange(300) % 2)[:, None], 2, 4, 16
  if case == "nothing_held":
    return jax.lax.top_k(jax.random.normal(key, (64, 4)), 2)[1], 8, 4, 16
  if case == "all_on_one_expert":
    return jnp.full((257, 3), 5, jnp.int32), 4, 2, 16
  if case == "long_runs_of_equal_keys":
    # Runs of 97 assignments an expert, in an order that is not sorted.
    return ((jnp.arange(2000 * 2) // 97 * 5) % 8).reshape(2000, 2), 2, 4, 8
  if case == "rows_do_not_divide":
    return _routing("pushed")[0], ROUNDS["first"], ROUNDS["held"], 16
  if case == "held_1":
    return jax.lax.top_k(jax.random.normal(key, (300, 8)), 3)[1], 6, 1, 8
  raise ValueError(case)


@pytest.mark.parametrize("case", [
    "every_expert_held", "none_of_a_tokens_experts_held", "nothing_held",
    "all_on_one_expert", "long_runs_of_equal_keys", "rows_do_not_divide",
    "held_1"])
def test_the_bookkeeping_equals_its_old_definitions(monkeypatch, case):
  """The order, the weights in that order, the counts and the weights'
  gradient: exactly what `jnp.argsort(stable=True)`, the gather by it
  with JAX's own gradient, and `jnp.bincount` give; and the layer
  around them gives the same result, counters and gradients to the
  last bit as with those put back."""
  experts, first, held, num_experts = _strained(case)
  experts = experts.astype(jnp.int32)
  n, k = experts.shape
  keys = jax.random.split(jax.random.PRNGKey(22), 4)
  weights = jax.random.uniform(keys[0], (n, k), minval=0.1)
  probe = jax.random.normal(keys[1], (n * k,))
  local = experts.reshape(-1) - first
  local = jnp.where((local >= 0) & (local < held), local, held)

  def riding(sort_by_expert):
    def weighed(weights):
      order, sorted_weights = sort_by_expert(local, weights.reshape(-1))
      return jnp.sum(sorted_weights * probe), (order, sorted_weights)
    return jax.grad(weighed, has_aux=True)(weights)

  got_grad, (got_order, got_weights) = riding(moe._sort_by_expert)
  want_grad, (want_order, want_weights) = riding(_old_sort_by_expert)
  np.testing.assert_array_equal(got_order, want_order)
  np.testing.assert_array_equal(got_weights, want_weights)
  np.testing.assert_array_equal(got_grad, want_grad)
  got_counts = moe._held_counts(local, held)
  np.testing.assert_array_equal(got_counts, _old_held_counts(local, held))
  assert got_counts.dtype == jnp.int32 and got_counts.shape == (held,)
  if case == "nothing_held":
    assert int(jnp.sum(got_counts)) == 0
  if case == "long_runs_of_equal_keys":  # ties in the order they came
    assert bool(jnp.all((jnp.diff(got_order) > 0)
                        | (jnp.diff(local[got_order]) > 0)))

  # The layer around the bookkeeping.
  x = jax.random.normal(keys[2], (n, WIDTH))
  gate, up, down = (jax.random.normal(key, shape) * 0.4 for key, shape in
                    zip(jax.random.split(keys[3], 3),
                        ((held, WIDTH, F), (held, WIDTH, F),
                         (held, F, WIDTH))))

  def layer(x, weights, *matrices):
    out, counters = moe.held_experts_ffn(
        x, experts, weights, *matrices, first_expert=first,
        num_experts=num_experts, dtype=jnp.float32)
    return jnp.sum(out ** 2), (out, counters)

  def run():
    return jax.grad(layer, argnums=(0, 1, 2, 3, 4), has_aux=True)(
        x, weights, gate, up, down)

  got = run()
  monkeypatch.setattr(moe, "_sort_by_expert", _old_sort_by_expert)
  monkeypatch.setattr(moe, "_held_counts", _old_held_counts)
  want = run()
  for a, b in zip(jax.tree_util.tree_leaves(got),
                  jax.tree_util.tree_leaves(want)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  if case == "rows_do_not_divide":
    assert (n * k) % moe.round_rows(n * k, held, num_experts)
  if case != "nothing_held":
    assert float(jnp.max(jnp.abs(got[0][1]))) > 0.0  # weights' gradient


def test_one_round_running_is_round_compute_called_once():
  """Under uniform routing the layer is its first round, to the last
  bit: nothing is added to it and nothing is summed in another
  order."""
  n, k, held = ROUNDS["tokens"], ROUNDS["k"], ROUNDS["held"]
  x = jax.random.normal(jax.random.PRNGKey(11), (n, WIDTH))
  experts, weights = _routing("uniform")
  matrices = _expert_weights()
  got, counters = _held(x, experts, weights, *matrices)
  assert counters["rounds_run"] == 1.0
  local = np.asarray(experts).reshape(-1) - ROUNDS["first"]
  local = np.where((local >= 0) & (local < held), local, held)
  order = np.argsort(local, kind="stable")
  counts = np.bincount(local, minlength=held + 1)[:held].astype(np.int32)
  rows = moe.round_rows(n * k, held, ROUNDS["experts"])
  once = moe._round_compute(
      jnp.zeros((n, WIDTH), jnp.float32), 0, int(counts.sum()),
      jnp.asarray(order // k, jnp.int32), jnp.cumsum(counts), counts, x,
      weights.reshape(-1)[order], *matrices, rows, jnp.float32)
  np.testing.assert_array_equal(np.asarray(got), np.asarray(once))
  assert float(jnp.max(jnp.abs(got))) > 0.1


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
