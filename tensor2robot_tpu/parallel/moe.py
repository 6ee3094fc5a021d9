"""Mixture-of-experts with expert parallelism over the `expert` axis.

The reference has no MoE and no expert parallelism (SURVEY.md §3
parallelism inventory marks EP "n/a"); the `expert` mesh axis exists so
the transformer trunk scales capacity without scaling per-token FLOPs —
the same reason the `seq` axis carries ring attention. The design is
the standard static-shape GShard/Switch formulation, built TPU-first:

  * Routing is top-k softmax gating with a STATIC per-group capacity
    C = ceil(k · tokens/E · capacity_factor): dispatch and combine are
    dense one-hot einsums over [tokens, E, C], so XLA sees fixed
    shapes — no sorts with dynamic output sizes, no ragged buffers.
    Tokens past an expert's capacity are dropped (their combine weight
    is zero and the residual stream carries them through unchanged —
    the Switch-transformer semantics).
  * Expert parallelism is a `shard_map` over the `expert` axis: each
    device routes ITS OWN tokens (router weights replicated, router
    math is tiny), then one `lax.all_to_all` carries dispatched tokens
    to the devices holding their experts and a second carries expert
    outputs back. Both are differentiable (transpose of all-to-all is
    all-to-all), so training works through the sharded path.
  * Capacity is per token-group (= per device), so device count only
    changes WHICH tokens overflow a full expert, never the math of
    routed tokens: with capacity_factor high enough that nothing
    drops, the sharded result equals the single-device reference
    exactly (tested).

`moe_mlp` is the functional core (used under shard_map and as the
single-device reference); `MoEMLP` is the flax module that owns the
params and sows the load-balance auxiliary loss.

**The dropless layer over the experts held** (`SparseMoE`,
`held_experts_ffn`; docs/SEQUENCE.md) is a second formulation, for
models whose routing a capacity tensor cannot hold (hundreds of
experts, ten a token: `[N, E, C]` at 32,768 tokens and 512 experts is
out of the question) and whose semantics are not Switch's (no token
is dropped). The router scores every expert and keeps the top k of
all of them; the layer is told which contiguous range of experts it
holds, sorts the assignments that fall on those by expert, and runs
one grouped matrix product (`lax.ragged_dot`) per projection over the
sorted rows: no dense dispatch tensor, no capacity. It computes the
part of the layer's sum that its own experts give; on an
expert-parallel pod the parts of all the shares add up to the layer
(tests/test_sparse_moe.py), and this module adds nothing that stands
in for the exchange. The capacity formulation above stays for its
callers: `CausalTransformer(moe_experts=...)`, the vrgripper MoE gin
file, the `expert`-axis shard_map path and their tests.

Composition note: EP groups tokens over the data (+expert) axes. In a
mesh that ALSO has a non-trivial `seq` axis (ring attention), the MoE
layer still computes correctly, but GSPMD must reshard activations
from sequence-sharded to token-group-sharded and back around every
MoE layer — an extra all-to-all-ish cost the collective audit does
not pin. Long-context MoE layouts should put MoE cadence low
(`moe_every` high) or keep `expert` and `seq` on separate meshes.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from tensor2robot_tpu.parallel.mesh import EXPERT_AXIS, shard_map_compat

_EPS = 1e-9


def expert_capacity(num_tokens: int, num_experts: int, k: int,
                    capacity_factor: float) -> int:
  """Static per-group expert capacity (≥1 so every expert has a slot)."""
  return max(1, int(np.ceil(
      k * num_tokens / num_experts * capacity_factor)))


def top_k_routing(
    logits: jax.Array,
    capacity: int,
    k: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
  """Builds dense dispatch/combine tensors from router logits.

  Args:
    logits: [N, E] router logits for one token group (f32).
    capacity: static slots per expert for this group.
    k: experts per token (1 = Switch, 2 = GShard-style).

  Returns:
    dispatch: [N, E, C] 0/1 — token n occupies slot c of expert e.
    combine:  [N, E, C] f32 — gate weights (renormalized over the
      token's KEPT choices) at the occupied slots.
    aux: scalar load-balance loss (Switch eq. 4: E · Σ_e f_e·p_e with
      f_e the fraction of tokens whose FIRST choice is e and p_e the
      mean router probability of e) — 1.0 at perfect balance.
  """
  n, num_experts = logits.shape
  gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

  remaining = gates
  counts = jnp.zeros((num_experts,), jnp.float32)
  dispatch = jnp.zeros((n, num_experts, capacity), jnp.float32)
  gate_sum = jnp.zeros((n,), jnp.float32)
  combine = jnp.zeros((n, num_experts, capacity), jnp.float32)
  aux = 0.0
  for choice in range(k):
    expert = jnp.argmax(remaining, axis=-1)                  # [N]
    onehot = jax.nn.one_hot(expert, num_experts)             # [N, E]
    if choice == 0:
      aux = num_experts * jnp.sum(
          jnp.mean(onehot, axis=0) * jnp.mean(gates, axis=0))
    # Slot index within each expert: tokens claim slots in order,
    # offset by the slots earlier choices already filled.
    position = (jnp.cumsum(onehot, axis=0) - onehot
                + counts[None, :])                           # [N, E]
    slot = jnp.sum(position * onehot, axis=-1).astype(jnp.int32)
    kept = (slot < capacity).astype(jnp.float32)
    gate = jnp.sum(gates * onehot, axis=-1)                  # [N]
    hot = (kept[:, None, None] * onehot[:, :, None]
           * jax.nn.one_hot(slot, capacity)[:, None, :])     # [N, E, C]
    dispatch = dispatch + hot
    combine = combine + gate[:, None, None] * hot
    gate_sum = gate_sum + gate * kept
    counts = counts + jnp.sum(onehot * kept[:, None], axis=0)
    remaining = remaining * (1.0 - onehot)
  combine = combine / jnp.maximum(gate_sum, _EPS)[:, None, None]
  return dispatch, combine, aux


def moe_mlp(
    x: jax.Array,
    router: jax.Array,
    w_in: jax.Array,
    b_in: jax.Array,
    w_out: jax.Array,
    b_out: jax.Array,
    *,
    k: int,
    capacity_factor: float,
) -> Tuple[jax.Array, jax.Array]:
  """Dense-dispatch MoE over one token group (the per-device body).

  x [N, M]; router [M, E]; w_in [E, M, H]; b_in [E, H];
  w_out [E, H, M]; b_out [E, M] → ([N, M], aux scalar).
  """
  n, _ = x.shape
  num_experts = router.shape[-1]
  capacity = expert_capacity(n, num_experts, k, capacity_factor)
  logits = x.astype(jnp.float32) @ router
  dispatch, combine, aux = top_k_routing(logits, capacity, k)
  dtype = x.dtype
  xd = jnp.einsum("nm,nec->ecm", x, dispatch.astype(dtype))
  h = jax.nn.gelu(
      jnp.einsum("ecm,emh->ech", xd, w_in) + b_in[:, None, :])
  y = jnp.einsum("ech,ehm->ecm", h, w_out) + b_out[:, None, :]
  out = jnp.einsum("ecm,nec->nm", y, combine.astype(dtype))
  return out.astype(dtype), aux


def _moe_local(x, router, w_in, b_in, w_out, b_out, *, k,
               capacity_factor, axis_name, num_experts, mean_axes):
  """Per-device body under shard_map: route local tokens, exchange.

  x local [N_local, M]; expert params local [E/P, ...]. The two
  all-to-alls are the whole EP communication story: dispatched tokens
  out to their experts' devices, expert outputs back home. `mean_axes`
  are every mesh axis the token dim is sharded over (data + expert),
  so the returned aux loss is the global mean and legitimately
  replicated.
  """
  n = x.shape[0]
  capacity = expert_capacity(n, num_experts, k, capacity_factor)
  logits = x.astype(jnp.float32) @ router
  dispatch, combine, aux = top_k_routing(logits, capacity, k)
  dtype = x.dtype
  # [E, C, M]: this device's tokens, laid out per destination expert.
  xd = jnp.einsum("nm,nec->ecm", x, dispatch.astype(dtype))
  # Exchange: split the expert dim across devices, concatenate the
  # incoming groups on the capacity dim → [E/P, C·P, M]: all devices'
  # tokens for MY experts.
  xd = jax.lax.all_to_all(xd, axis_name, split_axis=0, concat_axis=1,
                          tiled=True)
  h = jax.nn.gelu(
      jnp.einsum("ecm,emh->ech", xd, w_in) + b_in[:, None, :])
  y = jnp.einsum("ech,ehm->ecm", h, w_out) + b_out[:, None, :]
  # Inverse exchange: groups back to their home devices → [E, C, M].
  y = jax.lax.all_to_all(y, axis_name, split_axis=1, concat_axis=0,
                         tiled=True)
  out = jnp.einsum("ecm,nec->nm", y, combine.astype(dtype))
  return out.astype(dtype), jax.lax.pmean(aux, mean_axes)


class MoEMLP(nn.Module):
  """Switch/GShard-style MoE feed-forward (drop-in for a dense MLP).

  With `mesh=None` (or no non-trivial `expert` axis) runs the dense
  single-device formulation; with an `expert` axis, expert weights
  live sharded over it and tokens all-to-all to their experts. The
  load-balance auxiliary loss is sown into the "aux_loss" collection
  under "moe_aux" — training models add
  `aux_weight · sum(collected)` to their loss (see
  `collect_aux_losses`).
  """

  num_experts: int
  hidden_dim: int
  k: int = 2
  capacity_factor: float = 2.0
  mesh: Optional[Mesh] = None
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x: jax.Array) -> jax.Array:
    b, t, model_dim = x.shape
    e, h = self.num_experts, self.hidden_dim
    init = nn.initializers.lecun_normal()
    router = self.param("router", init, (model_dim, e), jnp.float32)
    # The "moe_expert_" prefix is the contract `expert_sharding` keys
    # on: it is OWNED by this module (nothing else may name params
    # with it), so expert weights shard correctly no matter what the
    # parent trunk names its MoEMLP instance.
    w_in = self.param("moe_expert_w_in", init, (e, model_dim, h),
                      jnp.float32).astype(self.dtype)
    b_in = self.param("moe_expert_b_in", nn.initializers.zeros,
                      (e, h), jnp.float32).astype(self.dtype)
    w_out = self.param("moe_expert_w_out", init, (e, h, model_dim),
                       jnp.float32).astype(self.dtype)
    b_out = self.param("moe_expert_b_out", nn.initializers.zeros,
                       (e, model_dim), jnp.float32).astype(self.dtype)

    x = x.astype(self.dtype)
    tokens = x.reshape(b * t, model_dim)
    mesh = self.mesh
    if (mesh is None or EXPERT_AXIS not in mesh.axis_names
        or mesh.shape[EXPERT_AXIS] == 1):
      out, aux = moe_mlp(tokens, router, w_in, b_in, w_out, b_out,
                         k=self.k, capacity_factor=self.capacity_factor)
    else:
      from jax.sharding import PartitionSpec as P

      from tensor2robot_tpu.parallel.mesh import DATA_AXIS

      part = mesh.shape[EXPERT_AXIS]
      if e % part:
        raise ValueError(
            f"num_experts {e} must be a multiple of the "
            f"{EXPERT_AXIS!r} axis size {part}.")
      # Tokens group per device: the batch shards over data AND
      # expert axes jointly (standard dp×ep layout — the expert axis
      # doubles as extra data parallelism outside MoE blocks).
      token_axes = tuple(a for a in (DATA_AXIS, EXPERT_AXIS)
                         if a in mesh.axis_names)
      groups = int(np.prod([mesh.shape[a] for a in token_axes]))
      if (b * t) % groups:
        raise ValueError(
            f"token count {b}×{t} must be a multiple of the {groups} "
            f"token groups of mesh axes {token_axes}.")
      body = functools.partial(
          _moe_local, k=self.k, capacity_factor=self.capacity_factor,
          axis_name=EXPERT_AXIS, num_experts=e,
          mean_axes=token_axes)
      tok = P(token_axes)
      ep = P(EXPERT_AXIS)
      out, aux = shard_map_compat(
          body, mesh,
          in_specs=(tok, P(), ep, ep, ep, ep),
          out_specs=(tok, P()),
      )(tokens, router, w_in, b_in, w_out, b_out)
    self.sow("aux_loss", "moe_aux", aux)
    return out.reshape(b, t, model_dim)


def collect_aux_losses(variables: Any) -> jax.Array:
  """Sums every sown aux loss (0.0 when the model has none)."""
  total = jnp.asarray(0.0, jnp.float32)
  for leaf in jax.tree_util.tree_leaves(variables.get("aux_loss", {})):
    total = total + jnp.sum(jnp.asarray(leaf, jnp.float32))
  return total


def router_scores(x: jax.Array, router: jax.Array,
                  scoring: str = "softmax") -> jax.Array:
  """x [N, M], router [M, E] -> scores [N, E] over ALL of the router's
  experts in float32: their `softmax`, or each one's own `sigmoid`."""
  logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGH)
  if scoring == "softmax":
    return jax.nn.softmax(logits, axis=-1)
  if scoring == "sigmoid":
    return jax.nn.sigmoid(logits)
  raise ValueError(f"Unknown scoring: {scoring!r}")


def choose_top_k(scores: jax.Array, k: int, normalise: bool = True,
                 bias: Optional[jax.Array] = None, scale: float = 1.0):
  """The k largest of scores [N, E], their weights divided by their
  sum where `normalise`, times `scale`. With `bias` [E] the k are
  chosen by score + bias and weighted by the score alone (the
  selection bias of auxiliary-loss-free balancing: it steers load and
  no gradient reaches it). -> (experts [N, k] int32, weights [N, k])."""
  if bias is None:
    weights, experts = jax.lax.top_k(scores, k)
  else:
    _, experts = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
  if normalise:
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
  return experts, (weights * scale if scale != 1.0 else weights)


def route_top_k(x: jax.Array, router: jax.Array, k: int,
                normalise: bool = True, *, scoring: str = "softmax",
                bias: Optional[jax.Array] = None, scale: float = 1.0):
  """`choose_top_k` of `router_scores`.
  x [N, M], router [M, E] -> (experts [N, k] int32, weights [N, k])."""
  return choose_top_k(router_scores(x, router, scoring), k, normalise,
                      bias, scale)


def round_rows(assignments: int, held: int, num_experts: int) -> int:
  """Rows of one round of `held_experts_ffn`: twice the share of the
  assignments that uniform routing sends to the experts held, in
  whole tiles of 512, at most all of them."""
  share = -(-2 * assignments * held // num_experts)
  return min(assignments, -(-share // 512) * 512)


def _group_sizes(start, ends, counts, rows):
  """The rows of each held expert's group within the round
  `start .. start + rows - 1` of the sorted assignments: what the
  round's grouped matrix products are told to work off. [H] int32."""
  return (jnp.clip(ends, start, start + rows)
          - jnp.clip(ends - counts, start, start + rows)
          ).astype(jnp.int32)


def _round_index(start, here, token, weight, ends, counts, rows):
  """What the round `start .. start + rows - 1` of the sorted
  assignments works on: its groups' sizes [H], its rows' tokens and
  weights [rows] and which of its rows hold a held assignment
  [rows, 1]."""
  sizes = _group_sizes(start, ends, counts, rows)
  tok = jax.lax.dynamic_slice(token, (start,), (rows,))
  wt = jax.lax.dynamic_slice(weight, (start,), (rows,))
  # Past the last held assignment a row belongs to no group.
  valid = (start + jnp.arange(rows) < here)[:, None]
  return sizes, tok, wt, valid


def _gated_units(xs, wt, w_gate, w_up, w_down, sizes, valid, dtype):
  """A round's gathered rows through their experts' gated units, times
  their weights. [rows, M] float32."""

  def grouped(lhs, rhs):
    # A row of no group is nobody's to write: the TPU's grouped
    # product leaves it as the memory was, in the result and in the
    # gradient that its transpose hands back for `lhs` (PR 34: a first
    # chip run's gradient norm read 1.5e7 times the reference's, from
    # such rows scatter-added onto their tokens). Zeros go in and
    # zeros come out, so the same holds for every cotangent.
    out = jax.lax.ragged_dot(
        jnp.where(valid, lhs, 0), rhs.astype(dtype), sizes,
        preferred_element_type=jnp.float32)
    return jnp.where(valid, out, 0.0)

  hidden = (jax.nn.silu(grouped(xs, w_gate))
            * grouped(xs, w_up)).astype(dtype)
  return grouped(hidden, w_down) * wt[:, None]


def _round_compute(out, start, here, token, ends, counts, x, weight,
                   w_gate, w_up, w_down, rows, dtype):
  """One round of `held_experts_ffn`: the sorted assignments
  `start .. start + rows - 1`, gathered, through the grouped gated
  units, scatter-added onto their tokens' rows of `out` [N, M]
  float32."""
  sizes, tok, wt, valid = _round_index(start, here, token, weight, ends,
                                       counts, rows)
  ys = _gated_units(x[tok], wt, w_gate, w_up, w_down, sizes, valid, dtype)
  return out.at[tok].add(ys)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def _rounds(here, token, ends, counts, x, weight, w_gate, w_up, w_down,
            rows, dtype):
  """Every round that holds a held assignment, one after the other:
  a loop of as many iterations as that, its carry the layer's result,
  which each round adds to where its rows land. -> (the result [N, M]
  float32, the rows that the rounds' grouped products were given, the
  rounds that ran).

  Its own gradient rule (`_rounds_bwd`): a loop's length that the
  routing decides cannot be differentiated by JAX, and the rule keeps
  nothing of a round: the residuals are the inputs, whatever the number
  of rounds."""

  def body(carry):
    done, out, given = carry
    start = done * rows
    out = _round_compute(out, start, here, token, ends, counts, x,
                         weight, w_gate, w_up, w_down, rows, dtype)
    # What the grouped products leave out of a group they do not write.
    given += jnp.sum(_group_sizes(start, ends, counts, rows))
    return done + 1, out, given

  done, out, given = jax.lax.while_loop(
      lambda carry: carry[0] * rows < here, body,
      (jnp.zeros((), jnp.int32), jnp.zeros(x.shape, jnp.float32),
       jnp.zeros((), jnp.int32)))
  return out, given, done


def _rounds_fwd(here, token, ends, counts, x, weight, w_gate, w_up,
                w_down, rows, dtype):
  args = (here, token, ends, counts, x, weight, w_gate, w_up, w_down)
  return _rounds(*args, rows, dtype), args


def _rounds_bwd(rows, dtype, args, cotangents):
  """The same rounds again, the five cotangents as the loop's carry: a
  round computes its forward pass anew from the inputs, and its
  contribution is added where it lands: a token's rows of `dx`, the
  round's own slice of `dweight`, the whole of each expert matrix's."""
  here, token, ends, counts, x, weight, w_gate, w_up, w_down = args
  d_out = cotangents[0]  # the two counts have none

  def body(carry):
    done, dx, dweight, d_experts = carry
    start = done * rows
    sizes, tok, wt, valid = _round_index(start, here, token, weight, ends,
                                         counts, rows)
    _, vjp = jax.vjp(
        lambda xs, wt, *experts: _gated_units(xs, wt, *experts, sizes,
                                              valid, dtype),
        x[tok], wt, w_gate, w_up, w_down)
    dxs, dwt, *d_round = vjp(d_out[tok])
    return (done + 1, dx.at[tok].add(dxs),
            jax.lax.dynamic_update_slice(dweight, dwt, (start,)),
            tuple(total + part for total, part in zip(d_experts,
                                                      d_round)))

  _, dx, dweight, d_experts = jax.lax.while_loop(
      lambda carry: carry[0] * rows < here, body,
      (jnp.zeros((), jnp.int32), jnp.zeros_like(x),
       jnp.zeros_like(weight),
       tuple(jnp.zeros_like(w) for w in (w_gate, w_up, w_down))))
  return (None,) * 4 + (dx, dweight) + d_experts


_rounds.defvjp(_rounds_fwd, _rounds_bwd)


@jax.custom_vjp
def _sort_by_expert(local, flat):
  """The assignments' stable order by `local` [A] (the held expert's
  number; `held` where the expert is not held here) and their weights
  `flat` [A] in that order, out of ONE sort: the positions and the
  weights ride the keys. -> (order [A] int32, `flat` in that order).

  Its own gradient rule: JAX's rule for a sort of several operands
  gathers the tangents by the order, and its transpose scatter-adds
  them; `order` is a permutation, so a sort of the cotangent by
  `order` puts each value where the scatter-add would have (nothing is
  summed). A gather or a scatter of scalars runs as a serial loop on
  the TPU, 7-9 ns an element, where a sort of the same 1.3 MB takes a
  third of a millisecond (PERF.md section 6, PR 44)."""
  _, order, riding = jax.lax.sort(
      (local, jax.lax.iota(jnp.int32, local.shape[0]), flat),
      num_keys=1, is_stable=True)
  return order, riding


def _sort_by_expert_fwd(local, flat):
  order, riding = _sort_by_expert(local, flat)
  return (order, riding), order


def _sort_by_expert_bwd(order, cotangents):
  # A permutation has no ties: a stable sort would carry a third
  # operand on the TPU to keep apart what cannot meet.
  _, d_flat = jax.lax.sort((order, cotangents[1]), num_keys=1,
                           is_stable=False)
  return None, d_flat  # the keys have none


_sort_by_expert.defvjp(_sort_by_expert_fwd, _sort_by_expert_bwd)


def _held_counts(local, held):
  """The assignments on each held expert, [held] int32: `local` [A]
  compared with every held expert's number and summed, one pass over
  the keys (counting into bins is a scatter-add of A ones)."""
  return jnp.sum(local[None, :] == jnp.arange(held)[:, None], axis=1,
                 dtype=jnp.int32)


def held_experts_ffn(x, experts, weights, w_gate, w_up, w_down, *,
                     first_expert: int, num_experts: int,
                     dtype: Any = jnp.bfloat16):
  """sum over a token's chosen experts THAT ARE HELD HERE of weight *
  down_e(silu(gate_e x) * up_e x). x [N, M]; experts, weights [N, k]
  from `route_top_k`; w_gate, w_up [H, M, F], w_down [H, F, M]: the H
  experts `first_expert .. first_expert + H - 1` of `num_experts`.
  Returns ([N, M] float32, counters).

  Dropless with static shapes: the N * k assignments are sorted by
  expert (those not held here sort last; their positions and weights
  ride the one sort, `_sort_by_expert`), and the sorted rows are
  worked off in rounds of `round_rows` rows, each one gather, three
  grouped matrix products and one scatter-add. Only the rounds that
  hold a held assignment run (`_rounds`: one loop forward, one
  backward), so uniform routing pays for one round and a router that
  sends everything here for all of them.
  """
  n, k = experts.shape
  held = w_gate.shape[0]
  total = n * k
  rows = round_rows(total, held, num_experts)
  local = experts.reshape(-1) - first_expert
  local = jnp.where((local >= 0) & (local < held), local, held)
  order, weight = _sort_by_expert(local, weights.reshape(-1))
  # Whole rounds: the last one's rows past the assignments are no
  # group's (nothing where `rows` divides the assignments).
  spare = -total % rows
  token = jnp.pad(order // k, (0, spare))
  weight = jnp.pad(weight, (0, spare))
  counts = _held_counts(local, held)
  ends = jnp.cumsum(counts)
  here = ends[-1]
  out, given, rounds = _rounds(here, token, ends, counts, x.astype(dtype),
                               weight, w_gate, w_up, w_down, rows, dtype)
  load = counts.astype(jnp.float32)
  counters = {
      # Of all assignments, those on experts held here.
      "assignments_here_share": here.astype(jnp.float32) / total,
      "expert_load_max_over_mean":
          jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9),
      # Held assignments that lay in no group of any round that ran.
      "dropped_assignments": (here - given).astype(jnp.float32),
      # The rounds that held a held assignment, and so ran.
      "rounds_run": rounds.astype(jnp.float32),
  }
  return out, counters


class SparseMoE(nn.Module):
  """A dropless top-k expert layer over the experts held here, beside
  one shared expert (sigmoid-gated where `shared_gated`); all experts
  are gated units down(silu(gate x) * up x) without biases. The router
  is `router_scores` under `choose_top_k`: `scoring`,
  `routed_scaling_factor` and, with `selection_bias`, a per-expert bias
  `router_bias` on the choice alone (a parameter that no gradient
  reaches; nothing updates it here).

  `num_experts` is the router's width (it routes over all of them),
  `experts_held` how many of them this module holds, from
  `first_expert` on: all of them on one chip that holds the layer, a
  chip's share on an expert-parallel pod. The routed part is the held
  experts' alone; the shared expert is computed in full (every chip
  computes it alike). The counters of the routing are sown into the
  `moe_counters` collection.
  """

  num_experts: int
  experts_held: int
  k: int
  expert_width: int
  shared_width: int = 0
  first_expert: int = 0
  normalise_top_k: bool = True
  scoring: str = "softmax"
  selection_bias: bool = False
  routed_scaling_factor: float = 1.0
  shared_gated: bool = True
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x: jax.Array) -> jax.Array:
    b, t, width = x.shape
    held, f = self.experts_held, self.expert_width
    init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                        batch_axis=(0,))
    router = self.param("router", nn.initializers.lecun_normal(),
                        (width, self.num_experts), jnp.float32)
    w_gate = self.param("experts_gate", init, (held, width, f),
                        jnp.float32)
    w_up = self.param("experts_up", init, (held, width, f), jnp.float32)
    w_down = self.param("experts_down", init, (held, f, width),
                        jnp.float32)
    bias = self.param("router_bias", nn.initializers.zeros,
                      (self.num_experts,), jnp.float32
                      ) if self.selection_bias else None
    tokens = x.reshape(b * t, width)
    with jax.named_scope("moe/route"):
      scores = router_scores(tokens, router, self.scoring)
      experts, weights = choose_top_k(
          scores, self.k, self.normalise_top_k, bias,
          self.routed_scaling_factor)
      if bias is not None:
        # Of the assignments, those the unbiased scores would not have
        # chosen.
        unbiased, _ = choose_top_k(scores, self.k, False)
        kept = jnp.any(experts[:, :, None] == unbiased[:, None, :], -1)
        self.sow("moe_counters", "bias_moved_choice_share",
                 1.0 - jnp.mean(kept.astype(jnp.float32)))
    with jax.named_scope("moe/experts"):
      out, counters = held_experts_ffn(
          tokens, experts, weights, w_gate, w_up, w_down,
          first_expert=self.first_expert,
          num_experts=self.num_experts, dtype=self.dtype)
    for name, value in counters.items():
      self.sow("moe_counters", name, value)
    if self.shared_width:
      with jax.named_scope("moe/shared"):
        y = tokens.astype(self.dtype)

        def dense(name, size):
          return nn.Dense(size, use_bias=False, dtype=self.dtype,
                          name=name)

        shared = dense("shared_down", width)(
            nn.silu(dense("shared_gate", self.shared_width)(y))
            * dense("shared_up", self.shared_width)(y))
        if self.shared_gated:
          gate = jax.nn.sigmoid(
              dense("shared_expert_gate", 1)(y).astype(jnp.float32))
          shared = gate * shared
        out = out + shared
    return out.reshape(b, t, width)
