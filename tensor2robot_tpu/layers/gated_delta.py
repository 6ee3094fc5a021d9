"""Gated DeltaNet: linear attention by the gated delta rule, in chunks.

A sequence mixer whose state is a matrix per head instead of a cache of
keys and values. Per head, with S in R^{Dk x Dv} from zero, a decay
g_t <= 0 and a write strength beta_t in (0, 1):

  S   <- exp(g_t) * S
  d_t  = beta_t * (v_t - S^T k_t)        # what the state gets wrong
  S   <- S + k_t d_t^T                   # the delta rule
  o_t  = S^T q_t

(Yang et al., "Gated Delta Networks", arXiv:2412.06464). The recurrence
over positions is exact and sequential; `gated_delta_rule` computes the
same numbers a chunk of positions at a time: inside a chunk everything
is matrix products (the WY form: the chunk's writes are solved for at
once through the inverse of a unit lower-triangular matrix), and a
walk over the chunks carries the float32 state. With chunk 64 a
sequence of 8,192 positions is a walk of 128 steps. On a TPU the walk
is a Pallas kernel pair (`ops/delta_rule_walk.py`), and a forward pass
that nothing differentiates through is one fused kernel, preparation,
walk and all (`ops/delta_rule_fused.py`).

`GatedDeltaNet` is the layer around it: one projection to q, k, v and
the output gate z, one to the two per-head scalars behind beta and g,
a causal depthwise convolution and SiLU on q, k, v, L2-normalised q and
k, and a gated RMS norm of the output under the out projection.

`KimiDeltaAttention` (arXiv:2510.26692) is the same rule with a finer
gate: S <- diag(exp(g_t)) S, a decay for every key channel, from a
low-rank projection of the input. `gated_delta_rule` takes that g with
a fourth axis; the chunks' operands are then built with reference
points (`_channel_operands`) and the walk scales the state's rows.

Precision: the big projections take `dtype` operands and accumulate in
float32; g, beta, the within-chunk decays, the triangular inverse and
the state between chunks are float32 whatever `dtype` is.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from tensor2robot_tpu.ops import delta_rule_fused, delta_rule_walk
from tensor2robot_tpu.telemetry import metrics as tmetrics

HIGH = jax.lax.Precision.HIGH

# What `GatedDeltaNet` names of a block for a checkpoint to keep, as
# `flash_attention.SAVED_RESIDUAL_NAMES` does for an attention block:
# the rule's output after the gated norm, as `out_proj` reads it,
# 2 B x tokens x value width in bfloat16. (The rule's float32 output
# before the norm is twice that, and rounding it there would change
# what the norm sees.)
SAVED_RESIDUAL_NAMES = ("gated_delta_normed_out",)


def causal_depthwise_conv(x: jax.Array, kernel: jax.Array) -> jax.Array:
  """y[t] = sum_j kernel[j] * x[t - (K - 1) + j] per channel, x before
  the sequence's start taken as 0. x [B, T, C], kernel [K, C]. K shifted
  multiply-adds: nothing for a convolution engine to win at K = 4."""
  taps, t = kernel.shape[0], x.shape[1]
  padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
  return sum(padded[:, j:j + t] * kernel[j] for j in range(taps))


@jax.custom_vjp
def _unit_lower_inverse(a: jax.Array) -> jax.Array:
  """(I + a)^-1 for strictly lower-triangular `a` [..., C, C], C a
  power of two: a is nilpotent, so the inverse is the finite product
  (I - a)(I + a^2)(I + a^4)...(I + a^(C/2)): 2 log2(C) - 2 matrix
  products where forward substitution takes C dependent steps. Its
  gradient is the inverse's own, -T^T dT T^T, from T alone: the
  product's ten intermediates are not kept for the backward pass."""
  c = a.shape[-1]
  inverse = jnp.eye(c, dtype=a.dtype) - a
  power = a
  for _ in range(int(np.log2(c)) - 1):
    power = jnp.matmul(power, power, precision=HIGH)
    inverse = inverse + jnp.matmul(inverse, power, precision=HIGH)
  return inverse


def _unit_lower_inverse_fwd(a):
  inverse = _unit_lower_inverse(a)
  return inverse, inverse


def _unit_lower_inverse_bwd(inverse, cotangent):
  t = jnp.swapaxes(inverse, -1, -2)
  return (-jnp.matmul(jnp.matmul(t, cotangent, precision=HIGH), t,
                      precision=HIGH),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd,
                           _unit_lower_inverse_bwd)


@jax.custom_vjp
def _unit_lower_inverse_by_halves(a: jax.Array) -> jax.Array:
  """(I + a)^-1 as `_unit_lower_inverse`, from the diagonal out: the
  inverse of [[P, 0], [R, Q]] is [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]], so
  the inverses of the diagonal blocks of 1, 2, 4, ... C/2 positions
  give those of twice the size in two batched products a level. Every
  intermediate is a block of the inverse itself. The product form's are
  the powers a^2 ... a^(C/2), whose entries reach C-choose-C/2 times the
  inverse's where a's entries share a sign, and float32 then returns
  rounding error: at C = 64 with every entry 0.42 (keys of one chunk
  at a cosine of 0.6 under beta 0.7) 26 where the inverse's largest
  entry is 1, at 0.9 some 2e9 (PERF.md section 6, PR 47: the
  Kimi-Linear job's keys align within fifteen steps; on the chip the
  product form then returned 38 for 1, and the loss was NaN). Here the
  same inputs come back to 3e-7, for 54 ms more a step of that cell
  (PERF.md section 7)."""
  c = a.shape[-1]
  lead = a.shape[:-2]
  inverse = jnp.ones(lead + (c, 1, 1), a.dtype)  # [..., C / s, s, s]
  size = 1
  while size < c:
    n = c // (2 * size)
    blocks = a.reshape(lead + (n, 2 * size, n, 2 * size))
    below = jnp.moveaxis(jnp.diagonal(blocks, axis1=-4, axis2=-2), -1,
                         -3)[..., size:, :size]  # R of each pair
    first, second = inverse[..., 0::2, :, :], inverse[..., 1::2, :, :]
    corner = -jnp.matmul(jnp.matmul(second, below, precision=HIGH), first,
                         precision=HIGH)
    inverse = jnp.concatenate(
        [jnp.concatenate([first, jnp.zeros_like(first)], -1),
         jnp.concatenate([corner, second], -1)], -2)
    size *= 2
  return inverse[..., 0, :, :]


def _unit_lower_inverse_by_halves_fwd(a):
  inverse = _unit_lower_inverse_by_halves(a)
  return inverse, inverse


_unit_lower_inverse_by_halves.defvjp(_unit_lower_inverse_by_halves_fwd,
                                     _unit_lower_inverse_bwd)


def scan_walk(writes, k_decayed, q_decayed, k_to_end, end_decay):
  """The walk over the chunks as a `lax.scan` carrying the float32
  state [B, H, Dk, Dv]: the plain path (a CPU, shapes that do not tile)
  and the oracle of `ops/delta_rule_walk.walk`, which has this
  signature. `writes` [N, B, H, C, Dv] float32; `k_decayed`,
  `q_decayed`, `k_to_end` [N, B, H, C, Dk] in the products' dtype;
  `end_decay` [N, B, H], or [N, B, H, Dk] where every key channel
  decays by its own (the state's rows are scaled). Returns (`new`,
  `carried`): what each chunk writes given the state it starts from,
  and what its queries read of that state, [N, B, H, C, Dv] float32."""
  dtype = k_decayed.dtype

  def mm(x, y, spec):
    return jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                      preferred_element_type=jnp.float32)

  def step(state, xs):
    writes_i, k_decayed_i, q_decayed_i, k_to_end_i, end_decay_i = xs
    new = writes_i - mm(k_decayed_i, state, "bhik,bhkv->bhiv")
    carried = mm(q_decayed_i, state, "bhik,bhkv->bhiv")
    # [B, H] -> [B, H, 1, 1]; [B, H, Dk] -> [B, H, Dk, 1]
    state = (state * end_decay_i.reshape(end_decay_i.shape[:2] + (-1, 1))
             + mm(k_to_end_i, new, "bhik,bhiv->bhkv"))
    return state, (new, carried)

  _, b, h, _, dv = writes.shape
  state0 = jnp.zeros((b, h, k_decayed.shape[-1], dv), jnp.float32)
  _, (new, carried) = jax.lax.scan(
      step, state0, (writes, k_decayed, q_decayed, k_to_end, end_decay))
  return new, carried


def _on_tpu() -> bool:
  return jax.devices()[0].platform == "tpu"


def _kernels_run(chunk, dk, dv, dtype, interpret) -> bool:
  """Whether the rule's Pallas programs run: on a TPU at shapes that
  tile, and wherever a test asks for the interpreter."""
  return interpret or (_on_tpu() and delta_rule_walk.tiles(
      chunk, dk, dv, dtype))


# Positions of a sub-chunk: where every key channel decays by its own,
# exp(G_i - G_j) is built in blocks of this many positions so that no
# exponential is of a positive number (`_channel_operands`).
_SUB_CHUNK = 16


def _scalar_operands(q, k, v, g, beta, mm):
  """A chunk's operands where a head decays by one scalar a position
  (g [N, B, H, C]): the decay comes out of k k^T as a [C, C] matrix."""
  dtype, chunk = q.dtype, q.shape[-2]
  g = jnp.cumsum(g, axis=-1)  # decay from the chunk's start, [N,B,H,C]
  rows = jnp.arange(chunk)
  lower = rows[:, None] >= rows[None, :]
  # exp(g_i - g_j) for j <= i; masked before the exponential, where
  # the other half would overflow.
  decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                            -jnp.inf))
  k_beta = k * beta[..., None]
  strict = rows[:, None] > rows[None, :]
  # The chunk's writes solve (I + A) W = beta (v - k S0 ...): A is the
  # strictly lower part of (beta k) k^T under the decays.
  a = jnp.where(strict, mm(k_beta, k, "...id,...jd->...ij") * decay, 0.0)
  solve = _unit_lower_inverse(a)
  writes = mm(solve, v * beta[..., None], "...ij,...jd->...id")
  # Operands of the walk's products only: kept in `dtype`.
  k_decayed = mm(solve, k_beta * jnp.exp(g)[..., None],
                 "...ij,...jd->...id").astype(dtype)
  within = jnp.where(lower, mm(q, k, "...id,...jd->...ij") * decay, 0.0)
  q_decayed = (q * jnp.exp(g)[..., None]).astype(dtype)
  # What each position's key still adds to the state at the chunk's end.
  k_to_end = (k * jnp.exp(g[..., -1:] - g)[..., None]).astype(dtype)
  end_decay = jnp.exp(g[..., -1])  # [N, B, H]
  return writes, k_decayed, q_decayed, k_to_end, end_decay, within


def _diagonal_decay(g):
  """exp(G_i - G_j) for j <= i inside a sub-chunk, 0 above the
  diagonal: g [..., S, Dk] -> [..., S, S, Dk]. Masked before the
  exponential. Never stands whole: its users reduce it in one fusion."""
  rows = jnp.arange(g.shape[-2])
  lower = (rows[:, None] >= rows[None, :])[..., None]
  return jnp.exp(jnp.where(
      lower, g[..., :, None, :] - g[..., None, :, :], -jnp.inf))


@jax.custom_vjp
def _diagonal_blocks(q, k_beta, k, g):
  """The sub-chunks' own blocks of the two [C, C] matrices where the
  decay is a channel's: sum_d x_id exp(G_id - G_jd) k_jd for x = beta k
  and x = q, j <= i. All [..., S, Dk] float32; returns two [..., S, S]
  (the caller masks the first's diagonal). The decay cannot leave the
  sum over d, so this is elementwise work over [S, S, Dk] and a
  reduction, with S = 16 a quarter of what [C, C, Dk] would be. Its
  gradient makes the decays again and keeps none: autodiff would keep
  [S, S, Dk] a sub-chunk, 2 GB a row of the Kimi-Linear cell."""
  decay = _diagonal_decay(g)
  pairs = k[..., None, :, :] * decay
  return (jnp.sum(k_beta[..., :, None, :] * pairs, -1),
          jnp.sum(q[..., :, None, :] * pairs, -1))


def _diagonal_blocks_fwd(q, k_beta, k, g):
  return _diagonal_blocks(q, k_beta, k, g), (q, k_beta, k, g)


def _diagonal_blocks_bwd(residuals, cotangents):
  q, k_beta, k, g = residuals
  d_a, d_within = (c[..., None] for c in cotangents)
  decay = _diagonal_decay(g)
  pairs = k[..., None, :, :] * decay
  d_k_beta = jnp.sum(d_a * pairs, -2)
  d_q = jnp.sum(d_within * pairs, -2)
  d_k = jnp.sum((d_a * k_beta[..., :, None, :]
                 + d_within * q[..., :, None, :]) * decay, -3)
  # d/dG_i of every term with row i less d/dG_j of every term with
  # column j: each term is its factors' product, so the sums are the
  # other three cotangents times their own operands.
  d_g = k_beta * d_k_beta + q * d_q - k * d_k
  return d_q, d_k_beta, d_k, d_g


_diagonal_blocks.defvjp(_diagonal_blocks_fwd, _diagonal_blocks_bwd)


def _channel_operands(q, k, v, g, beta, mm):
  """A chunk's operands where every key channel decays by its own
  (g [N, B, H, C, Dk]; Kimi Delta Attention, arXiv:2510.26692). With G
  the running sum of g inside the chunk,

    A_ij = beta_i sum_d k_id exp(G_id - G_jd) k_jd     (j < i)

  and the decay cannot leave the sum as a [C, C] matrix; factored as
  (k_i exp(G_i)) (k_j exp(-G_j)) the second overflows float32 at the
  decays a trained gate gives (-700 over 64 positions). So the [C, C]
  matrices are built from sub-chunks of `_SUB_CHUNK` positions: a block
  below the diagonal is a product of (x_i exp(G_i - G_r)) and
  (k_j exp(G_r - G_j)), r the first position of i's sub-chunk, so
  j < r <= i and both exponents are <= 0; a block on the diagonal is
  `_diagonal_blocks`. Every exponential here is of a number <= 0: what
  would overflow underflows to 0, which is what it is worth beside the
  terms of order 1."""
  dtype, chunk = q.dtype, q.shape[-2]
  sub = min(_SUB_CHUNK, chunk)  # both powers of two
  blocks = chunk // sub
  g = jnp.cumsum(g, axis=-2)  # [N, B, H, C, Dk]
  f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
  q32, k32 = f32(q), f32(k)
  k_beta = k32 * beta[..., None]

  def split(x):  # [..., C, D] -> [..., C / S, S, D]
    return x.reshape(x.shape[:-2] + (blocks, sub) + x.shape[-1:])

  g_sub = split(g)
  from_first = jnp.exp(g_sub - g_sub[..., :1, :])  # exp(G_i - G_r)
  a_rows, within_rows = (split(x) * from_first for x in (k_beta, q32))
  a_diag, within_diag = _diagonal_blocks(split(q32), split(k_beta),
                                         split(k32), g_sub)

  # The keys before sub-chunk s, decayed up to its first position.
  before = [k32[..., :s * sub, :]
            * jnp.exp(g_sub[..., s, :1, :] - g[..., :s * sub, :])
            for s in range(1, blocks)]

  def assemble(rows_operand, diagonal):
    """[..., C, C] from its blocks; zeros above the diagonal blocks."""
    out = []
    for s in range(blocks):
      row = [mm(rows_operand[..., s, :, :], before[s - 1],
                "...id,...jd->...ij")] if s else []
      row.append(diagonal[..., s, :, :])
      if s < blocks - 1:
        row.append(jnp.zeros(diagonal.shape[:-3]
                             + (sub, chunk - (s + 1) * sub), jnp.float32))
      out.append(jnp.concatenate(row, axis=-1))
    return jnp.concatenate(out, axis=-2)

  rows = jnp.arange(chunk)
  a = jnp.where(rows[:, None] > rows[None, :],
                assemble(a_rows, a_diag), 0.0)
  within = assemble(within_rows, within_diag)
  solve = _unit_lower_inverse_by_halves(a)
  writes = mm(solve, v * beta[..., None], "...ij,...jd->...id")
  from_start = jnp.exp(g)
  k_decayed = mm(solve, k_beta * from_start,
                 "...ij,...jd->...id").astype(dtype)
  q_decayed = (q32 * from_start).astype(dtype)
  k_to_end = (k32 * jnp.exp(g[..., -1:, :] - g)).astype(dtype)
  end_decay = jnp.exp(g[..., -1, :])  # [N, B, H, Dk]
  return writes, k_decayed, q_decayed, k_to_end, end_decay, within


def _prepared_rule(q, k, v, g, beta, chunk, dtype, interpret):
  """The rule over a whole number of chunks in three stages: every
  chunk's operands prepared in large XLA products (`_scalar_operands`,
  or `_channel_operands` where g has a key channel's axis), the walk
  over the chunks, `within @ new`. Differentiable by autodiff (the
  walk's kernels bring their own rule): the path of an evaluation that
  a backward pass follows, on a TPU too, and of every evaluation where
  the fused program cannot run."""
  b, t, h, dk = q.shape
  n = t // chunk
  channel = g.ndim == 4

  def chunks(x):  # [B, T, H, ...] -> [N, B, H, C, ...]
    x = x.reshape((b, n, chunk) + x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

  q, k, v = (chunks(x).astype(dtype) for x in (q, k, v))
  g, beta = (chunks(x.astype(jnp.float32)) for x in (g, beta))

  def mm(x, y, spec):
    return jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                      preferred_element_type=jnp.float32)

  operands = _channel_operands if channel else _scalar_operands
  *walked, within = operands(q, k, v, g, beta, mm)

  kernel = _kernels_run(chunk, dk, v.shape[-1], dtype, interpret)
  tmetrics.counter("gated_delta.walk.kernel_traces" if kernel
                   else "gated_delta.walk.scan_traces").inc()
  if channel:
    tmetrics.counter("gated_delta.channel_gate.kernel_traces" if kernel
                     else "gated_delta.channel_gate.scan_traces").inc()
  walk = (functools.partial(delta_rule_walk.walk, interpret=interpret)
          if kernel else scan_walk)
  new, carried = walk(*walked)
  out = carried + mm(within, new, "...ij,...jd->...id")
  # [N, B, H, C, Dv] -> [B, T, H, Dv]
  out = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 2, 3)
  return out.reshape(b, t, h, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused_rule(q, k, v, g, beta, chunk, dtype, interpret):
  """The rule where the fused program can run. Its value is the fused
  program's; a backward pass differentiates `_prepared_rule`."""
  return delta_rule_fused.forward(q, k, v, g, beta, chunk=chunk,
                                  dtype=dtype, interpret=interpret)


def _fused_rule_fwd(q, k, v, g, beta, chunk, dtype, interpret):
  return jax.vjp(functools.partial(
      _prepared_rule, chunk=chunk, dtype=dtype, interpret=interpret),
                 q, k, v, g, beta)


def _fused_rule_bwd(chunk, dtype, interpret, back, cotangent):
  del chunk, dtype, interpret
  return back(cotangent)


# `optimize_remat`, as on `delta_rule_walk._walk`: under the row's
# `jax.checkpoint` the forward pass, whose residuals nobody keeps, runs
# `_fused_rule` itself and not `_fused_rule_fwd`; the recomputation,
# whose residuals the backward pass reads, runs the rule above.
_fused_rule.defvjp(_fused_rule_fwd, _fused_rule_bwd, optimize_remat=True)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64,
                     dtype: Any = jnp.float32,
                     interpret: bool = False) -> jax.Array:
  """The gated delta rule over [B, T, H, D] in chunks of `chunk`
  positions (a power of two). q, k [B, T, H, Dk] (already normalised
  and scaled as the layer wants them), v [B, T, H, Dv], g and beta
  [B, T, H] float32; or g [B, T, H, Dk], a decay for every key channel
  (S <- diag(exp(g_t)) S: Kimi Delta Attention). Returns o
  [B, T, H, Dv] float32. Matrix products against q, k, v and the state
  take `dtype` operands and accumulate in float32.

  One algorithm, three programs. Where the platform is a TPU and the
  shapes tile, an evaluation that nothing differentiates through is
  `ops/delta_rule_fused`'s one kernel (a chunk's operands built in
  VMEM, the walk's step, `within @ new`; only `out` goes to HBM), and
  one that a backward pass follows is `_prepared_rule` with
  `ops/delta_rule_walk`'s kernel pair, whose intermediates are the
  backward pass's residuals. Everywhere else it is `_prepared_rule`
  with `scan_walk`. All of it is read off the input and off what JAX
  is doing to the call; nobody sets it. The registry's counters say
  what was traced (a compiled program runs what was traced):
  `gated_delta.forward.fused_traces` and `.prepared_traces` the calls
  whose undifferentiated evaluation is the fused program, and is not;
  `gated_delta.walk.kernel_traces` and `.scan_traces` the traces of
  `_prepared_rule` that took each walk. A decay per channel has no
  fused program: every evaluation is `_prepared_rule`, whose walk is
  the same kernel pair with the state's rows scaled
  (`gated_delta.channel_gate.kernel_traces` and `.scan_traces` count
  its traces beside the shared counters). `interpret` is the tests': the
  kernels in the Pallas interpreter, whatever the platform and the
  shapes."""
  t, dk = q.shape[1], q.shape[-1]
  pad = -t % chunk
  if pad:
    # beta 0 writes nothing, g 0 decays nothing, k 0 reads nothing.
    q, k, v, g, beta = (
        jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        for x in (q, k, v, g, beta))
  fused = g.ndim == 3 and _kernels_run(chunk, dk, v.shape[-1], dtype,
                                       interpret)
  tmetrics.counter("gated_delta.forward.fused_traces" if fused
                   else "gated_delta.forward.prepared_traces").inc()
  rule = _fused_rule if fused else _prepared_rule
  return rule(q, k, v, g, beta, chunk, dtype, interpret)[:, :t]


def l2_normalize(x: jax.Array, eps: float = 1e-6) -> jax.Array:
  return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                           + eps)


class GatedDeltaNet(nn.Module):
  """x [B, T, M] -> [B, T, M] through the gated delta rule.

  `num_k_heads` query/key heads of `head_k_dim` serve `num_v_heads`
  value heads of `head_v_dim` (each key head `num_v_heads //
  num_k_heads` of them). Parameters: `in_proj_qkvz` (columns q | k | v
  | z), `in_proj_ba` (b | a), `conv` [K, channels of q | k | v],
  `A_log`, `dt_bias` per value head, `norm` [head_v_dim], `out_proj`.
  """

  num_k_heads: int
  num_v_heads: int
  head_k_dim: int
  head_v_dim: int
  conv_kernel: int = 4
  chunk: int = 64
  eps: float = 1e-6
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
    b, t, width = x.shape
    hk, hv = self.num_k_heads, self.num_v_heads
    dk, dv = self.head_k_dim, self.head_v_dim
    key_dim, value_dim = hk * dk, hv * dv
    init = nn.initializers.lecun_normal()
    x = x.astype(self.dtype)
    qkvz = nn.Dense(2 * key_dim + 2 * value_dim, use_bias=False,
                    dtype=self.dtype, name="in_proj_qkvz")(x)
    ba = nn.Dense(2 * hv, use_bias=False, dtype=self.dtype,
                  name="in_proj_ba")(x).astype(jnp.float32)
    a_log = self.param("A_log", nn.initializers.zeros, (hv,),
                       jnp.float32)
    dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,),
                         jnp.float32)
    conv = self.param("conv", init,
                      (self.conv_kernel, 2 * key_dim + value_dim),
                      jnp.float32)
    norm = self.param("norm", nn.initializers.ones, (dv,), jnp.float32)

    with jax.named_scope("gated_delta/conv"):
      qkv, z = jnp.split(qkvz, [2 * key_dim + value_dim], axis=-1)
      qkv = nn.silu(causal_depthwise_conv(qkv, conv.astype(self.dtype)))
      q, k, v = jnp.split(qkv, [key_dim, 2 * key_dim], axis=-1)
    with jax.named_scope("gated_delta/scan"):
      beta = jax.nn.sigmoid(ba[..., :hv])
      g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
      q = l2_normalize(q.reshape(b, t, hk, dk).astype(jnp.float32),
                       self.eps) * dk ** -0.5
      k = l2_normalize(k.reshape(b, t, hk, dk).astype(jnp.float32),
                       self.eps)
      q, k = (jnp.repeat(y, hv // hk, axis=2) for y in (q, k))

      # A row of the batch at a time, each under `jax.checkpoint`: the
      # rule's intermediates (a dozen arrays of the size of v, the
      # state at every chunk) then stand for one row, forward and
      # backward; the heads and chunks of one row fill the chip. The
      # gated RMS norm is inside with the rule, so that a row hands on
      # what `out_proj` reads, [T, value_dim] in `dtype` (the cast is
      # the one `nn.Dense` makes), and the norm's backward finds the
      # rule's float32 output in the row's own recomputation.
      @jax.checkpoint
      def rule(row):
        *operands, z_row = row
        out = gated_delta_rule(*(y[None] for y in operands),
                               chunk=self.chunk, dtype=self.dtype)[0]
        # Gated RMS norm per head: plain weight, gate through SiLU.
        out = out * jax.lax.rsqrt(
            jnp.mean(jnp.square(out), -1, keepdims=True) + self.eps)
        out = norm * out * nn.silu(
            z_row.reshape(t, hv, dv).astype(jnp.float32))
        return out.reshape(t, value_dim).astype(self.dtype)

      out = jax.lax.map(
          rule, (q, k, v.reshape(b, t, hv, dv), g, beta, z))
      # Named outside the map: a block's checkpoint whose policy saves
      # the name (`transformer.apply_block`, `save_attention`) then
      # recomputes the block without the map, so the rule runs forward
      # twice for a backward pass (here and in `rule`'s own
      # recomputation) and not three times.
      out = checkpoint_name(out, SAVED_RESIDUAL_NAMES[0])
    return nn.Dense(width, use_bias=False, dtype=self.dtype,
                    name="out_proj")(out)


def _decay_rate_init(key, shape, dtype=jnp.float32):
  """`A_log` as the public `fla` layer of the name draws it: the
  logarithm of A uniform in [1, 16], a head."""
  return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _step_bias_init(key, shape, dtype=jnp.float32):
  """`dt_bias` likewise: the inverse softplus of a step log-uniform in
  [0.001, 0.1], a key channel, so that at a projection of 0 a channel
  decays by exp(-A dt), between exp(-0.001) and exp(-1.6) a position."""
  dt = jnp.exp(jax.random.uniform(key, shape, dtype, np.log(0.001),
                                  np.log(0.1)))
  return dt + jnp.log(-jnp.expm1(-dt))


class KimiDeltaAttention(nn.Module):
  """x [B, T, M] -> [B, T, M] through the delta rule with a decay per
  key channel (Kimi Delta Attention, arXiv:2510.26692).

  `num_heads` heads of `head_dim` for queries, keys and values alike:

    q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
    q, k L2-normalised a head, q times head_dim^-1/2
    g    = -exp(A_log) softplus(x W_fa W_fb + dt_bias)   [T, H, D]
    beta = sigmoid(x W_b)                                [T, H]
    o    = W_o (rmsnorm_head(rule(q, k, v, g, beta))
                * sigmoid(x W_ga W_gb + b_g))

  Parameters: `q_proj`, `k_proj`, `v_proj`, `q_conv`, `k_conv`,
  `v_conv` [K, H D], `f_a_proj` (M x D), `f_b_proj` (D x H D), `A_log`
  [H], `dt_bias` [H D] (both initialised as the public `fla` layer
  does), `b_proj` (M x H), `g_a_proj`, `g_b_proj` (with a bias), `norm`
  [D], `o_proj`. The scopes are `GatedDeltaNet`'s and
  hold what its hold, the convolutions and the rule with its gates and
  norm, the projections outside: it is the same rule, and the same two
  rows of a device trace.
  """

  num_heads: int
  head_dim: int
  conv_kernel: int = 4
  chunk: int = 64
  eps: float = 1e-6
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
    b, t, width = x.shape
    h, d = self.num_heads, self.head_dim
    rank = d  # of the two low-rank gates
    init = nn.initializers.lecun_normal()
    x = x.astype(self.dtype)

    def dense(name, size, use_bias=False):
      return nn.Dense(size, use_bias=use_bias, dtype=self.dtype,
                      name=name)

    a_log = self.param("A_log", _decay_rate_init, (h,), jnp.float32)
    dt_bias = self.param("dt_bias", _step_bias_init, (h * d,), jnp.float32)
    norm = self.param("norm", nn.initializers.ones, (d,), jnp.float32)
    taps = [self.param(f"{name}_conv", init, (self.conv_kernel, h * d),
                       jnp.float32).astype(self.dtype) for name in "qkv"]
    projected = [dense(f"{name}_proj", h * d)(x) for name in "qkv"]
    write = dense("b_proj", h)(x)
    decay = dense("f_b_proj", h * d)(dense("f_a_proj", rank)(x))
    gate = dense("g_b_proj", h * d, use_bias=True)(
        dense("g_a_proj", rank)(x))

    # A row of the batch and a group of its heads at a time (the heads
    # of one grid step of the walk's kernels), each under its own
    # checkpoint, the norm inside, as `GatedDeltaNet` takes a row (the
    # comment there). Here the map carries what the projections made,
    # in `dtype`, and a step makes everything after them inside: the
    # convolutions (causal inside a row) and the float32 operands, of
    # which g alone, [T, H, D], is as large as q, k and v together in
    # `dtype`. And it takes a group of heads, not a row: with a decay
    # per channel the rule's backward pass holds some forty float32
    # arrays of [T, heads, D] where the scalar's holds a dozen, 5 GB a
    # row of the Kimi-Linear cell, which then did not fit its chip
    # (PERF.md section 6, PR 47).
    heads = delta_rule_walk.head_block(h)
    groups = h // heads

    def grouped(y):  # [B, T, H * n] -> [B * G, T, heads * n]
      y = y.reshape(b, t, groups, -1)
      return jnp.moveaxis(y, 2, 1).reshape(b * groups, t, -1)

    def per_group(y):  # [H * n] -> [B * G, heads * n]
      return jnp.tile(y.reshape(groups, -1), (b, 1))

    @jax.checkpoint
    def rule(step):
      *qkv, write_step, decay_step, gate_step, step_taps, a, bias = step
      with jax.named_scope("gated_delta/conv"):
        q_step, k_step, v_step = (
            nn.silu(causal_depthwise_conv(y[None], tap)[0])
            .astype(jnp.float32).reshape(t, heads, d)
            for y, tap in zip(qkv, step_taps))
      with jax.named_scope("gated_delta/scan"):
        beta = jax.nn.sigmoid(write_step.astype(jnp.float32))
        g = -jnp.exp(a)[:, None] * jax.nn.softplus(
            decay_step.astype(jnp.float32) + bias).reshape(t, heads, d)
        q_step = l2_normalize(q_step, self.eps) * d ** -0.5
        k_step = l2_normalize(k_step, self.eps)
        out = gated_delta_rule(
            q_step[None], k_step[None], v_step[None], g[None],
            beta[None], chunk=self.chunk, dtype=self.dtype)[0]
        out = out * jax.lax.rsqrt(
            jnp.mean(jnp.square(out), -1, keepdims=True) + self.eps)
        out = norm * out * jax.nn.sigmoid(
            gate_step.astype(jnp.float32).reshape(t, heads, d))
        return out.reshape(t, heads * d).astype(self.dtype)

    # The map stands under neither scope: `gated_delta/conv` inside
    # `gated_delta/scan` would read as the outer one.
    out = jax.lax.map(rule, (
        *(grouped(y) for y in (*projected, write, decay, gate)),
        tuple(jnp.tile(jnp.moveaxis(
            tap.reshape(-1, groups, heads * d), 1, 0), (b, 1, 1))
              for tap in taps),
        per_group(a_log), per_group(dt_bias)))
    # [B * G, T, heads * D] -> [B, T, H * D]
    out = jnp.moveaxis(out.reshape(b, groups, t, heads * d), 1, 2
                       ).reshape(b, t, h * d)
    out = checkpoint_name(out, SAVED_RESIDUAL_NAMES[0])
    return dense("o_proj", width)(out)
