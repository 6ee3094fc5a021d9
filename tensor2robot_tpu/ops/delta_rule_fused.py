"""Pallas TPU kernel for the gated delta rule's forward pass, fused.

`layers/gated_delta.gated_delta_rule` in three stages is: a chunk's
operands prepared in XLA (decays, the triangular inverse, five matrix
products: a dozen arrays of v's size through HBM), the walk over the
chunks (`ops/delta_rule_walk.py`), and `out = carried + within @ new`.
Where nothing differentiates through the rule none of those arrays is
wanted afterwards. Here the three stages are ONE program: grid (rows of
the call, chunks), the chunk axis sequential, the heads' float32 states
[H, Dk, Dv] in VMEM scratch for the whole walk. A grid step reads the
chunk's q, k, v tiles and its per-position scalars, builds the
operands in VMEM, takes the walk's step on them and writes the chunk's
`out`, the only array that goes to HBM.

Layout: q, k, v enter and `out` leaves as [B, T * H, D], the free
reshape of the layer's [B, T, H, D]: a block is a chunk's [C * H, D]
rows, a head's [C, D] tile the rows h, h + H, ..., read and written at
a sublane stride (one load or store for eight rows). No transpose on
either side. Rows of a 16-bit array cannot be read at a stride (two
share a sublane), so q, k, v go in as float32 and are rounded to
`dtype` in VMEM: the same bits. The per-position scalars (the cumulated
g, beta and the exponentials of g that scale rows) are prepared in XLA,
1 MB a row of the batch, in the two orientations a tile needs them.

Two heads side by side: a head's [C, C] matrices fill half a vector
register's lanes and a quarter of an MXU pass at C = 64, and the
inverse is the longest chain of dependent products. Heads 2p and
2p + 1 share one [C, 2C] tile; a product against blockdiag(x_a, x_b)
serves both.

Arithmetic: each product has the operand dtype and the float32
accumulation of `gated_delta_rule`'s `mm`; the inverse of I + a is
`gated_delta._unit_lower_inverse`'s finite product in float32 at three
bfloat16 passes (`Precision.HIGH` written out: Mosaic takes no such
precision, so an operand is split into its bfloat16 high and low parts
and the three products that matter are added); the state is float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu.ops import delta_rule_walk
from tensor2robot_tpu.ops.delta_rule_walk import _NN, _NT, _TN, _dot

# The columns of `cols` a head has: g cumulated from the chunk's start,
# beta, exp(g), exp(g_end - g).
_COLUMNS = 4


def _split(x):
  """x float32 as (high, low) bfloat16 parts: x ~ high + low to 2^-16."""
  high = x.astype(jnp.bfloat16)
  return high, (x - high.astype(jnp.float32)).astype(jnp.bfloat16)


def _block_diagonal(x, c):
  """[x_a | x_b] [c, 2c] -> [[x_a, 0], [0, x_b]] [2c, 2c]."""
  lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
  zero = jnp.zeros_like(x)
  return jnp.concatenate([jnp.where(lane < c, x, zero),
                          jnp.where(lane >= c, x, zero)], axis=0)


def _pair_dot_high(y_parts, p_parts):
  """[y_a | y_b] @ blockdiag(p_a, p_b) = [y_a p_a | y_b p_b] for
  float32 operands given as their `_split` parts, at three bfloat16
  passes (high x high + high x low + low x high: what `Precision.HIGH`
  is on a TPU) in ONE product: the passes are concatenated along the
  contraction, so the MXU's accumulator adds them."""
  y_high, y_low = y_parts
  c = p_parts[0].shape[0]
  p_high, p_low = (_block_diagonal(p, c) for p in p_parts)
  return _dot(jnp.concatenate([y_high, y_high, y_low], axis=1),
              jnp.concatenate([p_high, p_low, p_high], axis=0), _NN)


def _pair_unit_lower_inverse(a):
  """`gated_delta._unit_lower_inverse` on two heads' tiles side by
  side, [a_a | a_b] [C, 2C]: the same products, those that do not
  depend on each other stacked ([inverse; power] @ power), six
  dependent steps for ten."""
  c = a.shape[0]
  row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
  lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
  eye = jnp.where((row == lane) | (row + c == lane), 1.0, 0.0)
  inverse = eye.astype(a.dtype) - a
  parts = _split(a)
  power = _pair_dot_high(parts, parts)  # a^2
  for _ in range(int(np.log2(c)) - 2):
    high, low = _split(jnp.concatenate([inverse, power], axis=0))
    stacked = _pair_dot_high((high, low), (high[c:], low[c:]))
    inverse = inverse + stacked[:c]
    power = stacked[c:]
  return inverse + _pair_dot_high(_split(inverse), _split(power))


def _diagonal(x_a, x_b):
  """[[x_a, 0], [0, x_b]] of two [C, D] tiles."""
  zero = jnp.zeros_like(x_a)
  return jnp.concatenate([jnp.concatenate([x_a, zero], axis=1),
                          jnp.concatenate([zero, x_b], axis=1)], axis=0)


def _two_heads(q, k, v, scalars, g_rows, end_decay, state_ref, two, dtype):
  """A chunk of the heads `two` with their [C, C] tiles side by side in
  one [C, 2C] tile. q, k, v, `scalars` ([C, 4]) and `end_decay`
  ([1, Dv]) are lists of the two heads'; `g_rows` [1, 2C]. Returns the
  two heads' `out`, [C, 2 Dv]."""
  c, dk = k[0].shape
  dv = v[0].shape[-1]
  row = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
  lane = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
  first = lane < c
  col = jnp.where(first, lane, lane - c)
  lower, strict = row >= col, row > col
  g, beta, exp_g, exp_to_end = (
      [s[:, i:i + 1] for s in scalars] for i in range(_COLUMNS))
  # exp(g_i - g_j) for j <= i; masked before the exponential, where
  # the other half would overflow.
  decay = jnp.exp(jnp.where(
      lower, jnp.where(first, g[0], g[1]) - g_rows, -jnp.inf))
  k_beta = [k[i] * beta[i] for i in range(2)]  # float32
  # [[k_beta_a | k_beta_b]; [q_a | q_b]] against [[k_a, 0]; [0, k_b]]:
  # [(beta k) k^T of a | of b] over [q k^T of a | of b].
  products = _dot(
      jnp.concatenate([
          jnp.concatenate([x.astype(dtype) for x in k_beta], axis=1),
          jnp.concatenate(q, axis=1)], axis=0),
      _diagonal(*k), _NT)
  a = jnp.where(strict, products[:c] * decay, 0.0)
  within = jnp.where(lower, products[c:] * decay, 0.0).astype(dtype)
  solve = _pair_unit_lower_inverse(a).astype(dtype)
  # [writes_a | k_decayed_a | writes_b | k_decayed_b]
  solved = _dot(solve, _diagonal(*(
      jnp.concatenate([(v[i] * beta[i]).astype(dtype),
                       (k_beta[i] * exp_g[i]).astype(dtype)], axis=1)
      for i in range(2))), _NN)
  news, carrieds = [], []
  for i, h in enumerate(two):
    writes = solved[:, i * (dv + dk):i * (dv + dk) + dv]
    k_decayed = solved[:, i * (dv + dk) + dv:(i + 1) * (dv + dk)]
    q_decayed = (q[i] * exp_g[i]).astype(dtype)
    k_to_end = (k[i] * exp_to_end[i]).astype(dtype)
    # The walk's step, as `delta_rule_walk._forward_kernel` has it;
    # k_decayed and q_decayed read the state in one product.
    state = state_ref[h]
    read = _dot(jnp.concatenate([k_decayed.astype(dtype), q_decayed],
                                axis=0), state.astype(dtype), _NN)
    new = (writes - read[:c]).astype(dtype)
    state_ref[h] = state * end_decay[i] + _dot(k_to_end, new, _TN)
    news.append(new)
    carrieds.append(read[c:])
  return (jnp.concatenate(carrieds, axis=1)
          + _dot(within, _diagonal(*news), _NN))


def _fused_kernel(q_ref, k_ref, v_ref, cols_ref, rows_ref, end_decay_ref,
                  out_ref, state_scr, *, block: int, dtype):
  """One chunk of all the heads. q, k refs [C * H, Dk], v and out
  [C * H, Dv] (row t * H + h: position t of head h); cols
  [groups, C, 4 * block], rows [groups, block / 2, 2C], end_decay
  [groups, block, Dv]; `state_scr` [H, Dk, Dv] float32 lives across the
  chunk axis. A loop over the groups of `block` heads, a group's pairs
  unrolled: independent chains side by side for the scheduler."""

  @pl.when(pl.program_id(1) == 0)
  def _start():
    state_scr[...] = jnp.zeros_like(state_scr)

  groups, c = cols_ref.shape[:2]
  heads = groups * block

  def group(i, carry):
    base = pl.multiple_of(i * block, block)
    cols = cols_ref[i]
    for p in range(block // 2):
      local = (2 * p, 2 * p + 1)  # the pair's heads in the group
      two = tuple(base + h for h in local)
      tile = lambda ref: [  # noqa: E731
          ref[pl.ds(h, c, stride=heads), :].astype(dtype) for h in two]
      out = _two_heads(
          tile(q_ref), tile(k_ref), tile(v_ref),
          [cols[:, _COLUMNS * h:_COLUMNS * (h + 1)] for h in local],
          rows_ref[i, p:p + 1, :],
          [end_decay_ref[i, h:h + 1, :] for h in local],
          state_scr, two, dtype)
      dv = out.shape[-1] // 2
      for j, h in enumerate(two):
        out_ref[pl.ds(h, c, stride=heads), :] = out[:, j * dv:(j + 1) * dv]
    return carry

  jax.lax.fori_loop(0, groups, group, 0)


def forward(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
            beta: jax.Array, *, chunk: int, dtype, block: int | None = None,
            interpret: bool = False) -> jax.Array:
  """The gated delta rule over q, k [B, T, H, Dk], v [B, T, H, Dv], g
  and beta [B, T, H], T a multiple of `chunk`: `out` [B, T, H, Dv]
  float32, what `gated_delta_rule` returns. Not differentiable: it is
  the program of an evaluation that no backward pass follows. `block`
  heads to a turn of the kernel's loop, made even (it need not divide
  H: the heads are padded); left out, `delta_rule_walk.head_block`'s."""
  b, t, h, dk = q.shape
  dv = v.shape[-1]
  n = t // chunk
  block = delta_rule_walk.head_block(h) if block is None else min(block, h)
  block += block % 2
  groups = pl.cdiv(h, block)
  heads = groups * block

  def pad_heads(x):  # beta 0 writes nothing: a padded head's out is 0
    return x if heads == h else jnp.pad(
        x, ((0, 0), (0, 0), (0, heads - h)) + ((0, 0),) * (x.ndim - 3))

  g, beta = (pad_heads(x.astype(jnp.float32)).reshape(b, n, chunk, heads)
             for x in (g, beta))
  g = jnp.cumsum(g, axis=2)  # decay from the chunk's start
  # [B, N, C, heads, 4] -> [B, N, groups, C, block * 4]
  cols = jnp.stack([g, beta, jnp.exp(g), jnp.exp(g[:, :, -1:] - g)], -1)
  cols = cols.reshape(b, n, chunk, groups, block * _COLUMNS).swapaxes(2, 3)
  # [B, N, C, heads] -> [B, N, groups, block / 2, 2C]: g along the
  # lanes, two heads side by side.
  rows = g.reshape(b, n, chunk, groups, block).transpose(0, 1, 3, 4, 2)
  rows = rows.reshape(b, n, groups, block // 2, 2 * chunk)
  end_decay = jnp.broadcast_to(
      jnp.exp(g[:, :, -1]).reshape(b, n, groups, block, 1),
      (b, n, groups, block, dv))
  smalls = (cols, rows, end_decay)

  def tiles(x):  # [B, T, H, D] -> [B, T * heads, D] float32
    return pad_heads(x).astype(jnp.float32).reshape(b, t * heads, -1)

  def wide(width):
    return pl.BlockSpec((None, chunk * heads, width),
                        lambda bi, i: (bi, i, 0))

  def small(x):
    return pl.BlockSpec((None, None) + x.shape[2:],
                        lambda bi, i: (bi, i, 0, 0, 0))

  out = pl.pallas_call(
      functools.partial(_fused_kernel, block=block, dtype=dtype),
      grid=(b, n),
      in_specs=[wide(dk), wide(dk), wide(dv)] + [small(x) for x in smalls],
      out_specs=wide(dv),
      out_shape=jax.ShapeDtypeStruct((b, t * heads, dv), jnp.float32),
      scratch_shapes=[pltpu.VMEM((heads, dk, dv), jnp.float32)],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "arbitrary"),
          vmem_limit_bytes=64 * 1024 * 1024),
      interpret=interpret,
  )(tiles(q), tiles(k), tiles(v), *smalls)
  return out.reshape(b, t, heads, dv)[:, :, :h]
