"""Pallas TPU flash attention: the long-context single-chip hot op.

The framework's attention surfaces (SNAIL trunks, ring attention's
per-device blocks) are MXU-dominated but HBM-limited at long sequence
lengths: materializing [T, T] scores costs O(T²) HBM traffic, which is
exactly what the memory hierarchy punishes (HBM → VMEM → MXU;
/opt/skills/guides/pallas_guide.md). This kernel computes exact
attention in O(T) memory: Q/K/V stream through VMEM in (block_q,
block_k) tiles, scores live only in registers/VMEM, and the online
softmax carries running max/normalizer/accumulator in f32 scratch.

Design notes (speeds were measured on an earlier installation and are
not measured on this one — PERF.md is the record): the default
(block_q, block_k) = (1024, 2048) keeps the grid short — it is a
sequential loop, so fewer, larger steps amortize both Mosaic's
per-step overhead and the online-softmax rescale chain — and needs the
raised VMEM budget below. Causal tiles come in three regimes (see
`_flash_kernel`): fully-future tiles skip all compute, fully-past
tiles skip the mask iotas/selects, only diagonal-straddling tiles pay
for masking. At D=64 the score/PV matmuls contract only 64 lanes of
the 128-wide MXU and the online-softmax VPU work (exp, max, rescale)
is comparable to the matmul time, so models that care about attention
throughput at long context should prefer MXU-width heads. At D=256
the kernels run unchanged at the default blocks (the language model's
gated attention: 16 heads, T=8192, 4 rows, bf16; a v5e, PR 34): the
forward kernel took 18.9-21.1 ms a call and the backward pair 58.7
ms, 53-59 % and 67 % of the bf16 peak counting the matrix products of
the causal half (PERF.md section 5); the f32 score tile, not the head
size, sets the VMEM budget. Keys and values need not be of one width:
q and k are Dk wide, v, the output and their cotangents Dv, read off
the arguments, and every tile, scratch and result has its own width.
At keys of 192 over values of 128 (latent attention: 32 heads, T=8192,
2 rows, bf16; one call outside the model on a v5e, PR 36) the forward
kernel took 18.9 ms at the default blocks (17.3 at 1024 x 1024) and
the backward pair 48.7 ms: 72.5 and 101.6 TFLOP/s over the products of
the causal half, 37 and 52 % of the bf16 peak. 256 over 128 took the
same 19.1 and 48.9 ms (a contraction of 192 lanes occupies two passes
of the 128-wide MXU), 128 over 128 14.2 and 33.1, 256 over 256 24.6 and
61.8; blocks of (2048, 2048) double the backward pair (91 ms). Since
PR 45 the backward is one fused program (below): 31.5 ms a call at 192
over 128 for the pair's 24.3 + 21.0, 62.0 for 50.2 + 38.2 at 48 heads
over 8 at 128 (4 rows), 17.6 for 16.0 + 12.1 under a band of 512 at 64
over 8, 40.3 for 31.8 + 25.1 at 256 (PERF.md section 5).

A window (`causal=True, window=W`: query i sees key j iff 0 <= i - j <
W) makes the mask a band, and the band sets the blocks: both are one
size, the largest power of two that is at most W (and the requests, and
divides T), so the default 1024 x 2048, two and four windows wide at
W = 512, come down to 512 x 512 (`window_tiling`). The grids then walk
the band's tiles alone: a row of tiles visits 1 + ceil((W - 1) / block)
key blocks, whatever T is, through index maps offset by the query
block (`_band_col`, `_band_row`); nothing outside the band is fetched.
At the band's trailing edge stands a fourth tile regime beside the
causal three (`_causal_tile_regimes`). At W = block every visited tile
straddles one edge and half of the tiles' pairs are masked work; at
half that block an unmasked tile stands between the two edges (two
thirds of the pairs are the band's) and the grid has twice the steps.
A window of T or more lowers to the causal programs.

Keys and values may come with fewer heads than the queries (grouped
queries): query head h reads key-value head h // group where it lies,
through the blocks' index maps, so nothing is repeated in HBM; the
backward program's grid runs over the key-value heads and walks a
group's query heads inside, so dK and dV are summed over the group in
the float32 scratch and written once.

Training works end to end, and the backward is Pallas too: ONE program
(`_fused_bwd_kernel`) that recomputes a visited tile pair's scores from
q/k + the saved logsumexp once and feeds all three gradients from it —
p = exp(s − lse), dp = dO·Vᵀ, ds = p·(dp − D)·scale, then dv += pᵀ·dO,
dk += dsᵀ·q, dq += ds·k: five matmuls and one pass of float32
exponentials a tile pair. It walks a key block outermost and the query
blocks innermost, so dk/dv accumulate per K-block in float32 scratch;
a Q-block's dq gets a term from every K-block that sees it, so dq's
float32 accumulator holds the head's whole sequence in VMEM ([T, Dk]:
6.3 MB at T=8192, Dk=192; 8.4 MB at 256 or at T=32k, D=64, which VMEM
pads to 128 lanes), zeroed when the grid reaches a head and written
once when it leaves it. Under a group a K-block comes round once a
query head, so dk/dv hold the whole sequence too (3 x 4.2 MB at
T=8192, D=128). The softmax-jacobian row term D_i = rowsum(dO·O)
(minus any lse cotangent) is a cheap XLA elementwise reduce computed
once outside. No [T, T] tensor exists in either direction; the
tri-regime causal tiling applies to both directions. The backward
program has no sequential max/rescale chain, so its five matmuls per
tile pair keep the MXU busier than the forward's two.

Keys wider than 128 lanes take key blocks of no more than 1024 in the
fused program (`_fused_blocks`, `_FUSED_WIDE_BLOCK`: Mosaic unrolls a
tile, and the program of the default tile at such keys is over the
size the core runs at speed): the caller's key block halved, so it
divides T as the caller's does, whatever T is.

Where a sequence's accumulators do not fit (`fused_backward_bytes`:
accumulators, their double-buffered output blocks and the streamed
blocks' buffers against the VMEM budget less 20 MiB for the tiles —
65,536 positions at D=128; 32k at D=64 in float32) the backward runs
the pair of programs the fused one replaced, each making the tiles
anew: `_dkdv_kernel` accumulates dk/dv per K-block over the Q grid,
`_dq_kernel` dq per Q-block over the K grid (seven matmuls a pair).
The choice is read off the shapes at trace time, counted in the
registry (`flash_attention.backward.fused_traces`, `.paired_traces`),
and the two add every sum's terms in the same order: in the
interpreter at float32 their results are equal to the bit.

Pairs with `parallel/ring_attention.py`: the ring shards the sequence
ACROSS chips (ppermute over ICI), this kernel tiles it WITHIN a chip;
both implement the same online-softmax math.

`flash_attention(..., interpret=True)` runs the kernels (forward AND
backward) in the pallas interpreter — how the CPU test suite verifies
numerics without TPU hardware.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu.telemetry import metrics as tmetrics

_NEG_INF = -1e30
# The names under which the forward's output and logsumexp become the
# backward's residuals (`_flash_lse_fwd`): what a checkpoint around a
# caller of the kernel saves to keep the backward pass from running
# the forward kernel a second time (`layers/transformer.apply_block`).
SAVED_RESIDUAL_NAMES = ("flash_attention_out", "flash_attention_lse")
# Mosaic's default scoped-VMEM budget is 16 MiB; at the default blocks
# the f32 score tile alone is 8 MiB and the dk/dv kernel needs 18.5 MiB
# (T=32k, D=64, bf16). Half of a v5e core's 128 MiB leaves room for
# the f32 case and for XLA's own use around the call.
_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=64 * 1024 * 1024)


def _auto_block(requested: int, t: int) -> int:
  """Largest block ≤ `requested` that divides T (halving fallback).

  Big blocks amortize Mosaic's per-grid-step overhead (the grid is a
  sequential loop, so step count is the tax); T not divisible by
  the default shrinks to a power-of-two divisor, or to T itself for
  short sequences.
  """
  b = min(requested, t)
  while b > 1 and t % b:
    b //= 2
  if b < 8 and b != t:
    # Mosaic tiles need a sublane dim ≥8 (or the full dimension);
    # such T (e.g. odd lengths > the default block) cannot tile.
    raise ValueError(
        f"Sequence length {t} has no TPU-tileable block size: need a "
        f"power-of-two divisor ≥ 8 (or T ≤ {requested}; under a "
        "window the request is at most the power of two at or under "
        "the window, and no less than 8); pad T upstream — lengths "
        "are static in this framework.")
  return b


def _causal_tile_regimes(row_block, col_block, block_q: int,
                         block_k: int, window=None):
  """(visible, unmasked) predicates for one causal score tile.

  Shared by every kernel so forward and backward can never
  disagree on which tiles are masked. Without a window:
    fully-future (not visible): every col > every row — all-masked,
      skip the tile's compute entirely;
    unmasked (fully past): every col <= every row — mask is all-true,
      run the unmasked update (no iota/select work);
    otherwise the tile straddles the diagonal and pays for masking.
  With `window` (row i sees col j iff 0 <= i - j < window) the band
  has a trailing edge too, a fourth regime: a tile whose every col is
  `window` or more behind every row is not visible, and a tile is
  unmasked only where its first col is also less than `window` behind
  its last row; what straddles either edge pays for masking
  (`_tile_mask`).
  """
  last_row = row_block * block_q + block_q - 1
  first_row = row_block * block_q
  first_col = col_block * block_k
  last_col = col_block * block_k + block_k - 1
  visible, unmasked = first_col <= last_row, last_col <= first_row
  if window is not None:
    visible &= last_col > first_row - window
    unmasked &= first_col > last_row - window
  return visible, unmasked


def _tile_mask(row_block, col_block, block_q: int, block_k: int, window):
  """[block_q, block_k] bool: which pairs of a straddling tile are
  seen, `col <= row` and with a window `col > row - window`."""
  rows = row_block * block_q + jax.lax.broadcasted_iota(
      jnp.int32, (block_q, block_k), 0)
  cols = col_block * block_k + jax.lax.broadcasted_iota(
      jnp.int32, (block_q, block_k), 1)
  mask = cols <= rows
  if window is not None:
    mask &= cols > rows - window
  return mask


def band_blocks(window: int, block: int) -> int:
  """Key blocks of `block` that the band of a query block of `block`
  rows touches: its own (the diagonal) and the ceil((window - 1) /
  block) before it. The same count of query blocks sees a key block."""
  return 1 + -(-(window - 1) // block)


def window_tiling(t: int, window: int, block_q: int = 1024,
                  block_k: int = 2048):
  """How a band of `window` over `t` positions is tiled, static:
  (block, blocks a row of tiles visits, pairs the band holds, pairs of
  the tiles the grid computes), the last two for one head of one row.
  With a window both blocks are one size, the largest power-of-two
  divisor of `t` that is at most the window and both requests: a wider
  tile computes `block - window` columns a row that the band does not
  hold (`flash_attention`)."""
  block = _window_block(block_q, block_k, window, t)
  visited = band_blocks(window, block)
  nq = t // block
  tiles = sum(min(i + 1, visited) for i in range(nq))
  held = min(window, t)  # the first `held` rows see 1 .. held keys
  band = held * (held + 1) // 2 + (t - held) * held
  return block, visited, band, tiles * block * block


def _window_block(block_q: int, block_k: int, window: int, t: int) -> int:
  # The power of two at or under the window; Mosaic tiles no fewer
  # than 8 rows, so a narrower window still takes blocks of 8.
  floor = max(8, 1 << (window.bit_length() - 1))
  return _auto_block(min(block_q, block_k, floor), t)


def _for_tile_regime(update, causal: bool, row_block, col_block,
                     block_q: int, block_k: int, window, in_range=None):
  """Runs `update(use_mask)` for one score tile in its regime
  (`_causal_tile_regimes`: the same predicates forward and backward):
  nothing where the tile is not visible or `in_range` (a band's blocks
  before the first and past the last) is false, without the mask where
  every pair is seen, with it where the tile straddles an edge. At
  T=32k with bq=1024/bk=2048 only ~1 straddling block per q row pays
  for the mask iotas + selects; fully-future tiles (half the grid) skip
  all compute. (`unmasked` implies `visible`, but the conjunction keeps
  the two pl.when predicates visibly disjoint-and-exhaustive over the
  visible tiles.)"""
  if not causal:
    update(False)
    return
  visible, unmasked = _causal_tile_regimes(
      row_block, col_block, block_q, block_k, window)
  if in_range is not None:
    visible &= in_range
  pl.when(visible & unmasked)(lambda: update(False))
  pl.when(visible & jnp.logical_not(unmasked))(lambda: update(True))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *, scale: float, causal: bool, block_q: int,
                  block_k: int, num_k_blocks: int, window=None):
  """Grid (batch*heads, T/block_q, key blocks visited); innermost dim
  iterates K/V blocks sequentially (TPU grids are loops), accumulating
  into VMEM scratch; the last K step normalizes, writes the output and
  the logsumexp (the backward's residual). Without a window the key
  blocks visited are all T/block_k; with one, the `num_k_blocks` that
  end at the query block's own (`_band_col`)."""
  j = pl.program_id(2)

  @pl.when(j == 0)
  def _init():
    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

  # program_id must be read OUTSIDE the pl.when bodies (the
  # interpreter cannot lower it inside the conditional); the mask
  # itself is built INSIDE the masked branch so unmasked tiles pay
  # for neither the iotas nor the selects.
  i = pl.program_id(1) if causal else None
  col = j if window is None else _band_col(i, j, num_k_blocks)

  def _update_impl(use_mask):
    q = q_ref[0]  # [block_q, D]
    k = k_ref[0]  # [block_k, D]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [bq, bk]
    if use_mask:
      mask = _tile_mask(i, col, block_q, block_k, window)
      s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if use_mask:
      p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

  _for_tile_regime(_update_impl, causal, i, col, block_q, block_k, window,
                   None if window is None else col >= 0)

  @pl.when(j == num_k_blocks - 1)
  def _finalize():
    l_final = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / l_final).astype(o_ref.dtype)
    # The per-row lse stays SUBLANE-major ([block_q, 1]) end to end:
    # that is the reduction layout m/l already live in, it is the
    # layout the backward broadcasts against score tiles, and storing
    # it directly is a plain VMEM→HBM copy of T×4 bytes per head.
    # Round 3 broadcast to 128 lanes (~134 MB of spurious writes per
    # layer at T=32k); rounds 4-5 transposed to lanes via an MXU
    # identity matmul (8× traffic + one systolic-array pass of
    # f32-emulation error on every lse, which the backward then paid
    # AGAIN relayouting back — the round-5 advisor's dv-error
    # finding). No matmul touches the lse anymore.
    lse_ref[0, 0] = m_scr[...] + jnp.log(l_final)  # [block_q, 1]


def _band_col(row_block, step, visited: int):
  """The key block that step `step` of `visited` reads for query block
  `row_block` of a band (both blocks one size): the last step reads
  the diagonal block, the ones before it the blocks behind. Negative
  for a band's first rows, whose band starts at position 0: the index
  map reads block 0 there and the kernel computes nothing."""
  return row_block - (visited - 1) + step


def _band_row(col_block, step):
  """The query block that step `step` reads for key block `col_block`
  of a band: the diagonal block first, then the ones after it; past
  the last block the index map reads the last and nothing is
  computed."""
  return col_block + step


def _kv_index(g, group: int):
  """The key-value head's row of the folded [B * KV, T, D] arrays that
  query head row `g` of [B * H, T, D] reads: heads h of a group of
  `group` share key-value head h // group."""
  return g if group == 1 else g // group


def _fold(x):
  """[B, T, H, D] -> [B*H, T, D]: one grid row per (batch, head)."""
  b, t, h, d = x.shape
  return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _flash_forward_impl(q, k, v, causal: bool, block_q: int,
                        block_k: int, interpret: bool, window=None
                        ) -> Tuple[jax.Array, jax.Array]:
  """Runs the kernel; returns (out [B,T,H,Dv], lse [B*H, T]). q and
  k are `d` wide, v and the output `dv`: the two need not be equal.
  k and v may come with fewer heads than q (a divisor): query head h
  reads key-value head h // group from where it lies, through the
  blocks' index maps, and nothing is repeated."""
  b, t, h, d = q.shape
  dv = v.shape[-1]
  group = h // k.shape[2]
  num_q_blocks = t // block_q
  num_k_blocks = (t // block_k if window is None
                  else band_blocks(window, block_k))
  scale = 1.0 / np.sqrt(d)

  if window is None:
    def kv_map(g, i, j):
      return (_kv_index(g, group), j, 0)
  else:
    def kv_map(g, i, j):
      return (_kv_index(g, group),
              jnp.maximum(_band_col(i, j, num_k_blocks), 0), 0)

  kernel = functools.partial(
      _flash_kernel, scale=scale, causal=causal, block_q=block_q,
      block_k=block_k, num_k_blocks=num_k_blocks, window=window)
  out, lse = pl.pallas_call(
      kernel,
      grid=(b * h, num_q_blocks, num_k_blocks),
      in_specs=[
          pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
          pl.BlockSpec((1, block_k, d), kv_map),
          pl.BlockSpec((1, block_k, dv), kv_map),
      ],
      out_specs=[
          pl.BlockSpec((1, block_q, dv), lambda g, i, j: (g, i, 0)),
          # lse packed [BH, num_q_blocks, block_q, 1]: sublane-major
          # per-row values, the same (block_q, 1) class as the m/l
          # scratch — T×4 bytes per head, no lane broadcast, no MXU
          # relayout (see _finalize).
          pl.BlockSpec((1, 1, block_q, 1), lambda g, i, j: (g, i, 0, 0)),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((b * h, t, dv), q.dtype),
          jax.ShapeDtypeStruct((b * h, num_q_blocks, block_q, 1),
                               jnp.float32),
      ],
      scratch_shapes=[
          pltpu.VMEM((block_q, 1), jnp.float32),   # running max
          pltpu.VMEM((block_q, 1), jnp.float32),   # running normalizer
          pltpu.VMEM((block_q, dv), jnp.float32),  # output accumulator
      ],
      compiler_params=_COMPILER_PARAMS,
      interpret=interpret,
  )(_fold(q), _fold(k), _fold(v))
  return (out.reshape(b, h, t, dv).transpose(0, 2, 1, 3),
          lse.reshape(b * h, t))


def _backward_tile(q, k, v, do, lse, delta, scale: float, mask):
  """(p, ds) of one [block_q, block_k] tile, float32, from the
  recomputed scores: p = exp(s − lse), ds = p·(dO·Vᵀ − δ)·scale. What
  every backward program makes of a visited tile pair; `mask` is
  `_tile_mask`'s where the tile straddles an edge, else None. lse and
  delta arrive sublane-major [block_q, 1] — already the layout the
  row-wise broadcasts against score tiles need; no relayout."""
  s = jax.lax.dot_general(
      q, k, (((1,), (1,)), ((), ())),
      preferred_element_type=jnp.float32) * scale    # [bq, bk]
  if mask is not None:
    s = jnp.where(mask, s, _NEG_INF)
  p = jnp.exp(s - lse)
  if mask is not None:
    p = jnp.where(mask, p, 0.0)
  dp = jax.lax.dot_general(
      do, v, (((1,), (1,)), ((), ())),
      preferred_element_type=jnp.float32)            # [bq, bk]
  return p, p * (dp - delta) * scale


def _transposed_product(a, b):
  """aᵀ·b, float32: a [block_q, block_k] tile cast to the input dtype
  for the MXU (f32 accumulation via preferred_element_type) — the
  standard flash-backward precision contract, bit-exact in f32 tests."""
  return jax.lax.dot_general(
      a.astype(b.dtype), b, (((0,), (0,)), ((), ())),
      preferred_element_type=jnp.float32)


def _fused_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                      scale: float, causal: bool, block_q: int,
                      block_k: int, num_q_blocks: int, num_k_blocks: int,
                      window=None, group: int = 1,
                      total_q_blocks: int = 0):
  """The backward pass as ONE program: a visited tile pair's p and ds
  are made once and feed dV, dK and dQ together (five products).

  Grid (B*KV, group, T/block_k, query blocks visited): for a key-value
  head each query head of its group in turn, for a query head a key
  block outermost and the query blocks innermost, `_dkdv_kernel`'s
  walk. A query block's dQ gets a term from every key block that sees
  it, so dQ's float32 accumulator holds the head's whole sequence
  (`dq_scr` [T, Dk], addressed by query block), zeroed where the grid
  reaches a head and written out once where it leaves it. dK and dV
  accumulate a key block at a time where a key-value head has one
  query head (`dk_scr` [block_k, Dk], written at the key block's last
  step); under a group a key block comes round once a query head, so
  they hold the whole sequence too and are written once a key-value
  head. The terms of every sum arrive in the pair's order: a query
  block's by ascending key block, a key block's head-major and query
  block minor."""
  head, j, step = (pl.program_id(axis) for axis in (1, 2, 3))
  qi = step if window is None else _band_row(j, step)
  first_tile = (j == 0) & (step == 0)
  last_tile = (j == num_k_blocks - 1) & (step == num_q_blocks - 1)
  if group == 1:
    kv_rows = slice(None)
    kv_first, kv_last = step == 0, step == num_q_blocks - 1
  else:
    kv_rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
    kv_first = first_tile & (head == 0)
    kv_last = last_tile & (head == group - 1)

  @pl.when(first_tile)
  def _init_dq():
    dq_scr[...] = jnp.zeros_like(dq_scr)

  @pl.when(kv_first)
  def _init_dkdv():
    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)

  def _update(use_mask):
    q, k, do = q_ref[0], k_ref[0], do_ref[0]
    mask = (_tile_mask(qi, j, block_q, block_k, window)
            if use_mask else None)
    p, ds = _backward_tile(q, k, v_ref[0], do, lse_ref[0, 0],
                           delta_ref[0, 0], scale, mask)
    dv_scr[kv_rows, :] += _transposed_product(p, do)
    ds = ds.astype(q.dtype)  # once, for both of its products
    dk_scr[kv_rows, :] += _transposed_product(ds, q)
    q_rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
    dq_scr[q_rows, :] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

  _for_tile_regime(
      _update, causal, qi, j, block_q, block_k, window,
      None if window is None else qi < total_q_blocks)

  @pl.when(kv_last)
  def _write_dkdv():
    dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

  @pl.when(last_tile)
  def _write_dq():
    dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                 causal: bool, block_q: int, block_k: int,
                 num_q_blocks: int, window=None, group: int = 1,
                 total_q_blocks: int = 0):
  """The pair's first program. Grid (B*KV, T/block_k, group * query
  blocks visited); the innermost dim iterates, for each query head of
  the key-value head's group in turn, the Q blocks sequentially,
  accumulating this K-block's dk/dv in float32 VMEM scratch from
  recomputed p = exp(s − lse) tiles; the last step writes out, so a
  group's sum is made once, in float32. Without a window the query
  blocks visited are all `num_q_blocks` = T/block_q; with one, the
  `num_q_blocks` from the key block's own on (`_band_row`), of
  `total_q_blocks`."""
  j = pl.program_id(1)
  step = pl.program_id(2)

  @pl.when(step == 0)
  def _init():
    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)

  qi = step if group == 1 else step % num_q_blocks
  if window is not None:
    qi = _band_row(j, qi)

  def _update(use_mask):
    q, do = q_ref[0], do_ref[0]
    mask = (_tile_mask(qi, j, block_q, block_k, window)
            if use_mask else None)
    p, ds = _backward_tile(q, k_ref[0], v_ref[0], do, lse_ref[0, 0],
                           delta_ref[0, 0], scale, mask)
    dv_scr[...] += _transposed_product(p, do)
    dk_scr[...] += _transposed_product(ds, q)

  _for_tile_regime(
      _update, causal, qi, j, block_q, block_k, window,
      None if window is None else qi < total_q_blocks)

  @pl.when(step == group * num_q_blocks - 1)
  def _finalize():
    dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *, scale: float, causal: bool,
               block_q: int, block_k: int, num_k_blocks: int,
               window=None):
  """The pair's second program, which makes every tile's p and ds
  anew. Grid (B*H, T/block_q, key blocks visited); innermost iterates K
  blocks, accumulating this Q-block's dq = Σ_j ds_j·k_j in VMEM
  scratch. The key blocks visited are the forward's."""
  i = pl.program_id(1)
  step = pl.program_id(2)
  kj = step if window is None else _band_col(i, step, num_k_blocks)

  @pl.when(step == 0)
  def _init():
    dq_scr[...] = jnp.zeros_like(dq_scr)

  def _update(use_mask):
    k = k_ref[0]
    mask = (_tile_mask(i, kj, block_q, block_k, window)
            if use_mask else None)
    _, ds = _backward_tile(q_ref[0], k, v_ref[0], do_ref[0],
                           lse_ref[0, 0], delta_ref[0, 0], scale, mask)
    dq_scr[...] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

  _for_tile_regime(_update, causal, i, kj, block_q, block_k, window,
                   None if window is None else kj >= 0)

  @pl.when(step == num_k_blocks - 1)
  def _finalize():
    dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


# What the fused backward program may hold in VMEM beside its tiles
# (`fused_backward_bytes`): the kernels' budget less 20 MiB for a tile
# pair's float32 scores and their 16-bit copies at the default blocks.
# The least limit under which Mosaic compiles the program for a v5e,
# bisected at twelve shapes (PR 45), is the count less 15 MiB to the
# count and 14: the most in bfloat16 at the 1024 x 2048 tile (57 MiB
# for a count of 43 at 34,816 x 128; 55 for 41 at 32,768 x 64; 36 for
# 29 at 8,192 x 128 under a group of 6), under the count at key blocks
# of 1024 and in float32. So a count within this budget compiles with
# 6 MiB to spare (tests/test_windowed_language_model.py compiles seven
# shapes at 93 to 100 % of it).
_FUSED_BACKWARD_BUDGET = _COMPILER_PARAMS.vmem_limit_bytes - 20 * 2**20
# Mosaic unrolls a tile's arithmetic, twice for a causal program (the
# masked and the unmasked regime), and the core does not run a program
# over some size at speed: at 1024 x 2048 over keys wider than 128
# lanes the fused program took 2.6-2.9x the dK/dV program's time a
# tile (a v5e, PR 45: 70.8 ms a call at keys of 192 and 82.4 at 256,
# against the pair's 45.3 and 56.9; 157,000 and 181,000 lines of
# Mosaic's last dump against the dK/dV program's 148,000 at 256, which
# runs at speed), while the same tile without the causal regimes'
# second copy ran at speed (51.0 ms over 32 tiles a head for 75.2 over
# 20), and so did half the tile (1024 x 1024 or 512 x 2048: 35.9 and
# 40.3 ms). At keys of 128 lanes (115,000 lines) the whole tile is
# best (61.9 ms for the pair's 88.4; 68.2 at 1024 x 1024). So keys over
# one lane tile take key blocks of no more than this.
_FUSED_WIDE_BLOCK = 1024


def _fused_blocks(d: int, block_q: int, block_k: int, window):
  """The fused backward program's blocks, from the caller's (which
  divide T, `_blocks_and_window`): where a key is wider than one
  128-lane tile the key block is halved while it is over
  `_FUSED_WIDE_BLOCK` and its half is still a whole number of 8-row
  sublane tiles, so what comes out divides the caller's block and with
  it T (1536, a T under the default that is its own block, gives 768;
  an odd T's stays whole). Under a window both blocks, which are one
  size."""
  if d > 128:
    while block_k > _FUSED_WIDE_BLOCK and block_k % 16 == 0:
      block_k //= 2
  return (block_q if window is None else block_k), block_k


def fused_backward_bytes(t: int, d: int, dv: int, group: int,
                         block_q: int, block_k: int,
                         itemsize: int) -> int:
  """Bytes of VMEM that `_fused_bwd_kernel` holds outside a tile's own
  arithmetic: the float32 accumulators and the output blocks they are
  written to (two buffers each, the pipeline's) — dQ's over the whole
  sequence, dK's and dV's over a key block, or under a group over the
  whole sequence too — and the two buffers of every streamed block (q,
  dO, k, v, the two row vectors). A row is padded to whole 128-lane
  tiles."""
  def lanes(width):
    return -(-width // 128) * 128

  both = lanes(d) + lanes(dv)
  kv_rows = t if group > 1 else block_k
  held = (t * lanes(d) + kv_rows * both) * (4 + 2 * itemsize)
  streamed = 2 * ((block_q + block_k) * both * itemsize
                  + 2 * block_q * 128 * 4)
  return held + streamed


def _flash_bwd_impl(q, k, v, out, lse, do, dlse, causal: bool,
                    block_q: int, block_k: int, interpret: bool,
                    window=None):
  """Pallas flash backward: one fused program, or where a sequence's
  accumulators do not fit in VMEM the dkdv kernel + the dq kernel.

  `dlse` ([BH, T]) is the cotangent of the logsumexp output — zeros
  when the caller only used `out`: since ∂lse_i/∂s_ij = p_ij, it
  folds into the softmax-jacobian diagonal as ds = p·(dp − (δ − g)) —
  one subtraction in the precomputed per-row term, which is what makes
  the lse-composed ring attention trainable through this kernel.

  Where k and v have fewer heads than q, dk and dv come out with k's
  and v's heads: both programs' grids run over the key-value heads and
  walk each one's group of query heads inside, so the group's sum is
  made in the float32 scratch.

  Which program runs is read off the shapes (`fused_backward_bytes`
  against `_FUSED_BACKWARD_BUDGET`), and the registry counts it once a
  traced call: `flash_attention.backward.fused_traces`,
  `.paired_traces`. At the same blocks the two give the same sums in
  the same order; the fused program's key blocks are `_fused_blocks`'.
  """
  b, t, h, d = q.shape
  dv = v.shape[-1]  # v, out, do and dv are this wide; q, k, dq, dk `d`
  fused_q, fused_k = _fused_blocks(d, block_q, block_k, window)
  fused = fused_backward_bytes(
      t, d, dv, h // k.shape[2], fused_q, fused_k,
      q.dtype.itemsize) <= _FUSED_BACKWARD_BUDGET
  tmetrics.counter("flash_attention.backward.fused_traces" if fused
                   else "flash_attention.backward.paired_traces").inc()
  if fused:
    block_q, block_k = fused_q, fused_k

  q_f, k_f, v_f, do_f, o_f = map(_fold, (q, k, v, do, out))
  # δ_i = rowsum(dO·O) − dlse_i: the softmax-jacobian row term, a
  # cheap elementwise reduce XLA fuses. Both per-row vectors enter
  # the kernels in the forward's SUBLANE-major [BH, nq, block_q, 1]
  # layout — the broadcast layout the score-tile math needs, so
  # neither side pays an MXU relayout (rounds 4-5 made two lossy
  # systolic-array passes here — forward identity-transpose, backward
  # 1/8-contraction — which was the dominant term in the hardware
  # gate's dv error; chip_smoke.py's flash leg holds it).
  delta = (jnp.sum(do_f.astype(jnp.float32) * o_f.astype(jnp.float32),
                   axis=-1)
           - dlse.astype(jnp.float32))              # [BH, T]

  def tile_cols(x):  # [BH, T] → [BH, nq, block_q, 1]
    return x.astype(jnp.float32).reshape(b * h, t // block_q, block_q, 1)

  dq_f, dk_f, dv_f = (_fused_backward if fused else _paired_backward)(
      q_f, k_f, v_f, do_f, tile_cols(lse), tile_cols(delta), b, causal,
      block_q, block_k, interpret, window)

  def unfold(x):  # [B*heads, T, D] -> [B, T, heads, D]
    return x.reshape(b, -1, t, x.shape[-1]).transpose(0, 2, 1, 3)

  return unfold(dq_f), unfold(dk_f), unfold(dv_f)


def _fused_backward(q_f, k_f, v_f, do_f, lse, delta, b: int,
                    causal: bool, block_q: int, block_k: int,
                    interpret: bool, window):
  """(dq, dk, dv), folded, by `_fused_bwd_kernel`."""
  (bh, t, d), dv = q_f.shape, v_f.shape[-1]
  h, kv = bh // b, k_f.shape[0] // b
  group, scale = h // kv, 1.0 / np.sqrt(d)
  # A block that did not divide T would leave the last rows unvisited.
  assert t % block_q == 0 and t % block_k == 0, (t, block_q, block_k)
  nq, nk = t // block_q, t // block_k
  # Query blocks visited along the inner dimension: all, or a band's.
  nq_in = nq if window is None else band_blocks(window, block_k)

  # Step `s` of key block `j` of query head `head` of key-value head
  # `g` reads the `s`-th query block that the key block's band visits.
  def q_row(g, head, j, s):
    block = s if window is None else jnp.minimum(_band_row(j, s), nq - 1)
    return g * group + head, block

  def q_map(g, head, j, s):
    return (*q_row(g, head, j, s), 0)

  def row_map(g, head, j, s):
    return (*q_row(g, head, j, s), 0, 0)

  def k_map(g, head, j, s):
    return (g, j, 0)

  def whole_map(g, head, j, s):
    return (g, 0, 0)

  # dK and dV: a key block at a time, or under a group the sequence.
  kv_rows, kv_map = (block_k, k_map) if group == 1 else (t, whole_map)
  return pl.pallas_call(
      functools.partial(_fused_bwd_kernel, scale=scale, causal=causal,
                        block_q=block_q, block_k=block_k,
                        num_q_blocks=nq_in, num_k_blocks=nk,
                        window=window, group=group, total_q_blocks=nq),
      grid=(b * kv, group, nk, nq_in),
      in_specs=[
          pl.BlockSpec((1, block_q, d), q_map),
          pl.BlockSpec((1, block_k, d), k_map),
          pl.BlockSpec((1, block_k, dv), k_map),
          pl.BlockSpec((1, block_q, dv), q_map),
          pl.BlockSpec((1, 1, block_q, 1), row_map),
          pl.BlockSpec((1, 1, block_q, 1), row_map),
      ],
      out_specs=[
          pl.BlockSpec((1, t, d),
                       lambda g, head, j, s: (g * group + head, 0, 0)),
          pl.BlockSpec((1, kv_rows, d), kv_map),
          pl.BlockSpec((1, kv_rows, dv), kv_map),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((b * h, t, d), q_f.dtype),
          jax.ShapeDtypeStruct((b * kv, t, d), k_f.dtype),
          jax.ShapeDtypeStruct((b * kv, t, dv), v_f.dtype),
      ],
      scratch_shapes=[
          pltpu.VMEM((t, d), jnp.float32),         # dq accumulator
          pltpu.VMEM((kv_rows, d), jnp.float32),   # dk accumulator
          pltpu.VMEM((kv_rows, dv), jnp.float32),  # dv accumulator
      ],
      compiler_params=_COMPILER_PARAMS,
      interpret=interpret,
  )(q_f, k_f, v_f, do_f, lse, delta)


def _paired_backward(q_f, k_f, v_f, do_f, lse, delta, b: int,
                     causal: bool, block_q: int, block_k: int,
                     interpret: bool, window):
  """(dq, dk, dv), folded, by `_dkdv_kernel` and `_dq_kernel`. The pair
  survives as the fused program's oracle (the bitwise tests) and as the
  path of a sequence whose accumulators the fused program cannot hold
  (65,536 positions at D=128): no benchmark cell and no trunk of this
  repo runs it."""
  (bh, t, d), dv = q_f.shape, v_f.shape[-1]
  h, kv = bh // b, k_f.shape[0] // b
  group, scale = h // kv, 1.0 / np.sqrt(d)
  nq, nk = t // block_q, t // block_k
  # Blocks visited along the inner dimension: all of them, or a band's.
  visited = None if window is None else band_blocks(window, block_k)
  nq_in, nk_in = (nq, nk) if window is None else (visited, visited)

  # The dkdv grid's rows are key-value heads; step `s` of its inner
  # dimension is query head `s // nq_in` of the group, at the
  # `s % nq_in`-th query block visited.
  if group == 1 and window is None:
    def q_row(g, j, s):
      return g, s
  else:
    def q_row(g, j, s):
      head = g if group == 1 else g * group + s // nq_in
      block = s if group == 1 else s % nq_in
      if window is not None:
        block = jnp.minimum(_band_row(j, block), nq - 1)
      return head, block

  def q_map(g, j, s):
    return (*q_row(g, j, s), 0)

  def row_map(g, j, s):
    return (*q_row(g, j, s), 0, 0)

  dk_f, dv_f = pl.pallas_call(
      functools.partial(_dkdv_kernel, scale=scale, causal=causal,
                        block_q=block_q, block_k=block_k,
                        num_q_blocks=nq_in, window=window, group=group,
                        total_q_blocks=nq),
      grid=(b * kv, nk, group * nq_in),
      in_specs=[
          pl.BlockSpec((1, block_q, d), q_map),
          pl.BlockSpec((1, block_k, d), lambda g, j, i: (g, j, 0)),
          pl.BlockSpec((1, block_k, dv), lambda g, j, i: (g, j, 0)),
          pl.BlockSpec((1, block_q, dv), q_map),
          pl.BlockSpec((1, 1, block_q, 1), row_map),
          pl.BlockSpec((1, 1, block_q, 1), row_map),
      ],
      out_specs=[
          pl.BlockSpec((1, block_k, d), lambda g, j, i: (g, j, 0)),
          pl.BlockSpec((1, block_k, dv), lambda g, j, i: (g, j, 0)),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((b * kv, t, d), k_f.dtype),
          jax.ShapeDtypeStruct((b * kv, t, dv), v_f.dtype),
      ],
      scratch_shapes=[
          pltpu.VMEM((block_k, d), jnp.float32),   # dk accumulator
          pltpu.VMEM((block_k, dv), jnp.float32),  # dv accumulator
      ],
      compiler_params=_COMPILER_PARAMS,
      interpret=interpret,
  )(q_f, k_f, v_f, do_f, lse, delta)

  if window is None:
    def kv_map(g, i, j):
      return (_kv_index(g, group), j, 0)
  else:
    def kv_map(g, i, j):
      return (_kv_index(g, group),
              jnp.maximum(_band_col(i, j, nk_in), 0), 0)

  dq_f = pl.pallas_call(
      functools.partial(_dq_kernel, scale=scale, causal=causal,
                        block_q=block_q, block_k=block_k,
                        num_k_blocks=nk_in, window=window),
      grid=(b * h, nq, nk_in),
      in_specs=[
          pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
          pl.BlockSpec((1, block_k, d), kv_map),
          pl.BlockSpec((1, block_k, dv), kv_map),
          pl.BlockSpec((1, block_q, dv), lambda g, i, j: (g, i, 0)),
          pl.BlockSpec((1, 1, block_q, 1),
                       lambda g, i, j: (g, i, 0, 0)),
          pl.BlockSpec((1, 1, block_q, 1),
                       lambda g, i, j: (g, i, 0, 0)),
      ],
      out_specs=[
          pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
      ],
      out_shape=[jax.ShapeDtypeStruct((b * h, t, d), q_f.dtype)],
      scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
      compiler_params=_COMPILER_PARAMS,
      interpret=interpret,
  )(q_f, k_f, v_f, do_f, lse, delta)[0]
  return dq_f, dk_f, dv_f


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, block_q, block_k, interpret, window):
  return _flash_forward_impl(q, k, v, causal, block_q, block_k,
                             interpret, window)


def _flash_lse_fwd(q, k, v, causal, block_q, block_k, interpret, window):
  out, lse = _flash_forward_impl(q, k, v, causal, block_q, block_k,
                                 interpret, window)
  # Named HERE, where they become residuals, and both: a
  # `jax.checkpoint` whose policy saves `SAVED_RESIDUAL_NAMES` then
  # hands the backward these two arrays and does not run the forward
  # kernel again; one left unnamed would force the re-run. The lse in
  # its [B*H, T] form (the kernel's [..., block_q, 1] pads 128-fold
  # in HBM). Identities under any other policy and outside a checkpoint.
  out = checkpoint_name(out, SAVED_RESIDUAL_NAMES[0])
  lse = checkpoint_name(lse, SAVED_RESIDUAL_NAMES[1])
  return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(causal, block_q, block_k, interpret, window,
                   residuals, cotangents):
  q, k, v, out, lse = residuals
  do, dlse = cotangents
  return _flash_bwd_impl(q, k, v, out, lse, do, dlse, causal, block_q,
                         block_k, interpret, window)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _blocks_and_window(q, k, causal: bool, block_q: int, block_k: int,
                       window: Optional[int]):
  """(block_q, block_k, window) as the programs take them. A window
  needs `causal`; one that holds the whole sequence is no window, and
  lowers to the causal programs; under a narrower one both blocks are
  `window_tiling`'s."""
  t = q.shape[1]
  if q.shape[2] % k.shape[2]:
    raise ValueError(f"{q.shape[2]} query heads over {k.shape[2]} "
                     "key-value heads: not a whole group each")
  if window is not None:
    if not causal:
      raise ValueError("a window is a band under the causal mask: "
                       "pass causal=True")
    if window < 1:
      raise ValueError(f"window {window}: a query sees itself at least")
    if window >= t:
      window = None
  if window is None:
    return _auto_block(block_q, t), _auto_block(block_k, t), None
  block = _window_block(block_q, block_k, window, t)
  return block, block, window


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k",
                              "interpret", "window"))
def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = 1024,
    block_k: int = 2048,
    interpret: bool = False,
    window: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
  """Like `flash_attention` but also returns the logsumexp.

  Returns (out [B, T, H, Dv], lse [B, H, T]). The lse makes attention
  COMPOSABLE: partial attentions over disjoint key sets combine
  exactly as out = Σ_s softmax_s(lse_s) · out_s — which is how ring
  attention runs this kernel per device and merges blocks arriving
  over the ICI ring. Differentiable in BOTH outputs: the custom VJP
  folds the lse cotangent into the softmax-jacobian diagonal
  (∂lse/∂s = p), so `jax.grad` through an lse-weighted combine — the
  ring's merge — is exact.
  """
  b, t, h, d = q.shape
  block_q, block_k, window = _blocks_and_window(q, k, causal, block_q,
                                                block_k, window)
  out, lse = _flash_lse(q, k, v, causal, block_q, block_k, interpret,
                        window)
  return out, lse.reshape(b, h, t)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k",
                              "interpret", "window"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = 1024,
    block_k: int = 2048,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
  """Exact attention, O(T) memory both ways. q [B, T, H, Dk], k
  [B, T, KV, Dk], v [B, T, KV, Dv] → [B, T, H, Dv]; the scale is
  Dk^-1/2. The two widths are read off the arguments and need not be
  equal (latent attention: keys of 192 over values of 128): every
  tile, scratch and result has its own, so P·V, dO·Vᵀ and dV are Dv
  wide and no value is padded to the keys' width. KV divides H: query
  head h attends over key-value head h // (H / KV), read where it
  lies; dk and dv come back KV heads wide, a group's sum made in
  float32.

  With `causal` and `window`, query i sees key j iff 0 <= i - j <
  window (itself and the window - 1 before it). The grids then walk
  the band's tiles alone (`window_tiling`): both blocks are the
  largest power of two that is at most the window, the requests and
  divides T, and a row of tiles visits 1 + ceil((window - 1) / block)
  key blocks, whatever T is. A window of T or more is `causal`.

  Block sizes auto-shrink to divide T (`_auto_block`), so any static
  T works; power-of-two T keeps the large overhead-amortizing blocks.
  Differentiable via the flash custom VJP (logsumexp residual +
  blockwise Pallas recompute); shares `_flash_lse`'s backward — the
  dropped lse output contributes a zero cotangent, so there is exactly
  ONE backward implementation to keep correct.
  """
  block_q, block_k, window = _blocks_and_window(q, k, causal, block_q,
                                                block_k, window)
  out, _ = _flash_lse(q, k, v, causal, block_q, block_k, interpret,
                      window)
  return out
