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
61.8; blocks of (2048, 2048) double the backward pair (91 ms).

Training works end to end, and the backward is Pallas too: two kernels
in the standard flash-backward formulation, each recomputing score
tiles from q/k + the saved logsumexp — `_dkdv_kernel` accumulates
dk/dv per K-block over the Q grid, `_dq_kernel` accumulates dq per
Q-block over the K grid. The softmax-jacobian row term
D_i = rowsum(dO·O) (minus any lse cotangent) is a cheap XLA
elementwise reduce computed once outside. No [T, T] tensor exists in
either direction; the tri-regime causal tiling applies to both
directions. The backward kernels have no sequential max/rescale
chain, so their five matmuls per tile pair keep the MXU busier than
the forward's two.

Pairs with `parallel/ring_attention.py`: the ring shards the sequence
ACROSS chips (ppermute over ICI), this kernel tiles it WITHIN a chip;
both implement the same online-softmax math.

`flash_attention(..., interpret=True)` runs the kernels (forward AND
backward) in the pallas interpreter — how the CPU test suite verifies
numerics without TPU hardware.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
        f"power-of-two divisor ≥ 8 (or T ≤ {requested}); pad T "
        "upstream — lengths are static in this framework.")
  return b


def _causal_tile_regimes(row_block, col_block, block_q: int,
                         block_k: int):
  """(not_future, fully_past) predicates for one causal score tile.

  Shared by all three kernels so forward and backward can never
  disagree on which tiles are masked:
    fully-future (not not_future): every col > every row — all-masked,
      skip the tile's compute entirely;
    fully_past: every col <= every row — mask is all-true, run the
      unmasked update (no iota/select work);
    otherwise the tile straddles the diagonal and pays for masking.
  """
  last_row = row_block * block_q + block_q - 1
  first_row = row_block * block_q
  first_col = col_block * block_k
  last_col = col_block * block_k + block_k - 1
  return first_col <= last_row, last_col <= first_row


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *, scale: float, causal: bool, block_q: int,
                  block_k: int, num_k_blocks: int):
  """Grid (batch*heads, T/block_q, T/block_k); innermost dim iterates
  K/V blocks sequentially (TPU grids are loops), accumulating into
  VMEM scratch; the last K step normalizes, writes the output and the
  logsumexp (the backward's residual)."""
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

  def _update_impl(use_mask):
    q = q_ref[0]  # [block_q, D]
    k = k_ref[0]  # [block_k, D]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [bq, bk]
    if use_mask:
      rows = i * block_q + jax.lax.broadcasted_iota(
          jnp.int32, (block_q, block_k), 0)
      cols = j * block_k + jax.lax.broadcasted_iota(
          jnp.int32, (block_q, block_k), 1)
      mask = cols <= rows
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

  if causal:
    # Tri-regime causal tiling (see _causal_tile_regimes): at T=32k
    # with bq=1024/bk=2048 only ~1 straddling block per q row pays
    # for the mask iotas + selects; fully-future tiles (half the
    # grid) skip all compute. (`fully_past` implies `not_future`,
    # but the conjunction keeps the two pl.when predicates visibly
    # disjoint-and-exhaustive over the not-future half.)
    not_future, fully_past = _causal_tile_regimes(
        i, j, block_q, block_k)
    pl.when(not_future & fully_past)(lambda: _update_impl(False))
    pl.when(not_future & jnp.logical_not(fully_past))(
        lambda: _update_impl(True))
  else:
    _update_impl(False)

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


def _flash_forward_impl(q, k, v, causal: bool, block_q: int,
                        block_k: int, interpret: bool
                        ) -> Tuple[jax.Array, jax.Array]:
  """Runs the kernel; returns (out [B,T,H,Dv], lse [B*H, T]). q and
  k are `d` wide, v and the output `dv`: the two need not be equal."""
  b, t, h, d = q.shape
  dv = v.shape[-1]
  num_q_blocks = t // block_q
  num_k_blocks = t // block_k
  scale = 1.0 / np.sqrt(d)

  # [B, T, H, D] -> [B*H, T, D]: one grid row per (batch, head).
  def fold(x):
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[-1])

  kernel = functools.partial(
      _flash_kernel, scale=scale, causal=causal, block_q=block_q,
      block_k=block_k, num_k_blocks=num_k_blocks)
  out, lse = pl.pallas_call(
      kernel,
      grid=(b * h, num_q_blocks, num_k_blocks),
      in_specs=[
          pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
          pl.BlockSpec((1, block_k, d), lambda g, i, j: (g, j, 0)),
          pl.BlockSpec((1, block_k, dv), lambda g, i, j: (g, j, 0)),
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
  )(fold(q), fold(k), fold(v))
  return (out.reshape(b, h, t, dv).transpose(0, 2, 1, 3),
          lse.reshape(b * h, t))


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                 causal: bool, block_q: int, block_k: int,
                 num_q_blocks: int):
  """Grid (B*H, T/block_k, T/block_q); the innermost dim iterates Q
  blocks sequentially, accumulating this K-block's dk/dv in VMEM
  scratch from recomputed p = exp(s − lse) tiles; the last Q step
  writes out."""
  j = pl.program_id(1)
  qi = pl.program_id(2)

  @pl.when(qi == 0)
  def _init():
    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)

  def _update_impl(use_mask):
    q = q_ref[0]                                   # [bq, D]
    k = k_ref[0]                                   # [bk, D]
    v = v_ref[0]
    do = do_ref[0]                                 # [bq, D]
    # lse/delta arrive sublane-major [bq, 1] — already the layout the
    # row-wise broadcasts against score tiles need; no relayout.
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [bq, bk]
    if use_mask:
      rows = qi * block_q + jax.lax.broadcasted_iota(
          jnp.int32, (block_q, block_k), 0)
      cols = j * block_k + jax.lax.broadcasted_iota(
          jnp.int32, (block_q, block_k), 1)
      mask = cols <= rows
      s = jnp.where(mask, s, _NEG_INF)
    p = jnp.exp(s - lse)
    if use_mask:
      p = jnp.where(mask, p, 0.0)
    # dv += pᵀ·dO. p/ds cast to the input dtype for the MXU matmul
    # (f32 accumulation via preferred_element_type) — the standard
    # flash-backward precision contract, bit-exact in f32 tests.
    dv_scr[...] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # [bq, bk]
    ds = p * (dp - delta) * scale
    dk_scr[...] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

  if causal:
    # Same tri-regime tiling as the forward (shared predicates).
    not_future, fully_past = _causal_tile_regimes(
        qi, j, block_q, block_k)
    pl.when(not_future & fully_past)(
        lambda: _update_impl(False))
    pl.when(not_future & jnp.logical_not(fully_past))(
        lambda: _update_impl(True))
  else:
    _update_impl(False)

  @pl.when(qi == num_q_blocks - 1)
  def _finalize():
    dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *, scale: float, causal: bool,
               block_q: int, block_k: int, num_k_blocks: int):
  """Grid (B*H, T/block_q, T/block_k); innermost iterates K blocks,
  accumulating this Q-block's dq = Σ_j ds_j·k_j in VMEM scratch."""
  i = pl.program_id(1)
  kj = pl.program_id(2)

  @pl.when(kj == 0)
  def _init():
    dq_scr[...] = jnp.zeros_like(dq_scr)

  def _update_impl(use_mask):
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0]       # sublane-major [bq, 1], see _dkdv_kernel
    delta = delta_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if use_mask:
      rows = i * block_q + jax.lax.broadcasted_iota(
          jnp.int32, (block_q, block_k), 0)
      cols = kj * block_k + jax.lax.broadcasted_iota(
          jnp.int32, (block_q, block_k), 1)
      mask = cols <= rows
      s = jnp.where(mask, s, _NEG_INF)
    p = jnp.exp(s - lse)
    if use_mask:
      p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dq_scr[...] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

  if causal:
    # Same tri-regime tiling as the forward (shared predicates).
    not_future, fully_past = _causal_tile_regimes(
        i, kj, block_q, block_k)
    pl.when(not_future & fully_past)(
        lambda: _update_impl(False))
    pl.when(not_future & jnp.logical_not(fully_past))(
        lambda: _update_impl(True))
  else:
    _update_impl(False)

  @pl.when(kj == num_k_blocks - 1)
  def _finalize():
    dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_impl(q, k, v, out, lse, do, dlse, causal: bool,
                    block_q: int, block_k: int, interpret: bool):
  """Pallas flash backward: dkdv kernel + dq kernel.

  `dlse` ([BH, T]) is the cotangent of the logsumexp output — zeros
  when the caller only used `out`: since ∂lse_i/∂s_ij = p_ij, it
  folds into the softmax-jacobian diagonal as ds = p·(dp − (δ − g)) —
  one subtraction in the precomputed per-row term, which is what makes
  the lse-composed ring attention trainable through this kernel.
  """
  b, t, h, d = q.shape
  dv = v.shape[-1]  # v, out, do and dv are this wide; q, k, dq, dk `d`
  scale = 1.0 / np.sqrt(d)
  nq, nk = t // block_q, t // block_k

  def fold(x):  # [B, T, H, D] -> [B*H, T, D]
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[-1])

  q_f, k_f, v_f, do_f, o_f = map(fold, (q, k, v, do, out))
  # δ_i = rowsum(dO·O) − dlse_i: the softmax-jacobian row term, a
  # cheap elementwise reduce XLA fuses. Both per-row vectors enter
  # the kernels in the forward's SUBLANE-major [BH, nq, block_q, 1]
  # layout — the broadcast layout the score-tile math needs, so
  # neither side pays an MXU relayout (rounds 4-5 made two lossy
  # systolic-array passes here — forward identity-transpose, backward
  # 1/8-contraction — which was the dominant term in the hardware
  # gate's dv error; see bench_verify_numerics).
  delta = (jnp.sum(do_f.astype(jnp.float32) * o_f.astype(jnp.float32),
                   axis=-1)
           - dlse.astype(jnp.float32))              # [BH, T]

  def tile_cols(x):  # [BH, T] → [BH, nq, block_q, 1]
    return x.astype(jnp.float32).reshape(b * h, nq, block_q, 1)

  lse = tile_cols(lse)
  delta = tile_cols(delta)

  dk_f, dv_f = pl.pallas_call(
      functools.partial(_dkdv_kernel, scale=scale, causal=causal,
                        block_q=block_q, block_k=block_k,
                        num_q_blocks=nq),
      grid=(b * h, nk, nq),
      in_specs=[
          pl.BlockSpec((1, block_q, d), lambda g, j, i: (g, i, 0)),
          pl.BlockSpec((1, block_k, d), lambda g, j, i: (g, j, 0)),
          pl.BlockSpec((1, block_k, dv), lambda g, j, i: (g, j, 0)),
          pl.BlockSpec((1, block_q, dv), lambda g, j, i: (g, i, 0)),
          pl.BlockSpec((1, 1, block_q, 1),
                       lambda g, j, i: (g, i, 0, 0)),
          pl.BlockSpec((1, 1, block_q, 1),
                       lambda g, j, i: (g, i, 0, 0)),
      ],
      out_specs=[
          pl.BlockSpec((1, block_k, d), lambda g, j, i: (g, j, 0)),
          pl.BlockSpec((1, block_k, dv), lambda g, j, i: (g, j, 0)),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((b * h, t, d), k.dtype),
          jax.ShapeDtypeStruct((b * h, t, dv), v.dtype),
      ],
      scratch_shapes=[
          pltpu.VMEM((block_k, d), jnp.float32),   # dk accumulator
          pltpu.VMEM((block_k, dv), jnp.float32),  # dv accumulator
      ],
      compiler_params=_COMPILER_PARAMS,
      interpret=interpret,
  )(q_f, k_f, v_f, do_f, lse, delta)

  dq_f = pl.pallas_call(
      functools.partial(_dq_kernel, scale=scale, causal=causal,
                        block_q=block_q, block_k=block_k,
                        num_k_blocks=nk),
      grid=(b * h, nq, nk),
      in_specs=[
          pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
          pl.BlockSpec((1, block_k, d), lambda g, i, j: (g, j, 0)),
          pl.BlockSpec((1, block_k, dv), lambda g, i, j: (g, j, 0)),
          pl.BlockSpec((1, block_q, dv), lambda g, i, j: (g, i, 0)),
          pl.BlockSpec((1, 1, block_q, 1),
                       lambda g, i, j: (g, i, 0, 0)),
          pl.BlockSpec((1, 1, block_q, 1),
                       lambda g, i, j: (g, i, 0, 0)),
      ],
      out_specs=[
          pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
      ],
      out_shape=[jax.ShapeDtypeStruct((b * h, t, d), q.dtype)],
      scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
      compiler_params=_COMPILER_PARAMS,
      interpret=interpret,
  )(q_f, k_f, v_f, do_f, lse, delta)[0]

  def unfold(x):  # [BH, T, D] -> [B, T, H, D]
    return x.reshape(b, h, t, x.shape[-1]).transpose(0, 2, 1, 3)

  return unfold(dq_f), unfold(dk_f), unfold(dv_f)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse(q, k, v, causal, block_q, block_k, interpret):
  return _flash_forward_impl(q, k, v, causal, block_q, block_k,
                             interpret)


def _flash_lse_fwd(q, k, v, causal, block_q, block_k, interpret):
  out, lse = _flash_forward_impl(q, k, v, causal, block_q, block_k,
                                 interpret)
  # Named HERE, where they become residuals, and both: a
  # `jax.checkpoint` whose policy saves `SAVED_RESIDUAL_NAMES` then
  # hands the backward these two arrays and does not run the forward
  # kernel again; one left unnamed would force the re-run. The lse in
  # its [B*H, T] form (the kernel's [..., block_q, 1] pads 128-fold
  # in HBM). Identities under any other policy and outside a checkpoint.
  out = checkpoint_name(out, SAVED_RESIDUAL_NAMES[0])
  lse = checkpoint_name(lse, SAVED_RESIDUAL_NAMES[1])
  return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(causal, block_q, block_k, interpret, residuals,
                   cotangents):
  q, k, v, out, lse = residuals
  do, dlse = cotangents
  return _flash_bwd_impl(q, k, v, out, lse, do, dlse, causal, block_q,
                         block_k, interpret)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k",
                              "interpret"))
def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = 1024,
    block_k: int = 2048,
    interpret: bool = False,
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
  block_q = _auto_block(block_q, t)
  block_k = _auto_block(block_k, t)
  out, lse = _flash_lse(q, k, v, causal, block_q, block_k, interpret)
  return out, lse.reshape(b, h, t)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k",
                              "interpret"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = 1024,
    block_k: int = 2048,
    interpret: bool = False,
) -> jax.Array:
  """Exact attention, O(T) memory both ways. q, k [B, T, H, Dk],
  v [B, T, H, Dv] → [B, T, H, Dv]; the scale is Dk^-1/2. The two
  widths are read off the arguments and need not be equal (latent
  attention: keys of 192 over values of 128): every tile, scratch and
  result has its own, so P·V, dO·Vᵀ and dV are Dv wide and no value is
  padded to the keys' width.

  Block sizes auto-shrink to divide T (`_auto_block`), so any static
  T works; power-of-two T keeps the large overhead-amortizing blocks.
  Differentiable via the flash custom VJP (logsumexp residual +
  blockwise Pallas recompute); shares `_flash_lse`'s backward — the
  dropped lse output contributes a zero cotangent, so there is exactly
  ONE backward implementation to keep correct.
  """
  b, t, h, d = q.shape
  block_q = _auto_block(block_q, t)
  block_k = _auto_block(block_k, t)
  out, _ = _flash_lse(q, k, v, causal, block_q, block_k, interpret)
  return out
