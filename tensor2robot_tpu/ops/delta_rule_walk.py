"""Pallas TPU kernels for the gated delta rule's walk over chunks.

`layers/gated_delta.gated_delta_rule` prepares every chunk's operands
in large products and then walks the chunks in order, carrying one
float32 state [Dk, Dv] a head:

  new_n     = writes_n - k_decayed_n @ S_n
  carried_n = q_decayed_n @ S_n
  S_{n+1}   = end_decay_n * S_n + k_to_end_n^T @ new_n        S_0 = 0

As a `lax.scan` that is eight XLA operations an iteration, some 2 us
each on a v5e, and the state goes through HBM every chunk: 100 ms of
the language model's 1.83 s step (PERF.md section 5, PR 35). Here the
walk is one kernel each way, bound by the HBM traffic of its operands.

Forward, grid (blocks of heads, chunks), the chunk axis sequential: a
block of heads' states stays in VMEM scratch for the whole walk (64 KB
a head at 128 x 128, zeroed at chunk 0); a grid step reads the chunk's
four [C, D] tiles of each head through its BlockSpecs, runs the three
products with the operands' dtype in and float32 out, exactly as the
scan's `mm` does, and writes `new`, `carried` and the state at the
chunk's START: what the backward pass needs, and what the scan's
autodiff keeps too (N x H x Dk x Dv float32).

Backward, the same grid walked from the last chunk to the first with
the state's cotangent in VMEM scratch: from d new, d carried, the
saved states and `new` it gives the cotangents of the five inputs in
six products a head and chunk; d end_decay is a sum over S * dS.
Cotangents enter a product rounded to the operands' dtype, as XLA's
default precision rounds them in the scan's transpose.

`end_decay` is one scalar a head and chunk. It enters, and its
cotangent leaves, as a row of Dv lanes ([N, G, 1, Dv]): a [1, Dv] tile
broadcasts against the state's sublanes with no scalar memory, and the
lane sum of the cotangent is XLA's.

Or it is a vector over the key channels ([N, G, Dk]: the state's ROWS
are scaled, `layers/gated_delta.py`'s decay per channel). A row of Dk
lanes scales columns, not rows, so these calls keep the state
transposed, [Dv, Dk], in scratch and in the saved states: the row
[1, Dk] then broadcasts against its sublanes as the scalar's does, the
cotangent is a sublane sum that leaves as [1, Dk], and the six
products are the same with the state's two axes exchanged in their
dimension numbers. One kernel body each way; which layout a call has
is read off `end_decay`'s rank, and the scalar's calls trace the
operations they always have.

`tiles()` says whether shapes are ones Mosaic takes; which path runs is
`gated_delta_rule`'s to read off its input. `interpret=True` runs both
kernels in the Pallas interpreter: how the CPU tests check them.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Heads a grid step takes. A step costs some 0.35 us whatever it does;
# a head and chunk moves 208 KB forward and 288 KB backward (bfloat16,
# 64 x 128 tiles, a 128 x 128 state): 0.25 and 0.35 us of HBM time. A
# few heads a step share the fixed cost and let one head's products
# run under another's loads and stores: 1, 2, 4, 8, 16 heads took 5.72,
# 4.11, 3.33, 3.26, 3.25 ms forward and backward at 32 heads and 128
# chunks (a v5e, PR 35). Double-buffered blocks of 8 heads are 3.3 MB
# forward and 4.6 MB backward of VMEM.
_HEAD_BLOCK = 8
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def tiles(chunk: int, dk: int, dv: int, dtype) -> bool:
  """Whether Mosaic tiles a [chunk, dk] operand of `dtype` and a
  [dk, dv] float32 state: lanes in 128s, sublanes in the dtype's tile
  (8 rows of 32 bits, 16 of 16)."""
  sublanes = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
  return dk % 128 == 0 and dv % 128 == 0 and chunk % sublanes == 0


def head_block(heads: int) -> int:
  """The largest divisor of `heads` up to `_HEAD_BLOCK`."""
  return max(d for d in range(1, _HEAD_BLOCK + 1) if heads % d == 0)


def _dot(x, y, contract):
  return jax.lax.dot_general(x, y, (contract, ((), ())),
                             preferred_element_type=jnp.float32)


_NN = ((1,), (0,))  # x @ y
_NT = ((1,), (1,))  # x @ y^T
_TN = ((0,), (0,))  # x^T @ y


# The products against the state S [Dk, Dv], which a call whose
# `end_decay` is a vector holds as S^T (`transposed`).
def _through(x, state, transposed):  # x S: [C, Dk] -> [C, Dv]
  return _dot(x, state, _NT if transposed else _NN)


def _back_through(x, state, transposed):  # x S^T: [C, Dv] -> [C, Dk]
  return _dot(x, state, _NN if transposed else _NT)


def _outer(x, y, transposed):  # x^T y: [C, Dk], [C, Dv] -> the state's
  return _dot(y, x, _TN) if transposed else _dot(x, y, _TN)


def _forward_kernel(writes_ref, k_decayed_ref, q_decayed_ref,
                    k_to_end_ref, end_decay_ref, new_ref, carried_ref,
                    *states_ref_and_scratch, heads: int,
                    transposed: bool):
  """One chunk of `heads` heads. Refs [1, heads, C, D] (end_decay
  [1, heads, 1, Dv], states [1, heads, Dk, Dv], there only where a
  backward pass will read them); `state_scr` [heads, Dk, Dv] float32
  lives across the chunk axis. `transposed`: end_decay [1, heads, 1,
  Dk], the states and the scratch [.., Dv, Dk]."""
  *states_ref, state_scr = states_ref_and_scratch

  @pl.when(pl.program_id(1) == 0)
  def _start():
    state_scr[...] = jnp.zeros_like(state_scr)

  dtype = k_decayed_ref.dtype
  for h in range(heads):
    state = state_scr[h]
    for ref in states_ref:
      ref[0, h] = state
    operand = state.astype(dtype)
    new = writes_ref[0, h] - _through(k_decayed_ref[0, h], operand,
                                      transposed)
    new_ref[0, h] = new
    carried_ref[0, h] = _through(q_decayed_ref[0, h], operand,
                                 transposed)
    state_scr[h] = (state * end_decay_ref[0, h]
                    + _outer(k_to_end_ref[0, h], new.astype(dtype),
                             transposed))


def _backward_kernel(k_decayed_ref, q_decayed_ref, k_to_end_ref,
                     end_decay_ref, new_ref, states_ref, d_new_ref,
                     d_carried_ref, d_writes_ref, d_k_decayed_ref,
                     d_q_decayed_ref, d_k_to_end_ref, d_end_decay_ref,
                     d_state_scr, *, heads: int, transposed: bool):
  """The chunk's transpose; the grid's chunk axis runs from the last
  chunk to the first (the index maps reverse it). `d_state_scr` holds
  the cotangent of the state at the chunk's END on entry and of the
  state at its start on exit."""

  @pl.when(pl.program_id(1) == 0)
  def _start():
    d_state_scr[...] = jnp.zeros_like(d_state_scr)

  dtype = k_decayed_ref.dtype
  for h in range(heads):
    d_end = d_state_scr[h]
    d_end_operand = d_end.astype(dtype)
    state = states_ref[0, h]
    operand = state.astype(dtype)
    d_new = d_new_ref[0, h] + _through(k_to_end_ref[0, h],
                                       d_end_operand, transposed)
    d_writes_ref[0, h] = d_new
    d_new = d_new.astype(dtype)
    d_carried = d_carried_ref[0, h].astype(dtype)
    d_k_decayed_ref[0, h] = (-_back_through(d_new, operand, transposed)
                             ).astype(dtype)
    d_q_decayed_ref[0, h] = _back_through(d_carried, operand, transposed
                                          ).astype(dtype)
    d_k_to_end_ref[0, h] = _back_through(
        new_ref[0, h].astype(dtype), d_end_operand, transposed
    ).astype(dtype)
    # Over the state's sublanes: all of it for a scalar (XLA sums the
    # lanes), the value axis for a vector, whose state is transposed.
    d_end_decay_ref[0, h] = jnp.sum(state * d_end, axis=0,
                                    keepdims=True)
    d_state_scr[h] = (d_end * end_decay_ref[0, h]
                      + _outer(q_decayed_ref[0, h], d_carried, transposed)
                      - _outer(k_decayed_ref[0, h], d_new, transposed))


def _fold(x):  # [N, B, H, ...] -> [N, B * H, ...]
  return x.reshape((x.shape[0], x.shape[1] * x.shape[2]) + x.shape[3:])


def _lanes(x, dv):  # [N, G] -> [N, G, 1, Dv]; [N, G, Dk] -> [N, G, 1, Dk]
  if x.ndim == 3:
    return x[:, :, None, :]
  return jnp.broadcast_to(x[..., None, None], x.shape + (1, dv))


def _state_layout(end_decay, dk, dv):
  """(whether the call holds the state transposed, the state's shape):
  read off `end_decay`'s rank, [N, G] or [N, G, Dk]."""
  transposed = end_decay.ndim == 3
  return transposed, (dv, dk) if transposed else (dk, dv)


def _forward(writes, k_decayed, q_decayed, k_to_end, end_decay, block,
             interpret, save_states):
  """(new, carried), and the states [N, G, Dk, Dv] after them where
  `save_states`."""
  n, g, chunk, dv = writes.shape
  dk = k_decayed.shape[-1]
  transposed, state = _state_layout(end_decay, dk, dv)
  spec = lambda *tile: pl.BlockSpec(  # noqa: E731
      (1, block) + tile, lambda j, i: (i, j, 0, 0))
  return pl.pallas_call(
      functools.partial(_forward_kernel, heads=block,
                        transposed=transposed),
      grid=(pl.cdiv(g, block), n),
      in_specs=[spec(chunk, dv), spec(chunk, dk), spec(chunk, dk),
                spec(chunk, dk), spec(1, state[1])],
      out_specs=[spec(chunk, dv), spec(chunk, dv)]
      + [spec(*state)] * save_states,
      out_shape=[jax.ShapeDtypeStruct((n, g, chunk, dv), jnp.float32)] * 2
      + [jax.ShapeDtypeStruct((n, g) + state, jnp.float32)] * save_states,
      scratch_shapes=[pltpu.VMEM((block,) + state, jnp.float32)],
      # `new` over `writes`: a step reads its tile before it writes it.
      input_output_aliases={0: 0},
      compiler_params=_COMPILER_PARAMS,
      interpret=interpret,
  )(writes, k_decayed, q_decayed, k_to_end, _lanes(end_decay, dv))


def _backward(k_decayed, q_decayed, k_to_end, end_decay, new, states,
              d_new, d_carried, block, interpret):
  n, g, chunk, dv = new.shape
  dk = k_decayed.shape[-1]
  dtype = k_decayed.dtype
  transposed, state = _state_layout(end_decay, dk, dv)
  spec = lambda *tile: pl.BlockSpec(  # noqa: E731
      (1, block) + tile, lambda j, i: (n - 1 - i, j, 0, 0))
  *cotangents, d_end_decay = pl.pallas_call(
      functools.partial(_backward_kernel, heads=block,
                        transposed=transposed),
      grid=(pl.cdiv(g, block), n),
      in_specs=[spec(chunk, dk), spec(chunk, dk), spec(chunk, dk),
                spec(1, state[1]), spec(chunk, dv), spec(*state),
                spec(chunk, dv), spec(chunk, dv)],
      out_specs=[spec(chunk, dv), spec(chunk, dk), spec(chunk, dk),
                 spec(chunk, dk), spec(1, state[1])],
      out_shape=[
          jax.ShapeDtypeStruct((n, g, chunk, dv), jnp.float32),
          jax.ShapeDtypeStruct((n, g, chunk, dk), dtype),
          jax.ShapeDtypeStruct((n, g, chunk, dk), dtype),
          jax.ShapeDtypeStruct((n, g, chunk, dk), dtype),
          jax.ShapeDtypeStruct((n, g, 1, state[1]), jnp.float32),
      ],
      scratch_shapes=[pltpu.VMEM((block,) + state, jnp.float32)],
      # d `writes` over d `new`, likewise.
      input_output_aliases={6: 0},
      compiler_params=_COMPILER_PARAMS,
      interpret=interpret,
  )(k_decayed, q_decayed, k_to_end, _lanes(end_decay, dv), new, states,
    d_new, d_carried)
  if transposed:
    return (*cotangents, d_end_decay[:, :, 0, :])
  return (*cotangents, jnp.sum(d_end_decay, axis=(-2, -1)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _walk(writes, k_decayed, q_decayed, k_to_end, end_decay, block,
          interpret):
  return tuple(_forward(writes, k_decayed, q_decayed, k_to_end,
                        end_decay, block, interpret, save_states=False))


def _walk_fwd(writes, k_decayed, q_decayed, k_to_end, end_decay, block,
              interpret):
  new, carried, states = _forward(writes, k_decayed, q_decayed,
                                  k_to_end, end_decay, block, interpret,
                                  save_states=True)
  return (new, carried), (k_decayed, q_decayed, k_to_end, end_decay,
                          new, states)


def _walk_bwd(block, interpret, residuals, cotangents):
  return _backward(*residuals, *cotangents, block, interpret)


# `optimize_remat`: under `jax.checkpoint` a forward pass that no
# backward pass follows (the rule has two of three such a step) runs
# `_walk` and not `_walk_fwd`, and writes no states.
_walk.defvjp(_walk_fwd, _walk_bwd, optimize_remat=True)


def walk(writes: jax.Array, k_decayed: jax.Array, q_decayed: jax.Array,
         k_to_end: jax.Array, end_decay: jax.Array, *,
         block: int | None = None, interpret: bool = False
         ) -> Tuple[jax.Array, jax.Array]:
  """The walk over chunks, with the scan's own signature: `writes`
  [N, B, H, C, Dv] float32; `k_decayed`, `q_decayed`, `k_to_end`
  [N, B, H, C, Dk] in the products' dtype; `end_decay` [N, B, H]
  float32, or [N, B, H, Dk] (a decay for every row of the state).
  Returns (`new`, `carried`), [N, B, H, C, Dv] float32.
  Differentiable in all five. `block` heads a grid step (of the B * H
  the call has; it need not divide them); left out, `head_block`'s."""
  shape = writes.shape
  args = [_fold(x) for x in (writes.astype(jnp.float32), k_decayed,
                             q_decayed, k_to_end,
                             end_decay.astype(jnp.float32))]
  heads = args[0].shape[1]
  block = head_block(heads) if block is None else min(block, heads)
  new, carried = _walk(*args, block, interpret)
  return new.reshape(shape), carried.reshape(shape)
