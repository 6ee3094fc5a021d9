"""Ring attention: sequence/context parallelism over the `seq` mesh axis.

The reference never needed long-context — robot episodes are short
(SURVEY.md §3 parallelism table marks SP/CP "n/a for parity; design
mesh axes so it can be added"). The `seq` axis was reserved in
`parallel/mesh.py` for exactly this module: attention over sequences
too long for one chip's HBM, sharded on the time dimension.

Design (ring attention, Liu et al. 2023-style, built from JAX SPMD
primitives — no NCCL-ish backend to port):
  * q/k/v live sharded [B, T/P, H, D] per device over the `seq` axis
    (`shard_map` keeps XLA from trying to gather the full sequence).
  * Each device keeps its Q block resident and consumes K/V blocks as
    they rotate around the ring via `lax.ppermute` — P-1 neighbor
    exchanges over ICI, each overlapped with the block's attention
    math, never materializing the [T, T] score matrix or the full K/V.
  * Blocks combine with the flash-attention online softmax (running
    max/normalizer/accumulator in f32), so the result is EXACT
    attention, independent of P.
  * Causal masking uses global positions derived from
    `lax.axis_index` — block-diagonal triangular, fully-masked blocks
    contribute zero (guarded against -inf/0 NaNs).

`ring_attention` is the public entry: full [B, T, H, D] arrays in, the
shard_map + sharding plumbing handled here; it degrades to the exact
same math single-device, so models call one function everywhere.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tensor2robot_tpu.parallel.mesh import (
    DATA_AXIS,
    SEQ_AXIS,
    shard_map_compat,
)

_NEG_INF = -1e30  # finite sentinel: avoids -inf - -inf = nan paths


def attention_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = False,
                        window: Optional[int] = None) -> jax.Array:
  """Plain softmax attention (f32 accumulation), the exactness oracle.

  q, k [B, T, H, D], v [B, T, H, Dv] → [B, T, H, Dv]. With `causal`
  and `window`, query i sees key j iff 0 <= i - j < window.
  """
  scale = 1.0 / np.sqrt(q.shape[-1])
  s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                 k.astype(jnp.float32)) * scale
  if window is not None and not causal:
    raise ValueError("a window is a band under the causal mask")
  if causal:
    t = q.shape[1]
    behind = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    mask = behind >= 0
    if window is not None:
      mask &= behind < window
    s = jnp.where(mask[None, None], s, _NEG_INF)
  p = jax.nn.softmax(s, axis=-1)
  out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
  return out.astype(q.dtype)


def _block_attend(q, k, v, mask, m, l, o, scale):
  """One flash-style block update of the (m, l, o) running state.

  q [B, Tq, H, D]; k/v [B, Tk, H, D]; mask [Tq, Tk] bool or None;
  m/l [B, H, Tq]; o [B, H, Tq, D] (all f32).
  """
  s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                 k.astype(jnp.float32)) * scale
  if mask is not None:
    s = jnp.where(mask[None, None], s, _NEG_INF)
  m_new = jnp.maximum(m, s.max(axis=-1))
  # Fully-masked-so-far rows keep m at the sentinel; exp underflows to
  # 0 harmlessly because the sentinel is finite.
  p = jnp.exp(s - m_new[..., None])
  if mask is not None:
    p = jnp.where(mask[None, None], p, 0.0)
  alpha = jnp.exp(m - m_new)
  l_new = alpha * l + p.sum(axis=-1)
  o_new = (alpha[..., None] * o
           + jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32)))
  return m_new, l_new, o_new


def _ring_attention_local(q, k, v, axis_name: str, causal: bool):
  """Per-device body under shard_map: local Q, rotating K/V blocks."""
  ring_size = jax.lax.psum(1, axis_name)
  idx = jax.lax.axis_index(axis_name)
  batch, t_local, heads, dim = q.shape
  scale = 1.0 / np.sqrt(dim)
  rows = idx * t_local + jnp.arange(t_local)

  perm = [(j, (j - 1) % ring_size) for j in range(ring_size)]

  def step(carry, s):
    k_blk, v_blk, m, l, o = carry
    src = (idx + s) % ring_size
    mask = None
    if causal:
      cols = src * t_local + jnp.arange(t_local)
      mask = cols[None, :] <= rows[:, None]
    m, l, o = _block_attend(q, k_blk, v_blk, mask, m, l, o, scale)
    # Rotate: device j's block moves to j-1, so next step this device
    # holds the block that originated at idx + s + 1. The final
    # rotation returns K/V to their home devices (donation-friendly).
    k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
    v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
    return (k_blk, v_blk, m, l, o), ()

  init = (
      k, v,
      jnp.full((batch, heads, t_local), _NEG_INF, jnp.float32),
      jnp.zeros((batch, heads, t_local), jnp.float32),
      jnp.zeros((batch, heads, t_local, dim), jnp.float32),
  )
  (_, _, m, l, o), _ = jax.lax.scan(step, init,
                                    jnp.arange(ring_size))
  # Rows with zero mass (possible only under exotic masks) output 0.
  out = o / jnp.maximum(l[..., None], 1e-30)
  return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _ring_attention_local_flash(q, k, v, axis_name: str, causal: bool,
                                ring_size: int, interpret: bool):
  """Per-device ring body running the PALLAS kernel on each block.

  The composition insight: with the ring statically unrolled, step 0
  is exactly the causal DIAGONAL block (q and k are the same local
  slice, so the kernel's in-call causal mask is the right mask), and
  every later step is either fully attended (source block in the
  past) or fully excluded (future) — a per-device SCALAR decision,
  so excluded steps skip the kernel entirely under `lax.cond`
  (halving the causal per-device FLOPs) and contribute lse = -inf.
  Partial outputs combine exactly via their logsumexps; because the
  kernel's lse output is differentiable, `jax.grad` flows through
  the whole ring (cond branches, ppermute rotations and the
  softmax-weighted merge are all standard differentiable JAX).
  """
  from tensor2robot_tpu.ops.flash_attention import (
      flash_attention_with_lse,
  )

  idx = jax.lax.axis_index(axis_name)
  perm = [(j, (j - 1) % ring_size) for j in range(ring_size)]
  batch, t_local, heads, _ = q.shape

  def attend(qq, kk, vv, block_causal):
    return flash_attention_with_lse(
        qq, kk, vv, causal=block_causal, interpret=interpret)

  def skip(qq, kk, vv):
    del kk, vv
    return (jnp.zeros_like(qq),
            jnp.full((batch, heads, t_local), _NEG_INF, jnp.float32))

  outs, lses = [], []
  for s in range(ring_size):
    if causal and s > 0:
      # Blocks from the future (src > idx) are fully excluded: skip
      # the kernel — the ppermute still rotates K/V through.
      src = (idx + s) % ring_size
      o_s, lse_s = jax.lax.cond(
          src < idx, functools.partial(attend, block_causal=False),
          skip, q, k, v)
    else:
      o_s, lse_s = attend(q, k, v, block_causal=(causal and s == 0))
    outs.append(o_s)
    lses.append(lse_s)
    if s < ring_size - 1:
      k = jax.lax.ppermute(k, axis_name, perm)
      v = jax.lax.ppermute(v, axis_name, perm)
  lse = jnp.stack(lses)                      # [S, B, H, Tq]
  weights = jax.nn.softmax(lse, axis=0)      # exact partial combine
  out = jnp.einsum("sbht,sbthd->bthd", weights,
                   jnp.stack(outs).astype(jnp.float32))
  return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Optional[Mesh] = None,
    axis_name: str = SEQ_AXIS,
    causal: bool = False,
    shard_batch: bool = True,
    block_impl: str = "reference",
    flash_interpret: bool = False,
) -> jax.Array:
  """Exact attention with the sequence dim sharded over `axis_name`.

  Args:
    q, k, v: [B, T, H, D]; T must divide by the `axis_name` mesh size.
    mesh: the device mesh; None (or no/trivial `axis_name` axis) falls
      back to the single-device reference — same math, one function
      for models to call everywhere.
    causal: causal masking by global position.
    shard_batch: also shard B over the `data` axis when the mesh has
      one (the standard data × sequence 2D layout).
    block_impl: per-device block math — "reference" (jnp online
      softmax) or "flash" (the Pallas kernel per block, partials
      combined by logsumexp; the long-context production path on TPU).
    flash_interpret: run the kernel in the pallas interpreter (CPU
      tests).

  Returns [B, T, H, D], sharded like q.
  """
  if (mesh is None or axis_name not in mesh.axis_names
      or mesh.shape[axis_name] == 1):
    return attention_reference(q, k, v, causal=causal)
  if q.shape[1] % mesh.shape[axis_name]:
    raise ValueError(
        f"Sequence length {q.shape[1]} must divide the {axis_name!r} "
        f"axis size {mesh.shape[axis_name]}.")

  # B shards over `data` when it divides; otherwise it replicates so
  # the function still serves any batch. B == 1 (a model init's dummy
  # batch, single-example serving) replicates silently — that's the
  # designed path. Any other non-divisible B warns: training batches
  # are divisibility-enforced upstream (`mesh.local_batch_size`), so
  # hitting this in a train loop means the layout is wrong and every
  # data row is burning axis_size× the FLOPs.
  batch_axis = None
  if shard_batch and DATA_AXIS in mesh.axis_names:
    data_size = mesh.shape[DATA_AXIS]
    if q.shape[0] % data_size == 0:
      batch_axis = DATA_AXIS
    elif q.shape[0] != 1:
      warnings.warn(
          f"ring_attention: batch {q.shape[0]} does not divide the "
          f"{DATA_AXIS!r} axis size {data_size}; replicating the "
          "batch across it (correct but axis_size× redundant "
          "compute). Fine for small-batch serving; a training batch "
          "should be a multiple of the data axis.",
          RuntimeWarning, stacklevel=2)
  spec = P(batch_axis, axis_name, None, None)
  if block_impl == "flash":
    local = functools.partial(
        _ring_attention_local_flash, axis_name=axis_name,
        causal=causal, ring_size=mesh.shape[axis_name],
        interpret=flash_interpret)
  elif block_impl == "reference":
    local = functools.partial(
        _ring_attention_local, axis_name=axis_name, causal=causal)
  else:
    raise ValueError(f"Unknown block_impl: {block_impl!r}")
  fn = shard_map_compat(
      lambda q, k, v: local(q, k, v),
      mesh, in_specs=(spec, spec, spec), out_specs=spec)
  return fn(q, k, v)


def sequence_sharding(mesh: Mesh,
                      shard_batch: bool = True) -> NamedSharding:
  """The [B, T, ...] activation sharding matching `ring_attention`."""
  batch_axis = (DATA_AXIS if shard_batch
                and DATA_AXIS in mesh.axis_names else None)
  return NamedSharding(mesh, P(batch_axis, SEQ_AXIS))
