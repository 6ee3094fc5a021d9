"""Pallas fused CEM population-head tail for the QT-Opt Q-network.

The Bellman step's remaining HBM bill after the linearity split
(`GraspingQNetwork.score_population`) is the [B·P, h', w', C']
population activation making several round trips through HBM
(merge-add, relu, conv, BN, relu, pool). The merge GEMM itself stays
in XLA (its row-major output feeds this kernel with no relayout); the
kernel fuses EVERYTHING after it — per-state enc0 add, relu, the
remaining 3×3/stride-2 head conv (as 9 parity-plane tap GEMMs), the
eval-BN affine, relu, spatial mean pool, and the dense Q head — so
the activation is read from HBM exactly once and only [B, P] Q values
return.

Mosaic constraints shaped the design (probe-verified on hardware):
the lane (minor) dim never changes across reshapes — everything stays
[..., C]; the stride-2 conv uses [N, H, W, C] → [N, H/2, 2, W/2, 2, C]
parity planes instead of strided slicing; broadcasts only extend
leading dims or the lane dim.

Numerics: GEMMs accumulate in f32 (`preferred_element_type`), bf16
operands — the same contract as the XLA path, verified to bf16
tolerance against it in tests (interpret mode on CPU, compiled on
TPU).

MEASURED OUTCOME (v5e, bench primary config, on an earlier
installation; not measured on this one): the fused kernel runs
the tail in 3.09 ms vs 1.12 ms for the tuned XLA P-major formulation
in `GraspingQNetwork.score_population` (3.84 vs 1.29 ms at 128-wide
channels — width doesn't flip it). The kernel's per-state loop,
9 sequential tap GEMMs, and plane-shift copies cost more than the HBM
round trips they save; XLA's fused conv pipeline is simply the better
schedule at this arithmetic intensity. The production path therefore
stays XLA; this kernel is kept as the measured, numerics-verified
baseline and as the repo's worked example of the parity-plane conv
trick under Mosaic's lane-dim constraints. Negative results are
results.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _tap_plane(x6, di: int, dj: int, h2: int, w2: int):
  """The stride-2 3×3 SAME tap (di, dj) as shifted parity planes.

  x6: [N, H/2, 2, W/2, 2, C]. XLA's SAME padding for stride 2 /
  kernel 3 on an EVEN input is asymmetric (pad_low=0, pad_high=1), so
  output position (i, j) reads input (2i + di, 2j + dj); in parity
  coordinates that is plane (di & 1, dj & 1) with a +1 block shift
  for di/dj == 2 — the overflow row/col is zero (the high padding).
  """
  n = x6.shape[0]
  c = x6.shape[-1]
  plane = x6[:, :, di % 2, :, dj % 2, :]  # [N, H/2, W/2, C]
  if di // 2:
    plane = jnp.concatenate(
        [plane[:, 1:], jnp.zeros((n, 1, w2, c), plane.dtype)], axis=1)
  if dj // 2:
    plane = jnp.concatenate(
        [plane[:, :, 1:], jnp.zeros((n, h2, 1, c), plane.dtype)],
        axis=2)
  return plane


def _cem_head_kernel(act_ref, enc0_ref, taps_ref, bn_scale_ref,
                     bn_shift_ref, *rest, block_b: int, p: int,
                     h1: int, w1: int, c1: int, c2: int,
                     num_dense: int, compute_dtype):
  """One grid cell: `block_b` states × the full population → Q."""
  dense_refs = rest[:-1]
  q_ref = rest[-1]
  h2, w2 = h1 // 2, w1 // 2

  qs = []
  for b in range(block_b):
    # Merge: act rows for state b (+ its enc0, broadcast over P), relu.
    act = act_ref[b * p:(b + 1) * p]            # [P, h1, w1, c1]
    enc0 = enc0_ref[b]                          # [h1, w1, c1]
    x = jnp.maximum(
        act.astype(jnp.float32) + enc0.astype(jnp.float32), 0.0)
    x6 = x.reshape(p, h2, 2, w2, 2, c1).astype(compute_dtype)

    # Remaining head conv: 9 parity-plane tap GEMMs, f32 accumulate.
    acc = jnp.zeros((p * h2 * w2, c2), jnp.float32)
    for di in range(3):
      for dj in range(3):
        plane = _tap_plane(x6, di, dj, h2, w2).reshape(
            p * h2 * w2, c1)
        acc = acc + jax.lax.dot_general(
            plane, taps_ref[di * 3 + dj],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    y = acc * bn_scale_ref[...].astype(jnp.float32) \
        + bn_shift_ref[...].astype(jnp.float32)
    y = jnp.maximum(y, 0.0)
    pooled = jnp.mean(y.reshape(p, h2 * w2, c2), axis=1)  # [P, c2]

    h = pooled.astype(compute_dtype)
    for layer in range(num_dense):
      w_ref, b_ref = dense_refs[2 * layer], dense_refs[2 * layer + 1]
      h = jax.lax.dot_general(
          h, w_ref[...], (((1,), (0,)), ((), ())),
          preferred_element_type=jnp.float32) + \
          b_ref[...].astype(jnp.float32)
      if layer < num_dense - 1:
        h = jnp.maximum(h, 0.0).astype(compute_dtype)
    qs.append(h)  # [P, 1]

  q = jnp.stack(qs, axis=0)  # [block_b, P, 1]
  q_ref[...] = jnp.broadcast_to(
      q, (block_b, p, 128)).astype(q_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def fused_cem_head_tail(
    act: jax.Array,
    enc0: jax.Array,
    conv_kernel: jax.Array,
    bn_scale: jax.Array,
    bn_shift: jax.Array,
    dense_params: Tuple[Tuple[jax.Array, jax.Array], ...],
    interpret: bool = False,
    block_b: int = 2,
) -> jax.Array:
  """Fused population tail. Returns [B, P] f32 Q values.

  Args:
    act: [B, P, h1, w1, C1] merge-GEMM output in B-major row order
      (the XLA GEMM's natural layout; `a @ v` reshaped).
    enc0: [B, h1, w1, C1] BN'd conv0 of the torso features.
    conv_kernel: [3, 3, C1, C2] remaining head conv (3×3, stride 2).
    bn_scale, bn_shift: [C2] eval-mode BN affine of that conv.
    dense_params: ((w, b), ...) of the q-head MLP; final width 1.
  """
  b, p = act.shape[:2]
  h1, w1, c1 = enc0.shape[1:]
  c2 = conv_kernel.shape[-1]
  if h1 % 2 or w1 % 2:
    raise ValueError(f"head conv input spatial dims must be even; got "
                     f"({h1}, {w1})")
  if b % block_b:
    raise ValueError(f"batch {b} must divide block_b={block_b}")
  taps = conv_kernel.reshape(9, c1, c2)

  flat_dense = []
  for w, bias in dense_params:
    flat_dense += [w, bias.reshape(1, -1)]
  num_dense = len(dense_params)

  kernel = functools.partial(
      _cem_head_kernel, block_b=block_b, p=p, h1=h1, w1=w1, c1=c1,
      c2=c2, num_dense=num_dense, compute_dtype=act.dtype)
  full = lambda *shape: pl.BlockSpec(  # noqa: E731
      shape, lambda i: (0,) * len(shape))
  out = pl.pallas_call(
      kernel,
      grid=(b // block_b,),
      in_specs=[
          pl.BlockSpec((block_b * p, h1, w1, c1),
                       lambda i: (i, 0, 0, 0)),
          pl.BlockSpec((block_b, h1, w1, c1), lambda i: (i, 0, 0, 0)),
          full(9, c1, c2),
          full(1, c2),
          full(1, c2),
      ] + [full(*x.shape) for x in flat_dense],
      out_specs=pl.BlockSpec((block_b, p, 128), lambda i: (i, 0, 0)),
      out_shape=jax.ShapeDtypeStruct((b, p, 128), jnp.float32),
      interpret=interpret,
  )(act.reshape(b * p, h1, w1, c1), enc0, taps,
    bn_scale.reshape(1, -1), bn_shift.reshape(1, -1), *flat_dense)
  return out[..., 0]
