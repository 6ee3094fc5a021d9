"""Pallas fused CEM scoring + running arg-top-k + elite-stats kernel.

The CEM inner loop (`research/qtopt/cem.py`) scores a [B, P] population
through the Q-head MLP, runs `lax.top_k`, gathers the elite actions,
and reduces them to a refreshed mean/std — four XLA ops with the full
[B, P] score tensor and an [B, E, A] elite gather materialized between
them. This kernel fuses the whole tail of one CEM iteration: the
q-head MLP applied to the pooled population features, a RUNNING top-k
over sample blocks (flash-attention-style: merge each block's
candidates into the kept elite set, so no full score tensor ever
exists), and the elite mean/std/best reduction — one HBM read of the
pooled features, four [B, ·] rows out. In the kernel the population
index stays on the leading (untiled) axis with states on sublanes and
features on lanes, so the per-state top-k is elementwise work across
vregs and nothing is relaid out between sublanes and lanes.

Selection semantics are EXACTLY `lax.top_k`'s: ties broken toward the
lower sample index. The running merge preserves that globally because
kept elites always precede the current block in combined order (see
`_select_top` — the proof is in tests/test_cem_select.py's tie cases).

Numerics: MLP GEMMs accumulate in f32 (`preferred_element_type`) from
the caller's operand dtype; all selection/statistics math is f32. The
`cem_select_lax` reference implements the identical contract in plain
lax and is the parity oracle for the interpret-mode CPU tests; on
hardware the compiled kernel is checked against it by `chip_smoke.py`
at the flagship shape (first compiled on a v5e in PR 21: exact
agreement). Its speed against the lax path is not measured on the
chip (ROADMAP S6); `cem_select="lax"` stays the default.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = float("-inf")


def _mlp_f32(x, flat_dense):
  """The q-head MLP with f32 accumulation; x [N, C] → [N, 1] f32."""
  h = x
  num_dense = len(flat_dense) // 2
  for layer in range(num_dense):
    w, b = flat_dense[2 * layer], flat_dense[2 * layer + 1]
    h = jax.lax.dot_general(
        h, w[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + \
        b[...].astype(jnp.float32)
    if layer < num_dense - 1:
      h = jnp.maximum(h, 0.0).astype(x.dtype)
  return h  # [N, 1] f32


def _select_top(scores, actions, num_elites):
  """Iterative top-k with lax.top_k tie semantics (first index wins).

  scores [N, Bb, 1] f32, actions [N, Bb, A] f32: candidates on the
  LEADING axis, states on sublanes. Returns (top_scores [E, Bb, 1],
  top_actions [E, Bb, A]) in descending score order per state.
  """
  n = scores.shape[0]
  idx = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
  top_s, top_a = [], []
  work = scores
  for _ in range(num_elites):
    m = jnp.max(work, axis=0, keepdims=True)               # [1, Bb, 1]
    first = jnp.min(jnp.where(work == m, idx, n), axis=0,
                    keepdims=True)
    hit = idx == first                                     # [N, Bb, 1]
    top_s.append(m)
    top_a.append(jnp.sum(jnp.where(hit, actions, 0.0), axis=0,
                         keepdims=True))
    work = jnp.where(hit, _NEG_INF, work)
  return jnp.concatenate(top_s, axis=0), jnp.concatenate(top_a, axis=0)


def _cem_select_kernel(pooled_ref, samples_ref, *rest, p: int,
                       num_elites: int, block_p: int, min_std: float,
                       sigmoid: bool):
  """One grid cell: `Bb` states' full populations → elite stats.

  Laid out for Mosaic: the population index stays on the LEADING
  (untiled) axis from the HBM block to the last reduction, states sit
  on sublanes and features on lanes — so the per-state top-k is
  elementwise work across vregs, sample blocks are free leading-axis
  slices, and no value is ever moved between sublanes and lanes.
  """
  hidden, (w_last, b_last) = rest[:-6], rest[-6:-4]
  mean_ref, std_ref, best_a_ref, best_s_ref = rest[-4:]
  block_b, c = pooled_ref.shape[1:]
  a_dim = samples_ref.shape[-1]

  # MLP over all P·Bb rows at once; hidden layers on the MXU, the
  # width-1 output layer as a lane reduction (an N=1 matmul would pad
  # to a full MXU pass for one useful column).
  h = pooled_ref[...].reshape(p * block_b, c)
  for layer in range(len(hidden) // 2):
    w, b = hidden[2 * layer], hidden[2 * layer + 1]
    h = jax.lax.dot_general(
        h, w[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b[...].astype(jnp.float32)
    h = jnp.maximum(h, 0.0).astype(pooled_ref.dtype)
  scores = jnp.sum(
      h.astype(jnp.float32) * w_last[...].astype(jnp.float32),
      axis=1, keepdims=True) + b_last[...].astype(jnp.float32)
  if sigmoid:
    scores = jax.nn.sigmoid(scores)
  scores = scores.reshape(p, block_b, 1)
  acts = samples_ref[...]                                  # [P, Bb, A]

  top_s = jnp.full((num_elites, block_b, 1), _NEG_INF, jnp.float32)
  top_a = jnp.zeros((num_elites, block_b, a_dim), jnp.float32)
  for lo in range(0, p, block_p):
    hi = min(lo + block_p, p)
    # Merge kept elites with this block; kept entries come FIRST in
    # combined order, so a tie between a kept elite (earlier global
    # index by construction) and a new candidate resolves to the
    # kept one — the global lax.top_k tie order.
    top_s, top_a = _select_top(
        jnp.concatenate([top_s, scores[lo:hi]], axis=0),
        jnp.concatenate([top_a, acts[lo:hi]], axis=0), num_elites)

  mean = jnp.mean(top_a, axis=0)                           # [Bb, A]
  var = jnp.mean((top_a - mean[None]) ** 2, axis=0)
  mean_ref[...] = mean
  std_ref[...] = jnp.maximum(jnp.sqrt(var), min_std)
  best_a_ref[...] = top_a[0]
  best_s_ref[...] = top_s[0]


@functools.partial(
    jax.jit, static_argnames=("num_elites", "min_std", "sigmoid",
                              "interpret", "block_p", "block_b"))
def fused_cem_select(
    pooled: jax.Array,
    samples: jax.Array,
    dense_params: Tuple[Tuple[jax.Array, jax.Array], ...],
    num_elites: int,
    min_std: float = 1e-2,
    sigmoid: bool = False,
    interpret: bool = False,
    block_p: int = 64,
    block_b: int = 16,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
  """Fused CEM iteration tail. Returns (mean, std, best_action,
  best_score) — mean/std/best_action [B, A] f32, best_score [B] f32.

  Args:
    pooled: [P, B, C] pooled population features in P-MAJOR order (the
      natural reshape of `GraspingQNetwork.pool_population`'s P-major
      GEMM output — no transpose on the hot path).
    samples: [B, P, A] the candidate actions that produced `pooled`.
    dense_params: ((w, b), ...) of the q-head MLP; final width 1.
    num_elites: E; the running top-k width.
    min_std: floor applied to the elite std (CEM contract).
    sigmoid: apply sigmoid to scores before selection (the
      `sigmoid_q` grasp-success head semantics; monotone, so selection
      is unchanged but best_score is reported on the sigmoid scale).
    interpret: pallas interpret mode (every backend but TPU).
    block_p: sample-block width of the running top-k; P need NOT be a
      multiple (the tail block is simply shorter).
    block_b: states per grid cell; B is zero-padded up to a multiple.
      Compiled, it must be a multiple of the sublane tile of
      `pooled.dtype` (8 for f32, 16 for bf16) or cover all of B.
  """
  p, b, c = pooled.shape
  if samples.shape[:2] != (b, p):
    raise ValueError(f"samples {samples.shape} != [B={b}, P={p}, A]")
  a_dim = samples.shape[-1]
  if num_elites > p:
    raise ValueError(f"num_elites {num_elites} > population {p}")
  if dense_params[-1][0].shape[-1] != 1:
    raise ValueError("q-head MLP must end at width 1")
  block_p = min(block_p, max(p, 1))
  # P-major like `pooled` (a [B, P, A] f32 transpose — P·A floats per
  # state, noise next to the pooled features).
  samples = samples.astype(jnp.float32).transpose(1, 0, 2)
  b_pad = -(-b // block_b) * block_b
  if b_pad != b:
    pooled = jnp.pad(pooled, ((0, 0), (0, b_pad - b), (0, 0)))
    samples = jnp.pad(samples, ((0, 0), (0, b_pad - b), (0, 0)))

  flat_dense = []
  for w, bias in dense_params[:-1]:
    flat_dense += [w, bias.reshape(1, -1)]
  w_last, b_last = dense_params[-1]
  flat_dense += [w_last.reshape(1, -1), b_last.reshape(1, 1)]

  kernel = functools.partial(
      _cem_select_kernel, p=p, num_elites=num_elites, block_p=block_p,
      min_std=min_std, sigmoid=sigmoid)
  full = lambda x: pl.BlockSpec(x.shape, lambda i: (0,) * x.ndim)  # noqa: E731
  per_state = lambda width: pl.BlockSpec(  # noqa: E731
      (block_b, width), lambda i: (i, 0))
  rows = lambda width: jax.ShapeDtypeStruct(  # noqa: E731
      (b_pad, width), jnp.float32)
  mean, std, best_action, best_score = pl.pallas_call(
      kernel,
      grid=(b_pad // block_b,),
      in_specs=[
          pl.BlockSpec((p, block_b, c), lambda i: (0, i, 0)),
          pl.BlockSpec((p, block_b, a_dim), lambda i: (0, i, 0)),
      ] + [full(x) for x in flat_dense],
      out_specs=[per_state(a_dim)] * 3 + [per_state(1)],
      out_shape=[rows(a_dim)] * 3 + [rows(1)],
      interpret=interpret,
  )(pooled, samples, *flat_dense)
  return mean[:b], std[:b], best_action[:b], best_score[:b, 0]


def cem_select_lax(
    pooled: jax.Array,
    samples: jax.Array,
    dense_params: Tuple[Tuple[jax.Array, jax.Array], ...],
    num_elites: int,
    min_std: float = 1e-2,
    sigmoid: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
  """The kernel's contract in plain lax — the parity oracle.

  Same signature and numerics policy (f32-accumulated MLP, f32
  selection/statistics, lax.top_k tie order); materializes the full
  score tensor the kernel exists to avoid.
  """
  p, b, c = pooled.shape
  scores = _mlp_f32(pooled.reshape(p * b, c),
                    [x if x.ndim == 2 else x.reshape(1, -1)
                     for pair in dense_params for x in
                     (pair[0], pair[1])])
  scores = scores.reshape(p, b).T  # [B, P]
  if sigmoid:
    scores = jax.nn.sigmoid(scores)
  elite_scores, elite_idx = jax.lax.top_k(scores, num_elites)
  elites = jnp.take_along_axis(
      samples.astype(jnp.float32), elite_idx[..., None], axis=1)
  mean = jnp.mean(elites, axis=1)
  std = jnp.maximum(jnp.std(elites, axis=1), min_std)
  return mean, std, elites[:, 0], elite_scores[:, 0]
