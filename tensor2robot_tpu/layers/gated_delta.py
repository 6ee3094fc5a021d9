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


def scan_walk(writes, k_decayed, q_decayed, k_to_end, end_decay):
  """The walk over the chunks as a `lax.scan` carrying the float32
  state [B, H, Dk, Dv]: the plain path (a CPU, shapes that do not tile)
  and the oracle of `ops/delta_rule_walk.walk`, which has this
  signature. `writes` [N, B, H, C, Dv] float32; `k_decayed`,
  `q_decayed`, `k_to_end` [N, B, H, C, Dk] in the products' dtype;
  `end_decay` [N, B, H]. Returns (`new`, `carried`): what each chunk
  writes given the state it starts from, and what its queries read of
  that state, [N, B, H, C, Dv] float32."""
  dtype = k_decayed.dtype

  def mm(x, y, spec):
    return jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                      preferred_element_type=jnp.float32)

  def step(state, xs):
    writes_i, k_decayed_i, q_decayed_i, k_to_end_i, end_decay_i = xs
    new = writes_i - mm(k_decayed_i, state, "bhik,bhkv->bhiv")
    carried = mm(q_decayed_i, state, "bhik,bhkv->bhiv")
    state = (state * end_decay_i[..., None, None]
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


def _prepared_rule(q, k, v, g, beta, chunk, dtype, interpret):
  """The rule over a whole number of chunks in three stages: every
  chunk's operands prepared in large XLA products, the walk over the
  chunks, `within @ new`. Differentiable by autodiff (the walk's
  kernels bring their own rule): the path of an evaluation that a
  backward pass follows, on a TPU too, and of every evaluation where
  the fused program cannot run."""
  b, t, h, dk = q.shape
  n = t // chunk

  def chunks(x):  # [B, T, H, ...] -> [N, B, H, C, ...]
    x = x.reshape((b, n, chunk) + x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

  q, k, v = (chunks(x).astype(dtype) for x in (q, k, v))
  g, beta = (chunks(x.astype(jnp.float32)) for x in (g, beta))
  g = jnp.cumsum(g, axis=-1)  # decay from the chunk's start, [N,B,H,C]

  def mm(x, y, spec):
    return jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                      preferred_element_type=jnp.float32)

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

  kernel = _kernels_run(chunk, dk, v.shape[-1], dtype, interpret)
  tmetrics.counter("gated_delta.walk.kernel_traces" if kernel
                   else "gated_delta.walk.scan_traces").inc()
  walk = (functools.partial(delta_rule_walk.walk, interpret=interpret)
          if kernel else scan_walk)
  new, carried = walk(writes, k_decayed, q_decayed, k_to_end, end_decay)
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
  [B, T, H] float32. Returns o [B, T, H, Dv] float32. Matrix products
  against q, k, v and the state take `dtype` operands and accumulate in
  float32.

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
  `_prepared_rule` that took each walk. `interpret` is the tests': the
  kernels in the Pallas interpreter, whatever the platform and the
  shapes."""
  t, dk = q.shape[1], q.shape[-1]
  pad = -t % chunk
  if pad:
    # beta 0 writes nothing, g 0 decays nothing, k 0 reads nothing.
    q, k, v, g, beta = (
        jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        for x in (q, k, v, g, beta))
  fused = _kernels_run(chunk, dk, v.shape[-1], dtype, interpret)
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
