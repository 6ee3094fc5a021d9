"""Causal transformer trunk with pluggable attention backends.

The reference's sequence models were SNAIL-style causal convs +
single-head attention over short episodes (`layers/snail.py` parity
module); this trunk is the long-context counterpart the TPU stack
makes first-class: the same module scales from short demo episodes to
32k-step contexts by swapping the attention implementation —

  * "reference": materialized softmax attention (CPU tests, short T),
  * "flash": the Pallas O(T)-memory kernel (`ops/flash_attention.py`),
  * "ring": sequence-parallel across chips
    (`parallel/ring_attention.py`; requires `mesh`). On TPU the
    per-device blocks run the flash kernel, whose lse output is
    differentiable — training through the ring works,
  * "ring_flash": the ring with flash blocks forced on (interpret
    mode off-TPU) — the CPU-testable spelling of the TPU ring path,
  * "auto": flash on TPU, reference elsewhere.

All backends compute EXACT attention in forward AND backward, so
checkpoints are portable across them (train with ring on a pod, serve
with flash on one chip).

A block is data (docs/SEQUENCE.md): `TransformerBlock` takes its norm
by name and, as module attributes, its sequence mixer and its
feed-forward (`MultiHeadAttention`, `GatedAttention`,
`layers/gated_delta.GatedDeltaNet`; a dense MLP, `parallel/moe.MoEMLP`,
`parallel/moe.SparseMoE`), so a trunk that alternates layer kinds is a
tuple of blocks (`SequenceTrunk`). Left out, they are the pre-LN
attention block that `CausalTransformer` has always stacked.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensor2robot_tpu.telemetry import metrics as tmetrics


def _repeat_kv(k, v, heads: int):
  """k and v [B, T, KV, D] with each key-value head repeated to its
  group's `heads // KV` query heads, in HBM; counts the traced calls
  that repeat in `attention.kv_repeat_traces`."""
  group = heads // k.shape[2]
  if group == 1:
    return k, v
  tmetrics.counter("attention.kv_repeat_traces").inc()
  return tuple(jnp.repeat(y, group, axis=2) for y in (k, v))


def _on_tpu() -> bool:
  return jax.devices()[0].platform == "tpu"


def _resolve_impl(impl: str) -> str:
  """`auto` is the flash kernel on a TPU and materialised attention
  elsewhere: read off the platform, nobody sets it."""
  if impl == "auto":
    return "flash" if _on_tpu() else "reference"
  return impl


def _attend(q, k, v, *, impl: str, causal: bool, mesh,
            window: Optional[int] = None) -> jax.Array:
  """Dispatches attention of q [B, T, H, D] over k, v [B, T, KV, D] to
  the chosen backend; KV divides H. `flash` and `reference` take
  values of another width than the keys', and with `causal` a
  `window` (query i sees key j iff 0 <= i - j < window). `flash` reads
  a group's key-value head where it lies; the other backends take as
  many key-value heads as query heads, so for them the key-value heads
  are repeated here (the registry's counter `attention.kv_repeat_traces`
  counts the traced calls that did)."""
  from tensor2robot_tpu.ops import flash_attention
  from tensor2robot_tpu.parallel import (
      attention_reference,
      ring_attention,
  )

  on_tpu = _on_tpu()
  impl = _resolve_impl(impl)
  if impl == "flash":
    return flash_attention(q, k, v, causal=causal, window=window)
  k, v = _repeat_kv(k, v, q.shape[2])
  if impl in ("ring", "ring_flash"):
    if mesh is None:
      raise ValueError(
          f"attention_impl={impl!r} needs a device mesh with a "
          "'seq' axis; pass mesh= (models: the mesh constructor "
          "argument) or use 'flash'/'reference' single-device.")
    if window is not None:
      raise ValueError(
          f"attention_impl={impl!r} has no window: the ring passes "
          "whole key blocks round; use 'flash' or 'reference'.")
    # On TPU the ring runs the flash kernel within each chip
    # (partials combined by logsumexp over the ICI ring);
    # "ring_flash" forces that composition off-TPU too, via the
    # pallas interpreter — how CPU tests cover the production path.
    use_flash = on_tpu or impl == "ring_flash"
    return ring_attention(q, k, v, mesh=mesh, causal=causal,
                          block_impl="flash" if use_flash
                          else "reference",
                          flash_interpret=use_flash and not on_tpu)
  if impl == "reference":
    return attention_reference(q, k, v, causal=causal, window=window)
  raise ValueError(f"Unknown attention impl: {impl!r}")


class MultiHeadAttention(nn.Module):
  """QKV projections around a pluggable exact-attention backend."""

  num_heads: int
  head_dim: int
  attention_impl: str = "reference"
  causal: bool = True
  mesh: Optional[Any] = None
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
    b, t, _ = x.shape
    h, d = self.num_heads, self.head_dim
    x = x.astype(self.dtype)
    qkv = nn.Dense(3 * h * d, use_bias=False, dtype=self.dtype,
                   name="qkv")(x)
    q, k, v = jnp.split(qkv.reshape(b, t, 3 * h, d), 3, axis=2)
    out = _attend(q, k, v, impl=self.attention_impl,
                  causal=self.causal, mesh=self.mesh)
    out = out.reshape(b, t, h * d)
    return nn.Dense(x.shape[-1], dtype=self.dtype, name="proj")(out)


class RMSNorm(nn.Module):
  """x / rms(x) * (1 + weight) over the last axis, in float32: the
  learnt weight is stored less one (zero-centred), so that w = 0 is
  the identity scale."""

  eps: float = 1e-6

  @nn.compact
  def __call__(self, x: jax.Array) -> jax.Array:
    x = x.astype(jnp.float32)
    weight = self.param("weight", nn.initializers.zeros,
                        (x.shape[-1],), jnp.float32)
    x = x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps)
    return x * (1.0 + weight)


class YarnRope(NamedTuple):
  """YaRN's parameters (arXiv:2309.00071) as a published
  `rope_parameters` block of `rope_type` `yarn` names them."""

  factor: float
  original_max_position_embeddings: int
  beta_fast: float
  beta_slow: float
  attention_factor: float  # the amplitude of cos and sin


def yarn_correction_range(rotary_dim: int, theta: float,
                          yarn: YarnRope) -> Tuple[int, int]:
  """(low, high): the pairs below `low` keep their frequency, those
  from `high` on are slowed by `factor`, those between are blended. The
  pair that turns `beta` times over the original context is pair
  d ln(L / (2 pi beta)) / (2 ln theta); floor for `beta_fast`, ceil for
  `beta_slow`, clipped to [0, d - 1] (the Hugging Face
  implementation's bounds)."""
  def pair(beta):
    return (rotary_dim * math.log(
        yarn.original_max_position_embeddings / (beta * 2 * math.pi))
            / (2 * math.log(theta)))
  return (max(math.floor(pair(yarn.beta_fast)), 0),
          min(math.ceil(pair(yarn.beta_slow)), rotary_dim - 1))


def rotary_frequencies(rotary_dim: int, theta: float,
                       yarn: Optional[YarnRope] = None
                       ) -> Tuple[jax.Array, float]:
  """(the `rotary_dim // 2` pairs' angular frequencies a position,
  the amplitude of cos and sin). Plain: f_i = theta^(-2 i / d), 1.
  YaRN: f'_i = (1 - r_i) f_i + r_i f_i / factor with the ramp
  r_i = clip((i - low) / (high - low), 0, 1) over
  `yarn_correction_range`, and `attention_factor`."""
  half = rotary_dim // 2
  inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
  if yarn is None:
    return inv_freq, 1.0
  low, high = yarn_correction_range(rotary_dim, theta, yarn)
  ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                  / max(high - low, 1e-3), 0.0, 1.0)
  return ((1.0 - ramp) * inv_freq + ramp * inv_freq / yarn.factor,
          yarn.attention_factor)


def rotary(x: jax.Array, rotary_dim: int, theta: float,
           interleaved: bool = False,
           yarn: Optional[YarnRope] = None) -> jax.Array:
  """Rotary position embedding on the first `rotary_dim` dims of each
  head of x [B, T, H, D]; positions are 0..T-1. Pair i of a head is
  turned by position * theta^(-i / half), or by YaRN's blended
  frequency and scaled by its amplitude (`rotary_frequencies`): dims
  (i, i + half) in the rotate-half layout, dims (2 i, 2 i + 1) where
  `interleaved`."""
  t = x.shape[1]
  half = rotary_dim // 2
  inv_freq, amplitude = rotary_frequencies(rotary_dim, theta, yarn)
  angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
  cos = jnp.cos(angles)[None, :, None, :]
  sin = jnp.sin(angles)[None, :, None, :]
  if yarn is not None:
    cos, sin = cos * amplitude, sin * amplitude
  turned, rest = jnp.split(x, [rotary_dim], axis=-1)
  if interleaved:
    pairs = turned.reshape(turned.shape[:-1] + (half, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                       axis=-1).reshape(turned.shape)
  else:
    x1, x2 = jnp.split(turned, 2, axis=-1)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                             axis=-1)
  return jnp.concatenate([turned, rest], axis=-1)


class GatedAttention(nn.Module):
  """Grouped-query softmax attention with a sigmoid gate on its output.

  `num_heads` query heads over `num_kv_heads` key-value heads of
  `head_dim`; `q_proj` gives per query head the query and a gate
  (columns: all queries, then all gates); zero-centred RMS norms over
  the head dimension of q and of k; rotary on the first `rotary_dim`
  dims at `rope_theta`, plain or with `yarn`'s blended frequencies and
  amplitude; out = attention * sigmoid(gate) under `o_proj`. With
  `window`, position i attends over itself and the `window - 1` before
  it (`ops/flash_attention.py`'s band), under the named scope
  `window_attention`; without, over all before it, under
  `gated_attention`. The key-value heads are repeated to the query
  heads before the backend, unless `grouped_kv`: then they go to it as
  they are, and the flash kernel reads a group's head where it lies
  (`_attend`; the hybrid model's layers keep the repeat until their
  cell has been measured without it, ROADMAP M3). A layer with a
  window counts its traced calls in `attention.window.kernel_traces`
  or `.materialised_traces`, and for the kernel the pairs its band
  holds and the pairs of the tiles its grid computes
  (`attention.window.band_pairs`, `.tile_pairs`).
  """

  num_heads: int
  num_kv_heads: int
  head_dim: int
  rotary_dim: int
  rope_theta: float = 1e7
  eps: float = 1e-6
  attention_impl: str = "auto"
  mesh: Optional[Any] = None
  dtype: Any = jnp.bfloat16
  window: Optional[int] = None
  yarn: Optional[YarnRope] = None
  grouped_kv: bool = False

  def _count_window(self, impl: str, rows: int, t: int) -> None:
    if impl != "flash":
      tmetrics.counter("attention.window.materialised_traces").inc()
      return
    from tensor2robot_tpu.ops.flash_attention import window_tiling
    _, _, band, tiles = window_tiling(t, self.window)
    tmetrics.counter("attention.window.kernel_traces").inc()
    tmetrics.counter("attention.window.band_pairs").inc(
        rows * self.num_heads * band)
    tmetrics.counter("attention.window.tile_pairs").inc(
        rows * self.num_heads * tiles)

  @nn.compact
  def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
    b, t, width = x.shape
    h, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
    x = x.astype(self.dtype)
    # A window that holds the whole sequence is none.
    window = self.window if self.window and self.window < t else None

    def proj(name, heads):
      return nn.Dense(heads * d, use_bias=False, dtype=self.dtype,
                      name=name)(x).reshape(b, t, heads, d)

    with jax.named_scope("window_attention" if window
                         else "gated_attention"):
      q, gate = jnp.split(proj("q_proj", 2 * h), 2, axis=2)
      k, v = proj("k_proj", kv), proj("v_proj", kv)
      q = RMSNorm(self.eps, name="q_norm")(q)
      k = RMSNorm(self.eps, name="k_norm")(k)
      q, k = (rotary(y, self.rotary_dim, self.rope_theta,
                     yarn=self.yarn).astype(self.dtype) for y in (q, k))
      if not self.grouped_kv:
        k, v = _repeat_kv(k, v, h)
      impl = _resolve_impl(self.attention_impl)
      if window:
        self._count_window(impl, b, t)
      out = _attend(q, k, v, impl=impl, causal=True, mesh=self.mesh,
                    window=window)
      out = out * jax.nn.sigmoid(gate.astype(jnp.float32)
                                 ).astype(self.dtype)
      return nn.Dense(width, use_bias=False, dtype=self.dtype,
                      name="o_proj")(out.reshape(b, t, h * d))


class LatentAttention(nn.Module):
  """Multi-head latent attention (docs/SEQUENCE.md): queries and
  key-values go through low-rank latents with an RMS norm on each, and
  a head's key is its own `qk_nope_head_dim` dims beside
  `qk_rope_head_dim` dims that ALL heads share and that alone carry
  the position (rotary, pairs interleaved as published, or rotate-half).

    c_q = norm(x W_qa);  q = c_q W_qb -> [T, H, nope + rope]
    x W_kva -> c_kv (kv_lora_rank) | k_r (rope, one head)
    norm(c_kv) W_kvb -> [T, H, nope + v]:  k_n | v
    out = softmax_causal([q_n; rot(q_r)] [k_n; rot(k_r)]^T
                         / sqrt(nope + rope)) v  under `o_proj`

  Keys and queries are `nope + rope` wide and values `v_head_dim`: the
  backend takes the two widths as they are (`ops/flash_attention.py`
  on a TPU, materialised attention elsewhere: `_resolve_impl`; the
  registry's counters `mla.attend.kernel_traces` and
  `.materialised_traces` count the traced calls that took each). The
  materialised form is the published training form; the absorbed
  (latent-space) form that serving wants is not here.

  `q_lora_rank` None: the query is one projection `q_proj` to
  [T, H, nope + rope], no latent and no norm. `rope_theta` None:
  nothing is turned (a model whose other layers place the positions),
  and the `rope` dims stay what the weights make them: one key head
  from `kv_a_proj` that all heads share, beside each head's own.
  """

  num_heads: int
  q_lora_rank: Optional[int]
  kv_lora_rank: int
  qk_nope_head_dim: int
  qk_rope_head_dim: int
  v_head_dim: int
  rope_theta: Optional[float] = 1e4
  rope_interleave: bool = True
  eps: float = 1e-6
  attention_impl: str = "auto"
  mesh: Optional[Any] = None
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
    b, t, width = x.shape
    h, nope, rope = (self.num_heads, self.qk_nope_head_dim,
                     self.qk_rope_head_dim)
    x = x.astype(self.dtype)

    def dense(name, size):
      return nn.Dense(size, use_bias=False, dtype=self.dtype, name=name)

    def turn(y):
      return rotary(y, rope, self.rope_theta,
                    self.rope_interleave).astype(self.dtype)

    with jax.named_scope("mla/q_proj"):
      if self.q_lora_rank is None:
        q = dense("q_proj", h * (nope + rope))(x)
      else:
        c_q = RMSNorm(self.eps, name="q_a_norm")(
            dense("q_a_proj", self.q_lora_rank)(x))
        q = dense("q_b_proj", h * (nope + rope))(c_q.astype(self.dtype))
      q = q.reshape(b, t, h, nope + rope)
      if self.rope_theta is not None:
        q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])],
                            axis=-1)
    with jax.named_scope("mla/kv_proj"):
      c_kv, k_r = jnp.split(
          dense("kv_a_proj", self.kv_lora_rank + rope)(x),
          [self.kv_lora_rank], axis=-1)
      c_kv = RMSNorm(self.eps, name="kv_a_norm")(c_kv)
      k_n, v = jnp.split(
          dense("kv_b_proj", h * (nope + self.v_head_dim))(
              c_kv.astype(self.dtype)
          ).reshape(b, t, h, nope + self.v_head_dim), [nope], axis=-1)
      k_r = k_r[:, :, None, :]
      if self.rope_theta is not None:
        k_r = turn(k_r)
      k_r = jnp.broadcast_to(k_r, (b, t, h, rope))
      k = jnp.concatenate([k_n, k_r], axis=-1)
    with jax.named_scope("mla/attend"):
      impl = _resolve_impl(self.attention_impl)
      tmetrics.counter("mla.attend.materialised_traces"
                       if impl == "reference"
                       else "mla.attend.kernel_traces").inc()
      out = _attend(q, k, v, impl=impl, causal=True, mesh=self.mesh)
    with jax.named_scope("mla/o_proj"):
      return dense("o_proj", width)(
          out.reshape(b, t, h * self.v_head_dim))


class GatedMLP(nn.Module):
  """The dense gated unit as a block's `ffn`:
  down(silu(gate x) * up x) at `width`, no biases."""

  width: int
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, x: jax.Array) -> jax.Array:
    def dense(name, size):
      return nn.Dense(size, use_bias=False, dtype=self.dtype, name=name)

    with jax.named_scope("dense_ffn"):
      x = x.astype(self.dtype)
      return dense("down_proj", x.shape[-1])(
          nn.silu(dense("gate_proj", self.width)(x))
          * dense("up_proj", self.width)(x))


class TransformerBlock(nn.Module):
  """A pre-norm residual block as data: x + mixer(norm(x)), then
  x + ffn(norm(x)).

  `norm` names the two norms (`layer`: LayerNorm; `rms`: zero-centred
  `RMSNorm` in float32, the residual stream then stays float32).
  `mixer` and `ffn` are the block's two modules, any that map
  [B, T, M] to [B, T, M]. Left out, they are what the block has always
  built from its other attributes, under the names it has always
  given them: `MultiHeadAttention(num_heads, head_dim)`, and a GELU MLP
  of `mlp_ratio` or, with `moe_experts > 0`, a capacity-routed
  `MoEMLP` (`parallel/moe.py`: dropped tokens pass through on the
  residual, the Switch-transformer semantics).
  """

  num_heads: int = 0
  head_dim: int = 0
  mlp_ratio: int = 4
  attention_impl: str = "reference"
  causal: bool = True
  mesh: Optional[Any] = None
  dtype: Any = jnp.bfloat16
  moe_experts: int = 0
  moe_k: int = 2
  moe_capacity_factor: float = 2.0
  norm: str = "layer"
  norm_eps: float = 1e-6
  mixer: Optional[nn.Module] = None
  ffn: Optional[nn.Module] = None

  def _norm(self, name: str):
    if self.norm == "rms":
      return RMSNorm(self.norm_eps, name=name)
    if self.norm == "layer":
      return nn.LayerNorm(dtype=self.dtype, name=name)
    raise ValueError(f"Unknown norm: {self.norm!r}")

  @nn.compact
  def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
    width = x.shape[-1]
    mixer = self.mixer
    if mixer is None:
      mixer = MultiHeadAttention(
          num_heads=self.num_heads, head_dim=self.head_dim,
          attention_impl=self.attention_impl, causal=self.causal,
          mesh=self.mesh, dtype=self.dtype, name="attn")
    x = x + mixer(self._norm("ln_attn")(x), train=train)
    y = self._norm("ln_mlp")(x)
    if self.ffn is not None:
      y = self.ffn(y)
    elif self.moe_experts:
      from tensor2robot_tpu.parallel.moe import MoEMLP
      y = MoEMLP(
          num_experts=self.moe_experts,
          hidden_dim=width * self.mlp_ratio, k=self.moe_k,
          capacity_factor=self.moe_capacity_factor, mesh=self.mesh,
          dtype=self.dtype, name="moe")(y)
    else:
      y = nn.Dense(width * self.mlp_ratio, dtype=self.dtype,
                   name="mlp_in")(y)
      y = nn.gelu(y)
      y = nn.Dense(width, dtype=self.dtype, name="mlp_out")(y)
    return x + y


class SequenceTrunk(nn.Module):
  """`blocks` in order over x [B, T, M], then an `RMSNorm`; no
  embedding and no learnt positions: its caller embeds, its mixers
  place (rotary, a convolution, a recurrence). With `remat_policy`
  every block runs under `jax.checkpoint`, so the backward pass holds
  one block's activations at a time. Four policies: `full` saves
  nothing of a block (the choice at the memory limit); `dots` and
  `dots_no_batch` as `AbstractT2RModel.remat_policy` names them;
  `save_attention` saves what the mixer's core returned and nothing
  else: of an attention block what the flash kernel returned
  (`ops/flash_attention.SAVED_RESIDUAL_NAMES`: the output,
  2 B x tokens x heads x value width in bfloat16, and the float32
  logsumexp, 4 B x tokens x heads), of a Gated-DeltaNet block the
  rule's output after its gated norm, as `out_proj` reads it
  (`gated_delta.SAVED_RESIDUAL_NAMES`: 2 B x tokens x value width in
  bfloat16). The backward pass then runs every line of the block
  again but that core: the forward kernel, whose two results are its
  backward's residuals, or the delta rule's map over the rows, which
  keeps its own recomputation a row. A block whose mixer took
  materialised attention carries no such name: under `save_attention`
  it is the program of `full`."""

  blocks: Tuple[nn.Module, ...]
  remat_policy: Optional[str] = None

  @nn.compact
  def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
    for block in self.blocks:  # flax names them blocks_0, blocks_1, ...
      x = apply_block(block, x, train, self.remat_policy)
    return RMSNorm(name="norm_out")(x)


def apply_block(block: nn.Module, x: jax.Array, train: bool,
                remat_policy: Optional[str]) -> jax.Array:
  """`block(x, train)`, under `jax.checkpoint` where `remat_policy`
  names one (`SequenceTrunk`, which says what each saves). The
  registry's counters `trunk.checkpoint.attention_saved_blocks` and
  `.recomputed_blocks` count the traced blocks whose policy keeps what
  the mixer's core returned, and those whose policy does not: by the
  policy, whatever the block's mixer."""
  if remat_policy in (None, "none"):
    return block(x, train)
  from tensor2robot_tpu.layers import gated_delta
  from tensor2robot_tpu.ops.flash_attention import SAVED_RESIDUAL_NAMES
  policies = jax.checkpoint_policies
  policy = {
      "full": None,
      "dots": policies.checkpoint_dots,
      "dots_no_batch": policies.dots_with_no_batch_dims_saveable,
      "save_attention": policies.save_only_these_names(
          *SAVED_RESIDUAL_NAMES, *gated_delta.SAVED_RESIDUAL_NAMES),
  }[remat_policy]
  tmetrics.counter("trunk.checkpoint.attention_saved_blocks"
                   if remat_policy == "save_attention"
                   else "trunk.checkpoint.recomputed_blocks").inc()
  return nn.remat(lambda module, y: module(y, train),
                  policy=policy)(block, x)


class CausalTransformer(nn.Module):
  """Embedding + learned positions + N blocks + final LN.

  Input: per-step feature vectors [B, T, F]; output [B, T, width].
  `max_len` bounds the learned positional table (positions are static
  in this framework — episode/context lengths come from specs).
  """

  width: int
  depth: int
  num_heads: int
  max_len: int
  attention_impl: str = "reference"
  causal: bool = True
  mesh: Optional[Any] = None
  dtype: Any = jnp.bfloat16
  # MoE: every `moe_every`-th block (1-indexed from the top of each
  # group) swaps its dense MLP for `moe_experts` routed experts; 0
  # disables. The GShard convention is every-other-block (moe_every=2).
  moe_experts: int = 0
  moe_every: int = 2
  moe_k: int = 2
  moe_capacity_factor: float = 2.0

  @nn.compact
  def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
    b, t, _ = x.shape
    # isinstance guard: under jax2tf shape polymorphism (the export
    # path) t is a symbolic dimension and the comparison would be
    # inconclusive. There is NO loud serving-side length check — an
    # exported graph fed t > max_len silently clips to the last
    # learned position (see the mode="clip" note below); in-process
    # callers get this ValueError.
    if isinstance(t, int) and t > self.max_len:
      raise ValueError(f"sequence length {t} > max_len {self.max_len}")
    if self.width % self.num_heads:
      raise ValueError(
          f"width {self.width} must divide evenly into "
          f"{self.num_heads} heads (got remainder "
          f"{self.width % self.num_heads}); attention would silently "
          "run at reduced capacity otherwise.")
    head_dim = self.width // self.num_heads
    x = nn.Dense(self.width, dtype=self.dtype, name="embed")(
        x.astype(self.dtype))
    positions = self.param(
        "positions", nn.initializers.normal(0.02),
        (self.max_len, self.width))
    # iota-gather instead of positions[:t]: basic slicing rejects the
    # symbolic t of the jax2tf-polymorphic export path, while a
    # dimension-sized arange is supported. mode="clip": in an exported
    # graph a t > max_len request repeats the last learned position
    # (predictable degradation) rather than jnp.take's default
    # fill-with-NaN; in-process callers still get the loud ValueError
    # from the isinstance guard above.
    pos_t = jnp.take(positions, jnp.arange(t), axis=0, mode="clip")
    x = x + pos_t[None].astype(self.dtype)
    for i in range(self.depth):
      is_moe = (self.moe_experts > 0
                and (i + 1) % max(self.moe_every, 1) == 0)
      x = TransformerBlock(
          num_heads=self.num_heads, head_dim=head_dim,
          attention_impl=self.attention_impl, causal=self.causal,
          mesh=self.mesh, dtype=self.dtype, name=f"block{i}",
          moe_experts=self.moe_experts if is_moe else 0,
          moe_k=self.moe_k,
          moe_capacity_factor=self.moe_capacity_factor,
      )(x, train=train)
    return nn.LayerNorm(dtype=self.dtype, name="ln_out")(
        x).astype(jnp.float32)
