"""Next-token language modelling as a T2R model: token ids in, the mean
cross-entropy of every position's successor out, trained by
`train_eval_model` like every other family.

The network is an embedding, a `layers/transformer.SequenceTrunk` whose
blocks are data, a final norm and an untied head. Four families build
it, each with the names of its published configuration's keys as
constructor arguments, so a configuration file and the model read
alike; what they share (specs, the loss in blocks, the reduction of the
routing counters, the per-layer checkpointing) is `_LanguageModel`'s:

- `NextTokenLanguageModel`, the hybrid pattern of Qwen3-Next (the
  shipped `models/configs/train_qwen3_next.gin` binds its published
  widths): layer i mixes with gated grouped-query attention where
  `(i + 1) % full_attention_interval == 0` and with a Gated DeltaNet
  otherwise (`layers/gated_delta.py`), and every layer's feed-forward
  is a dropless mixture of experts beside a gated shared expert
  (`parallel/moe.SparseMoE`).
- `LatentAttentionLanguageModel`, the DeepSeek-V3 pattern as
  JoyAI-LLM-Flash publishes it (`train_joyai_llm_flash.gin`): every
  layer mixes with multi-head latent attention
  (`layers/transformer.LatentAttention`); the first
  `first_k_dense_replace` feed-forwards are dense gated units, the
  others experts chosen by sigmoid score plus a selection bias beside
  an ungated shared expert; and a multi-token-prediction module
  (`MultiTokenPrediction`) adds `mtp_loss_weight` times the loss of
  predicting each position's successor's successor.
- `WindowedAttentionLanguageModel`, sliding-window attention beside
  full attention in one trunk as Laguna-XS.2 publishes it
  (`train_laguna_xs2.gin`): `layer_types[i]` says whether layer i's
  gated grouped-query attention sees every earlier position or a band
  of `sliding_window`; a layer's query heads
  (`num_attention_heads_per_layer[i]`) and its rotary embedding
  (`rope_parameters[layer_types[i]]`: plain or YaRN, its own base and
  share of a head's dims) follow its type; `mlp_layer_types[i]` says
  whether its feed-forward is a dense gated unit or experts chosen by
  sigmoid score beside an ungated shared expert.

- `ChannelGatedDeltaLanguageModel`, the delta rule with a decay per
  key channel beside latent attention without positions as
  Kimi-Linear publishes it (`train_kimi_linear.gin`): the 1-based lists
  `linear_attn_config["kda_layers"]` and `["full_attn_layers"]` say
  which layers mix with `layers/gated_delta.KimiDeltaAttention` and
  which with `LatentAttention` (no query latent; with `mla_use_nope`
  nothing is turned: the linear layers place the positions); the first
  `first_k_dense_replace` feed-forwards are dense, the others experts
  chosen by sigmoid score plus a selection bias beside an ungated
  shared expert.

A chip's share of an expert-parallel deployment (docs/SEQUENCE.md):
`num_experts` is the router's width, `experts_held` how many of them
this model holds from `first_expert` on; `vocab_size` is the slice of
the vocabulary held (embedding, head, logits and loss are over it).

The loss is computed a block of positions at a time, each block under
`jax.checkpoint`: float32 logits of 32,768 positions by 18,992 ids
never stand whole, forward or backward.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu import config as gin
from tensor2robot_tpu.data.abstract_input_generator import Mode
from tensor2robot_tpu.layers.gated_delta import (
    GatedDeltaNet,
    KimiDeltaAttention,
)
from tensor2robot_tpu.layers.transformer import (
    GatedAttention,
    GatedMLP,
    LatentAttention,
    RMSNorm,
    SequenceTrunk,
    TransformerBlock,
    YarnRope,
    apply_block,
)
from tensor2robot_tpu.models.abstract_model import AbstractT2RModel
from tensor2robot_tpu.parallel.moe import SparseMoE
from tensor2robot_tpu.specs import ExtendedTensorSpec, TensorSpecStruct

TOKEN_IDS = "token_ids"
NEXT_TOKEN_LOGITS = "next_token_logits"
MOE_COUNTERS = "moe_counters"


def next_token_loss(hidden: jax.Array, head: jax.Array,
                    targets: jax.Array, block: int, dtype: Any,
                    counted: Optional[jax.Array] = None) -> jax.Array:
  """Mean over N positions of logsumexp(h W) - (h W)[target], a block
  of `block` positions at a time (all at once where `block` does not
  divide N). hidden [N, M], head [M, V], targets [N]; with `counted`
  [N] bool, the mean over the positions it marks."""
  n, width = hidden.shape
  if n % block:
    block = n

  @jax.checkpoint
  def block_loss(h, t, c):
    logits = jnp.dot(h.astype(dtype), head.astype(dtype),
                     preferred_element_type=jnp.float32)
    picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
    each = jax.nn.logsumexp(logits, axis=-1) - picked
    return jnp.sum(each if c is None else jnp.where(c, each, 0.0))

  def blocks(x):
    return None if x is None else x.reshape((n // block, block)
                                            + x.shape[1:])

  sums = jax.lax.map(lambda htc: block_loss(*htc),
                     (blocks(hidden), blocks(targets), blocks(counted)))
  return jnp.sum(sums) / (n if counted is None else jnp.sum(counted))


class MultiTokenPrediction(nn.Module):
  """Multi-token prediction at depth 1 (DeepSeek-V3, arXiv:2412.19437
  section 2.2): position i's trunk output and the embedding of its
  successor, each under its own RMS norm, side by side through
  `eh_proj` (2 M -> M), then one more `block` and a final norm; what
  comes out predicts the successor's successor through the model's own
  head. `block` runs under `remat_policy` as the trunk's blocks do."""

  block: nn.Module
  remat_policy: Optional[str] = None
  eps: float = 1e-6
  dtype: Any = jnp.bfloat16

  @nn.compact
  def __call__(self, hidden: jax.Array, next_embedded: jax.Array,
               train: bool = False) -> jax.Array:
    with jax.named_scope("mtp/combine"):
      both = jnp.concatenate(
          [RMSNorm(self.eps, name="hnorm")(hidden),
           RMSNorm(self.eps, name="enorm")(next_embedded)], axis=-1)
      x = nn.Dense(hidden.shape[-1], use_bias=False, dtype=self.dtype,
                   name="eh_proj")(both.astype(self.dtype))
      x = x.astype(jnp.float32)  # the residual stream's dtype
    with jax.named_scope("mtp/block"):
      x = apply_block(self.block, x, train, self.remat_policy)
    return RMSNorm(self.eps, name="norm_out")(x)


class LanguageModelNetwork(nn.Module):
  """token ids [B, T + 1] -> the loss of predicting ids[:, 1:] from
  ids[:, :-1], and the logits after the last input position. With
  `mtp` (a `MultiTokenPrediction`) the loss is `loss_main +
  mtp_loss_weight * loss_mtp`, the second the mean over the T - 1
  positions that have a successor's successor, through the same
  embedding and head."""

  vocab_size: int
  hidden_size: int
  trunk: nn.Module
  loss_block: int = 4096
  dtype: Any = jnp.bfloat16
  mtp: Optional[nn.Module] = None
  mtp_loss_weight: float = 0.0

  @nn.compact
  def __call__(self, features, train: bool = False):
    ids = features[TOKEN_IDS]
    inputs, targets = ids[:, :-1], ids[:, 1:]
    embed = self.param("embed_tokens", nn.initializers.normal(1.0),
                       (self.vocab_size, self.hidden_size), jnp.float32)
    head = self.param("lm_head", nn.initializers.lecun_normal(),
                      (self.hidden_size, self.vocab_size), jnp.float32)
    x = jnp.take(embed, inputs, axis=0)
    x = self.trunk(x, train)
    with jax.named_scope("lm_head_loss"):
      loss = next_token_loss(
          x.reshape(-1, self.hidden_size), head, targets.reshape(-1),
          self.loss_block, self.dtype)
      last = jnp.dot(x[:, -1].astype(self.dtype), head.astype(self.dtype),
                     preferred_element_type=jnp.float32)
    outputs = {"loss": loss, NEXT_TOKEN_LOGITS: last}
    if self.mtp is not None:
      # All T positions go through the block (a length the attention
      # kernel tiles); the last has no target and is not counted, and
      # under a causal mixer it reaches no other.
      y = self.mtp(x, jnp.take(embed, targets, axis=0), train)
      with jax.named_scope("mtp_head_loss"):
        t = targets.shape[1]
        loss_mtp = next_token_loss(
            y.reshape(-1, self.hidden_size), head,
            jnp.pad(ids[:, 2:], ((0, 0), (0, 1))).reshape(-1),
            self.loss_block, self.dtype,
            jnp.broadcast_to(jnp.arange(t) < t - 1,
                             targets.shape).reshape(-1))
      outputs.update(
          loss=loss + self.mtp_loss_weight * loss_mtp,
          loss_main=loss, loss_mtp=loss_mtp)
    return outputs


class _LanguageModel(AbstractT2RModel):
  """What the language-model families share: ids of `sequence_length +
  1` positions in, `LanguageModelNetwork` over the family's blocks
  (`_block(layer)`; `_mtp()` where it has a multi-token-prediction
  module), each block under `remat_policy`, the routing counters of
  the expert layers reduced into the step's metrics."""

  _mtp_loss_weight = 0.0  # a family with a module sets its own

  def __init__(self, *, vocab_size: int, sequence_length: int,
               hidden_size: int, num_hidden_layers: int,
               rms_norm_eps: float, attention_impl: str,
               loss_block: int, device_dtype, remat_policy, **kwargs):
    """`remat_policy` is applied to each layer of the trunk, not to the
    whole loss: the backward pass holds one layer's activations at a
    time (`SequenceTrunk`)."""
    super().__init__(device_dtype=device_dtype,
                     remat_policy=remat_policy, **kwargs)
    self._vocab_size = vocab_size
    self._sequence_length = sequence_length
    self._hidden_size = hidden_size
    self._num_hidden_layers = num_hidden_layers
    self._rms_norm_eps = rms_norm_eps
    self._attention_impl = attention_impl
    self._loss_block = loss_block

  def get_feature_specification(self, mode: Mode) -> TensorSpecStruct:
    st = TensorSpecStruct()
    st[TOKEN_IDS] = ExtendedTensorSpec(
        shape=(self._sequence_length + 1,), dtype=np.int32,
        name=TOKEN_IDS)
    return st

  def get_label_specification(self, mode: Mode) -> TensorSpecStruct:
    return TensorSpecStruct()  # a position's label is its successor

  def _block(self, layer: int) -> TransformerBlock:
    raise NotImplementedError

  def _mtp(self) -> Optional[MultiTokenPrediction]:
    return None

  def create_network(self) -> nn.Module:
    return LanguageModelNetwork(
        vocab_size=self._vocab_size, hidden_size=self._hidden_size,
        trunk=SequenceTrunk(
            blocks=tuple(self._block(i)
                         for i in range(self._num_hidden_layers)),
            remat_policy=self._remat_policy),
        loss_block=self._loss_block, dtype=self.device_dtype,
        mtp=self._mtp(), mtp_loss_weight=self._mtp_loss_weight)

  def _loss_for_grad(self):
    return self.loss_fn  # the trunk checkpoints layer by layer

  def inference_network_fn(self, variables, features, mode: Mode,
                           rng: Optional[jax.Array] = None):
    """The network's outputs with the routing counters of its expert
    layers beside them (`parallel/moe.held_experts_ffn`, `SparseMoE`),
    reduced over the layers: the mean share of assignments that fall
    on held experts (and of those a selection bias moved), the worst
    load imbalance, the sum of dropped assignments, the most rounds
    that a layer's experts ran."""
    del rng  # the network draws nothing
    outputs, sown = self.network.apply(
        variables, features, train=mode == Mode.TRAIN,
        mutable=[MOE_COUNTERS])
    per_layer: Dict[str, list] = {}
    for path, value in jax.tree_util.tree_flatten_with_path(
        sown.get(MOE_COUNTERS, {}))[0]:
      name = next(p.key for p in reversed(path) if hasattr(p, "key"))
      per_layer.setdefault(name, []).append(value)
    reduce = {"assignments_here_share": jnp.mean,
              "bias_moved_choice_share": jnp.mean,
              "expert_load_max_over_mean": jnp.max,
              "dropped_assignments": jnp.sum,
              "rounds_run": jnp.max}
    outputs[MOE_COUNTERS] = {
        f"moe.{name}": reduce[name](jnp.stack(values))
        for name, values in per_layer.items()}
    return outputs, variables.get("batch_stats", {})

  def model_train_fn(self, features, labels, outputs, mode
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    scalars = dict(outputs[MOE_COUNTERS])
    scalars.update({f"lm.{name}": outputs[name]
                    for name in ("loss_main", "loss_mtp")
                    if name in outputs})
    return outputs["loss"], scalars

  def predict_step(self, state, features):
    outputs = super().predict_step(state, features)
    return {NEXT_TOKEN_LOGITS: outputs[NEXT_TOKEN_LOGITS]}


@gin.configurable
class NextTokenLanguageModel(_LanguageModel):
  """A hybrid linear-attention / attention mixture-of-experts language
  model trained on next-token cross-entropy (the module's docstring)."""

  def __init__(self,
               vocab_size: int = 151936,
               sequence_length: int = 8192,
               hidden_size: int = 2048,
               num_hidden_layers: int = 48,
               full_attention_interval: int = 4,
               num_attention_heads: int = 16,
               num_key_value_heads: int = 2,
               head_dim: int = 256,
               partial_rotary_factor: float = 0.25,
               rope_theta: float = 1e7,
               linear_num_key_heads: int = 16,
               linear_num_value_heads: int = 32,
               linear_key_head_dim: int = 128,
               linear_value_head_dim: int = 128,
               linear_conv_kernel_dim: int = 4,
               num_experts: int = 512,
               experts_held: Optional[int] = None,
               first_expert: int = 0,
               num_experts_per_tok: int = 10,
               norm_topk_prob: bool = True,
               moe_intermediate_size: int = 512,
               shared_expert_intermediate_size: int = 512,
               rms_norm_eps: float = 1e-6,
               attention_impl: str = "auto",
               loss_block: int = 4096,
               device_dtype=jnp.bfloat16,
               remat_policy: Optional[str] = "full",
               **kwargs):
    """`experts_held` defaults to all `num_experts`."""
    super().__init__(
        vocab_size=vocab_size, sequence_length=sequence_length,
        hidden_size=hidden_size, num_hidden_layers=num_hidden_layers,
        rms_norm_eps=rms_norm_eps, attention_impl=attention_impl,
        loss_block=loss_block, device_dtype=device_dtype,
        remat_policy=remat_policy, **kwargs)
    self._full_attention_interval = full_attention_interval
    self._num_attention_heads = num_attention_heads
    self._num_key_value_heads = num_key_value_heads
    self._head_dim = head_dim
    self._partial_rotary_factor = partial_rotary_factor
    self._rope_theta = rope_theta
    self._linear_num_key_heads = linear_num_key_heads
    self._linear_num_value_heads = linear_num_value_heads
    self._linear_key_head_dim = linear_key_head_dim
    self._linear_value_head_dim = linear_value_head_dim
    self._linear_conv_kernel_dim = linear_conv_kernel_dim
    self._num_experts = num_experts
    self._experts_held = (num_experts if experts_held is None
                          else experts_held)
    self._first_expert = first_expert
    self._num_experts_per_tok = num_experts_per_tok
    self._norm_topk_prob = norm_topk_prob
    self._moe_intermediate_size = moe_intermediate_size
    self._shared_expert_intermediate_size = (
        shared_expert_intermediate_size)

  def _block(self, layer: int) -> TransformerBlock:
    dtype, eps = self.device_dtype, self._rms_norm_eps
    if (layer + 1) % self._full_attention_interval == 0:
      mixer = GatedAttention(
          num_heads=self._num_attention_heads,
          num_kv_heads=self._num_key_value_heads,
          head_dim=self._head_dim,
          rotary_dim=int(self._head_dim * self._partial_rotary_factor),
          rope_theta=self._rope_theta, eps=eps,
          attention_impl=self._attention_impl, dtype=dtype)
    else:
      mixer = GatedDeltaNet(
          num_k_heads=self._linear_num_key_heads,
          num_v_heads=self._linear_num_value_heads,
          head_k_dim=self._linear_key_head_dim,
          head_v_dim=self._linear_value_head_dim,
          conv_kernel=self._linear_conv_kernel_dim, eps=eps,
          dtype=dtype)
    ffn = SparseMoE(
        num_experts=self._num_experts,
        experts_held=self._experts_held,
        first_expert=self._first_expert,
        k=self._num_experts_per_tok,
        normalise_top_k=self._norm_topk_prob,
        expert_width=self._moe_intermediate_size,
        shared_width=self._shared_expert_intermediate_size,
        dtype=dtype)
    return TransformerBlock(norm="rms", norm_eps=eps, mixer=mixer,
                            ffn=ffn, dtype=dtype)


@gin.configurable
class LatentAttentionLanguageModel(_LanguageModel):
  """A latent-attention mixture-of-experts language model with a
  multi-token-prediction loss (the module's docstring); the defaults
  are JoyAI-LLM-Flash's published configuration."""

  def __init__(self,
               vocab_size: int = 129280,
               sequence_length: int = 8192,
               hidden_size: int = 2048,
               num_hidden_layers: int = 40,
               num_attention_heads: int = 32,
               q_lora_rank: int = 1536,
               kv_lora_rank: int = 512,
               qk_nope_head_dim: int = 128,
               qk_rope_head_dim: int = 64,
               v_head_dim: int = 128,
               rope_theta: float = 32e6,
               rope_interleave: bool = True,
               first_k_dense_replace: int = 1,
               intermediate_size: int = 7168,
               n_routed_experts: int = 256,
               experts_held: Optional[int] = None,
               first_expert: int = 0,
               num_experts_per_tok: int = 8,
               norm_topk_prob: bool = True,
               scoring_func: str = "sigmoid",
               topk_method: str = "noaux_tc",
               n_group: int = 1,
               topk_group: int = 1,
               routed_scaling_factor: float = 2.5,
               n_shared_experts: int = 1,
               moe_intermediate_size: int = 768,
               num_nextn_predict_layers: int = 1,
               mtp_loss_weight: float = 0.3,
               rms_norm_eps: float = 1e-6,
               attention_impl: str = "auto",
               loss_block: int = 4096,
               device_dtype=jnp.bfloat16,
               remat_policy: Optional[str] = "full",
               **kwargs):
    """`experts_held` defaults to all `n_routed_experts`. `topk_method`
    `noaux_tc` chooses by score plus the router's bias, `greedy` by
    score; routing limited to groups of experts (`n_group` > 1) and
    more than one multi-token-prediction module are not here."""
    super().__init__(
        vocab_size=vocab_size, sequence_length=sequence_length,
        hidden_size=hidden_size, num_hidden_layers=num_hidden_layers,
        rms_norm_eps=rms_norm_eps, attention_impl=attention_impl,
        loss_block=loss_block, device_dtype=device_dtype,
        remat_policy=remat_policy, **kwargs)
    if topk_method not in ("noaux_tc", "greedy"):
      raise ValueError(f"Unknown topk_method: {topk_method!r}")
    if (n_group, topk_group) != (1, 1):
      raise ValueError("group-limited routing is not implemented: "
                       f"n_group={n_group}, topk_group={topk_group}")
    if num_nextn_predict_layers not in (0, 1):
      raise ValueError("multi-token prediction is implemented at depth "
                       f"1: num_nextn_predict_layers="
                       f"{num_nextn_predict_layers}")
    self._num_attention_heads = num_attention_heads
    self._q_lora_rank = q_lora_rank
    self._kv_lora_rank = kv_lora_rank
    self._qk_nope_head_dim = qk_nope_head_dim
    self._qk_rope_head_dim = qk_rope_head_dim
    self._v_head_dim = v_head_dim
    self._rope_theta = rope_theta
    self._rope_interleave = rope_interleave
    self._first_k_dense_replace = first_k_dense_replace
    self._intermediate_size = intermediate_size
    self._n_routed_experts = n_routed_experts
    self._experts_held = (n_routed_experts if experts_held is None
                          else experts_held)
    self._first_expert = first_expert
    self._num_experts_per_tok = num_experts_per_tok
    self._norm_topk_prob = norm_topk_prob
    self._scoring_func = scoring_func
    self._topk_method = topk_method
    self._n_group = n_group
    self._topk_group = topk_group
    self._routed_scaling_factor = routed_scaling_factor
    self._n_shared_experts = n_shared_experts
    self._moe_intermediate_size = moe_intermediate_size
    self._num_nextn_predict_layers = num_nextn_predict_layers
    self._mtp_loss_weight = mtp_loss_weight

  def _block(self, layer: int) -> TransformerBlock:
    """Layer `layer` of the trunk; any `layer` from
    `first_k_dense_replace` on is an expert layer."""
    dtype, eps = self.device_dtype, self._rms_norm_eps
    mixer = LatentAttention(
        num_heads=self._num_attention_heads,
        q_lora_rank=self._q_lora_rank, kv_lora_rank=self._kv_lora_rank,
        qk_nope_head_dim=self._qk_nope_head_dim,
        qk_rope_head_dim=self._qk_rope_head_dim,
        v_head_dim=self._v_head_dim, rope_theta=self._rope_theta,
        rope_interleave=self._rope_interleave, eps=eps,
        attention_impl=self._attention_impl, dtype=dtype)
    if layer < self._first_k_dense_replace:
      ffn = GatedMLP(width=self._intermediate_size, dtype=dtype)
    else:
      ffn = SparseMoE(
          num_experts=self._n_routed_experts,
          experts_held=self._experts_held,
          first_expert=self._first_expert,
          k=self._num_experts_per_tok,
          normalise_top_k=self._norm_topk_prob,
          scoring=self._scoring_func,
          selection_bias=self._topk_method == "noaux_tc",
          routed_scaling_factor=self._routed_scaling_factor,
          expert_width=self._moe_intermediate_size,
          shared_width=(self._n_shared_experts
                        * self._moe_intermediate_size),
          shared_gated=False, dtype=dtype)
    return TransformerBlock(norm="rms", norm_eps=eps, mixer=mixer,
                            ffn=ffn, dtype=dtype)

  def _mtp(self) -> Optional[MultiTokenPrediction]:
    if not self._num_nextn_predict_layers:
      return None
    return MultiTokenPrediction(
        block=self._block(self._num_hidden_layers),
        remat_policy=self._remat_policy, eps=self._rms_norm_eps,
        dtype=self.device_dtype)


FULL_ATTENTION, SLIDING_ATTENTION = "full_attention", "sliding_attention"
# Laguna-XS.2's published per-layer lists: 40 layers of period 4.
_LAGUNA_LAYER_TYPES = (FULL_ATTENTION,) + (SLIDING_ATTENTION,) * 3
_LAGUNA_ROPE = {
    FULL_ATTENTION: {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5},
    SLIDING_ATTENTION: {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1},
    "original_max_position_embeddings": 4096}


@gin.configurable
class WindowedAttentionLanguageModel(_LanguageModel):
  """A language model whose layers' attention is of two kinds in one
  trunk, full and sliding-window, each kind with its own number of
  query heads and its own rotary embedding, over dense and
  mixture-of-experts feed-forwards (the module's docstring); the
  defaults are Laguna-XS.2's published configuration, and the
  per-layer lists are as long as the published depth: a model of fewer
  layers reads their head."""

  def __init__(self,
               vocab_size: int = 100352,
               sequence_length: int = 8192,
               hidden_size: int = 2048,
               num_hidden_layers: int = 40,
               layer_types: Sequence[str] = _LAGUNA_LAYER_TYPES * 10,
               num_attention_heads_per_layer: Sequence[int] = (
                   (48, 64, 64, 64) * 10),
               num_key_value_heads: int = 8,
               head_dim: int = 128,
               sliding_window: int = 512,
               rope_parameters: Optional[Dict[str, Any]] = None,
               mlp_layer_types: Sequence[str] = (
                   ("dense",) + ("sparse",) * 39),
               intermediate_size: int = 8192,
               num_experts: int = 256,
               experts_held: Optional[int] = None,
               first_expert: int = 0,
               num_experts_per_tok: int = 8,
               moe_intermediate_size: int = 512,
               shared_expert_intermediate_size: int = 512,
               moe_routed_scaling_factor: float = 2.5,
               scoring_func: str = "sigmoid",
               norm_topk_prob: bool = True,
               rms_norm_eps: float = 1e-6,
               attention_impl: str = "auto",
               loss_block: int = 4096,
               device_dtype=jnp.bfloat16,
               remat_policy: Optional[str] = "full",
               **kwargs):
    """`rope_parameters` is the published block: for each layer type
    its `rope_theta`, `partial_rotary_factor` and `rope_type`
    (`default`, or `yarn` with `factor`,
    `original_max_position_embeddings`, `beta_fast`, `beta_slow`,
    `attention_factor`); it defaults to Laguna-XS.2's. `experts_held`
    defaults to all `num_experts`. The published file gives `gating:
    true` and no form, and no score function for its router: every
    layer has `GatedAttention`'s gate, the router's scores are
    `scoring_func` of the logits, the chosen weights renormalised
    where `norm_topk_prob` and multiplied by
    `moe_routed_scaling_factor`."""
    super().__init__(
        vocab_size=vocab_size, sequence_length=sequence_length,
        hidden_size=hidden_size, num_hidden_layers=num_hidden_layers,
        rms_norm_eps=rms_norm_eps, attention_impl=attention_impl,
        loss_block=loss_block, device_dtype=device_dtype,
        remat_policy=remat_policy, **kwargs)
    rope_parameters = (_LAGUNA_ROPE if rope_parameters is None
                       else rope_parameters)
    for name, per_layer in (
        ("layer_types", layer_types),
        ("num_attention_heads_per_layer", num_attention_heads_per_layer),
        ("mlp_layer_types", mlp_layer_types)):
      if len(per_layer) < num_hidden_layers:
        raise ValueError(f"{name} has {len(per_layer)} entries for "
                         f"{num_hidden_layers} layers")
    for kind in set(layer_types[:num_hidden_layers]):
      if kind not in (FULL_ATTENTION, SLIDING_ATTENTION):
        raise ValueError(f"Unknown layer type: {kind!r}")
      if rope_parameters[kind]["rope_type"] not in ("default", "yarn"):
        raise ValueError(f"Unknown rope_type for {kind}: "
                         f"{rope_parameters[kind]['rope_type']!r}")
    unknown = set(mlp_layer_types[:num_hidden_layers]) - {"dense",
                                                          "sparse"}
    if unknown:
      raise ValueError(f"Unknown mlp layer types: {sorted(unknown)}")
    self._layer_types = tuple(layer_types)
    self._num_attention_heads_per_layer = tuple(
        num_attention_heads_per_layer)
    self._num_key_value_heads = num_key_value_heads
    self._head_dim = head_dim
    self._sliding_window = sliding_window
    self._rope_parameters = rope_parameters
    self._mlp_layer_types = tuple(mlp_layer_types)
    self._intermediate_size = intermediate_size
    self._num_experts = num_experts
    self._experts_held = (num_experts if experts_held is None
                          else experts_held)
    self._first_expert = first_expert
    self._num_experts_per_tok = num_experts_per_tok
    self._moe_intermediate_size = moe_intermediate_size
    self._shared_expert_intermediate_size = (
        shared_expert_intermediate_size)
    self._moe_routed_scaling_factor = moe_routed_scaling_factor
    self._scoring_func = scoring_func
    self._norm_topk_prob = norm_topk_prob

  def _block(self, layer: int) -> TransformerBlock:
    dtype, eps = self.device_dtype, self._rms_norm_eps
    kind = self._layer_types[layer]
    rope = self._rope_parameters[kind]
    yarn = None
    if rope["rope_type"] == "yarn":
      yarn = YarnRope(**{name: rope[name] for name in YarnRope._fields})
    mixer = GatedAttention(
        num_heads=self._num_attention_heads_per_layer[layer],
        num_kv_heads=self._num_key_value_heads,
        head_dim=self._head_dim,
        rotary_dim=int(self._head_dim * rope["partial_rotary_factor"]),
        rope_theta=float(rope["rope_theta"]), yarn=yarn, eps=eps,
        window=(self._sliding_window if kind == SLIDING_ATTENTION
                else None),
        grouped_kv=True, attention_impl=self._attention_impl,
        dtype=dtype)
    if self._mlp_layer_types[layer] == "dense":
      ffn = GatedMLP(width=self._intermediate_size, dtype=dtype)
    else:
      ffn = SparseMoE(
          num_experts=self._num_experts,
          experts_held=self._experts_held,
          first_expert=self._first_expert,
          k=self._num_experts_per_tok,
          normalise_top_k=self._norm_topk_prob,
          scoring=self._scoring_func, selection_bias=False,
          routed_scaling_factor=self._moe_routed_scaling_factor,
          expert_width=self._moe_intermediate_size,
          shared_width=self._shared_expert_intermediate_size,
          shared_gated=False, dtype=dtype)
    return TransformerBlock(norm="rms", norm_eps=eps, mixer=mixer,
                            ffn=ffn, dtype=dtype)


# Kimi-Linear's published layer lists, 1-based: 27 layers of period 4,
# three KDA layers then one of latent attention; the last is layer 27.
_KIMI_LINEAR_ATTN = {
    "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
    "head_dim": 128,
    "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                   21, 22, 23, 25, 26],
    "num_heads": 32,
    "short_conv_kernel_size": 4}


@gin.configurable
class ChannelGatedDeltaLanguageModel(_LanguageModel):
  """A language model whose layers mix by the delta rule with a decay
  per key channel (Kimi Delta Attention) or by latent attention, over a
  leading dense feed-forward and mixtures of experts (the module's
  docstring); the defaults are Kimi-Linear-48B-A3B's published
  configuration, and the layer lists are as long as the published
  depth: a model of fewer layers reads their head."""

  def __init__(self,
               vocab_size: int = 163840,
               sequence_length: int = 8192,
               hidden_size: int = 2304,
               num_hidden_layers: int = 27,
               linear_attn_config: Optional[Dict[str, Any]] = None,
               num_attention_heads: int = 32,
               q_lora_rank: Optional[int] = None,
               kv_lora_rank: int = 512,
               qk_nope_head_dim: int = 128,
               qk_rope_head_dim: int = 64,
               v_head_dim: int = 128,
               mla_use_nope: bool = True,
               rope_theta: float = 10000.0,
               first_k_dense_replace: int = 1,
               intermediate_size: int = 9216,
               num_experts: int = 256,
               experts_held: Optional[int] = None,
               first_expert: int = 0,
               num_experts_per_token: int = 8,
               moe_renormalize: bool = True,
               moe_router_activation_func: str = "sigmoid",
               num_expert_group: int = 1,
               topk_group: int = 1,
               routed_scaling_factor: float = 2.446,
               num_shared_experts: int = 1,
               moe_intermediate_size: int = 1024,
               rms_norm_eps: float = 1e-5,
               attention_impl: str = "auto",
               loss_block: int = 4096,
               device_dtype=jnp.bfloat16,
               remat_policy: Optional[str] = "full",
               **kwargs):
    """`linear_attn_config` is the published block: `kda_layers` and
    `full_attn_layers` (1-based, together every layer once), and the
    linear layers' `num_heads`, `head_dim` and `short_conv_kernel_size`;
    it defaults to Kimi-Linear's. `experts_held` defaults to all
    `num_experts`. The router chooses among all experts (one group, as
    published; more are not here) by score plus a selection bias, a
    parameter that no gradient reaches, and weighs by the score alone.
    With `mla_use_nope` the latent attention turns nothing; without
    it, its `qk_rope_head_dim` dims by `rope_theta`."""
    super().__init__(
        vocab_size=vocab_size, sequence_length=sequence_length,
        hidden_size=hidden_size, num_hidden_layers=num_hidden_layers,
        rms_norm_eps=rms_norm_eps, attention_impl=attention_impl,
        loss_block=loss_block, device_dtype=device_dtype,
        remat_policy=remat_policy, **kwargs)
    linear = (_KIMI_LINEAR_ATTN if linear_attn_config is None
              else linear_attn_config)
    if (num_expert_group, topk_group) != (1, 1):
      raise ValueError("group-limited routing is not implemented: "
                       f"num_expert_group={num_expert_group}, "
                       f"topk_group={topk_group}")
    kda, full = set(linear["kda_layers"]), set(linear["full_attn_layers"])
    wanted = set(range(1, num_hidden_layers + 1))
    if kda & full or not wanted <= kda | full:
      raise ValueError(
          f"kda_layers and full_attn_layers must name each of the "
          f"{num_hidden_layers} layers once (1-based): "
          f"{sorted(kda & full)} are in both, "
          f"{sorted(wanted - kda - full)} in neither")
    self._linear_attn_config = linear
    self._num_attention_heads = num_attention_heads
    self._q_lora_rank = q_lora_rank
    self._kv_lora_rank = kv_lora_rank
    self._qk_nope_head_dim = qk_nope_head_dim
    self._qk_rope_head_dim = qk_rope_head_dim
    self._v_head_dim = v_head_dim
    self._mla_use_nope = mla_use_nope
    self._rope_theta = rope_theta
    self._first_k_dense_replace = first_k_dense_replace
    self._intermediate_size = intermediate_size
    self._num_experts = num_experts
    self._experts_held = (num_experts if experts_held is None
                          else experts_held)
    self._first_expert = first_expert
    self._num_experts_per_token = num_experts_per_token
    self._moe_renormalize = moe_renormalize
    self._moe_router_activation_func = moe_router_activation_func
    self._num_expert_group = num_expert_group
    self._topk_group = topk_group
    self._routed_scaling_factor = routed_scaling_factor
    self._num_shared_experts = num_shared_experts
    self._moe_intermediate_size = moe_intermediate_size

  def _block(self, layer: int) -> TransformerBlock:
    dtype, eps = self.device_dtype, self._rms_norm_eps
    linear = self._linear_attn_config
    if layer + 1 in linear["kda_layers"]:
      mixer = KimiDeltaAttention(
          num_heads=linear["num_heads"], head_dim=linear["head_dim"],
          conv_kernel=linear["short_conv_kernel_size"], eps=eps,
          dtype=dtype)
    else:
      mixer = LatentAttention(
          num_heads=self._num_attention_heads,
          q_lora_rank=self._q_lora_rank,
          kv_lora_rank=self._kv_lora_rank,
          qk_nope_head_dim=self._qk_nope_head_dim,
          qk_rope_head_dim=self._qk_rope_head_dim,
          v_head_dim=self._v_head_dim,
          rope_theta=None if self._mla_use_nope else self._rope_theta,
          eps=eps, attention_impl=self._attention_impl, dtype=dtype)
    if layer < self._first_k_dense_replace:
      ffn = GatedMLP(width=self._intermediate_size, dtype=dtype)
    else:
      ffn = SparseMoE(
          num_experts=self._num_experts,
          experts_held=self._experts_held,
          first_expert=self._first_expert,
          k=self._num_experts_per_token,
          normalise_top_k=self._moe_renormalize,
          scoring=self._moe_router_activation_func,
          selection_bias=True,
          routed_scaling_factor=self._routed_scaling_factor,
          expert_width=self._moe_intermediate_size,
          shared_width=(self._num_shared_experts
                        * self._moe_intermediate_size),
          shared_gated=False, dtype=dtype)
    return TransformerBlock(norm="rms", norm_eps=eps, mixer=mixer,
                            ffn=ffn, dtype=dtype)
