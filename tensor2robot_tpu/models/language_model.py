"""Next-token language modelling as a T2R model: token ids in, the mean
cross-entropy of every position's successor out, trained by
`train_eval_model` like every other family.

The network is an embedding, a `layers/transformer.SequenceTrunk` whose
blocks are data, a final norm and an untied head. The block pattern is
the hybrid one of Qwen3-Next (the shipped gin file,
`models/configs/train_qwen3_next.gin`, binds its published widths):
layer i mixes with gated grouped-query attention where
`(i + 1) % full_attention_interval == 0` and with a Gated DeltaNet
otherwise (`layers/gated_delta.py`), and every layer's feed-forward is
a dropless mixture of experts beside a gated shared expert
(`parallel/moe.SparseMoE`). The constructor's arguments carry the
names of the published configuration's keys, so a configuration file
and the model read alike.

A chip's share of an expert-parallel deployment (docs/SEQUENCE.md):
`num_experts` is the router's width, `experts_held` how many of them
this model holds from `first_expert` on; `vocab_size` is the slice of
the vocabulary held (embedding, head, logits and loss are over it).

The loss is computed a block of positions at a time, each block under
`jax.checkpoint`: float32 logits of 32,768 positions by 18,992 ids
never stand whole, forward or backward.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu import config as gin
from tensor2robot_tpu.data.abstract_input_generator import Mode
from tensor2robot_tpu.layers.gated_delta import GatedDeltaNet
from tensor2robot_tpu.layers.transformer import (
    GatedAttention,
    SequenceTrunk,
    TransformerBlock,
)
from tensor2robot_tpu.models.abstract_model import AbstractT2RModel
from tensor2robot_tpu.parallel.moe import SparseMoE
from tensor2robot_tpu.specs import ExtendedTensorSpec, TensorSpecStruct

TOKEN_IDS = "token_ids"
NEXT_TOKEN_LOGITS = "next_token_logits"
MOE_COUNTERS = "moe_counters"


def next_token_loss(hidden: jax.Array, head: jax.Array,
                    targets: jax.Array, block: int,
                    dtype: Any) -> jax.Array:
  """Mean over N positions of logsumexp(h W) - (h W)[target], a block
  of `block` positions at a time (all at once where `block` does not
  divide N). hidden [N, M], head [M, V], targets [N]."""
  n, width = hidden.shape
  if n % block:
    block = n

  @jax.checkpoint
  def block_loss(h, t):
    logits = jnp.dot(h.astype(dtype), head.astype(dtype),
                     preferred_element_type=jnp.float32)
    picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

  sums = jax.lax.map(
      lambda ht: block_loss(*ht),
      (hidden.reshape(n // block, block, width),
       targets.reshape(n // block, block)))
  return jnp.sum(sums) / n


class LanguageModelNetwork(nn.Module):
  """token ids [B, T + 1] -> the loss of predicting ids[:, 1:] from
  ids[:, :-1], and the logits after the last input position."""

  vocab_size: int
  hidden_size: int
  trunk: nn.Module
  loss_block: int = 4096
  dtype: Any = jnp.bfloat16

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
    return {"loss": loss, NEXT_TOKEN_LOGITS: last}


@gin.configurable
class NextTokenLanguageModel(AbstractT2RModel):
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
    """`remat_policy` is applied to each layer of the trunk, not to the
    whole loss: the backward pass holds one layer's activations at a
    time (`SequenceTrunk`). `experts_held` defaults to all
    `num_experts`."""
    super().__init__(device_dtype=device_dtype,
                     remat_policy=remat_policy, **kwargs)
    self._vocab_size = vocab_size
    self._sequence_length = sequence_length
    self._hidden_size = hidden_size
    self._num_hidden_layers = num_hidden_layers
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

  def create_network(self) -> nn.Module:
    return LanguageModelNetwork(
        vocab_size=self._vocab_size, hidden_size=self._hidden_size,
        trunk=SequenceTrunk(
            blocks=tuple(self._block(i)
                         for i in range(self._num_hidden_layers)),
            remat_policy=self._remat_policy),
        loss_block=self._loss_block, dtype=self.device_dtype)

  def _loss_for_grad(self):
    return self.loss_fn  # the trunk checkpoints layer by layer

  def inference_network_fn(self, variables, features, mode: Mode,
                           rng: Optional[jax.Array] = None):
    """The network's outputs with the routing counters of its expert
    layers beside them (`parallel/moe.held_experts_ffn`), reduced over
    the layers: the mean share of assignments that fall on held
    experts, the worst load imbalance, the sum of dropped
    assignments."""
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
              "expert_load_max_over_mean": jnp.max,
              "dropped_assignments": jnp.sum}
    outputs[MOE_COUNTERS] = {
        f"moe.{name}": reduce[name](jnp.stack(values))
        for name, values in per_layer.items()}
    return outputs, variables.get("batch_stats", {})

  def model_train_fn(self, features, labels, outputs, mode
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    return outputs["loss"], dict(outputs[MOE_COUNTERS])

  def predict_step(self, state, features):
    outputs = super().predict_step(state, features)
    return {NEXT_TOKEN_LOGITS: outputs[NEXT_TOKEN_LOGITS]}
