"""Model FLOPs of the channel-gated delta-rule language model's
training step (`ChannelGatedDeltaLanguageModel` of
`tensor2robot_tpu/models/language_model.py`: Kimi Delta Attention
beside latent attention), and the FLOPs and least HBM bytes of one call
of each Pallas program it runs: the benchmark's own count from a
configuration's `model` block, what the equations need, whatever the
program does to get it.

Counted, forward, per position of a sequence of T (a multiply-add is
2), layer by layer as `linear_attn_config`'s two lists say. A KDA
layer: its projections (q, k, v, the two low-rank gates, beta, the
output), its three convolutions, and the delta rule in its chunked
form at chunk C = 64 (per chunk and head: K K^T and Q K^T under the
decays, the triangular solve by forward substitution applied to
[v | k], the two products with the carried state, the product within
the chunk and the state's update: a decay per channel changes none of
the products' sizes). A latent-attention layer: its four projections
(no query latent) and causal attention (Q K^T at the keys' width
nope + rope, P V at the values', over the T (T + 1) / 2 pairs the mask
keeps). The dense feed-forward of the leading layers; in every other
layer the router, the routed experts AS ROUTED (a gated unit for each
assignment that falls on an expert held here: the share of assignments
is an argument, so a run's measured share can stand in for the uniform
held / routed) and the shared experts; the head. Elementwise work
(norms, activations, softmax, the decays' exponentials) is not counted.
A step is three forward passes' worth (backward twice the forward);
recomputation under `jax.checkpoint` or inside a kernel is the
program's business and not model FLOPs.
"""

from typing import Dict, Optional

CHUNK = 64


def layer_counts(model: dict):
  """(KDA layers, latent-attention layers) among the model's first
  `num_hidden_layers`; the published lists are 1-based."""
  kda = sum(i + 1 in model["linear_attn_config"]["kda_layers"]
            for i in range(model["num_hidden_layers"]))
  return kda, model["num_hidden_layers"] - kda


def forward_flops_per_position(model: dict,
                               assignments_here_share:
                               Optional[float] = None
                               ) -> Dict[str, float]:
  """Forward model FLOPs of one position, by part, averaged over the
  sequence's T positions and summed over the layers."""
  m, t, c = model["hidden_size"], model["sequence_length"], CHUNK
  linear = model["linear_attn_config"]
  hl, d = linear["num_heads"], linear["head_dim"]
  rank = d  # the two low-rank gates' (the configuration's `assumed`)
  kda_layers, mla_layers = layer_counts(model)
  layers = model["num_hidden_layers"]
  dense_layers = min(model["first_k_dense_replace"], layers)
  expert_layers = layers - dense_layers
  kda_proj = (2 * m * 3 * hl * d                      # q, k, v
              + 2 * (2 * m * rank + 2 * rank * hl * d)  # the two gates
              + 2 * m * hl                            # beta
              + 2 * hl * d * m)                       # the output
  conv = 2 * linear["short_conv_kernel_size"] * 3 * hl * d
  # Per position and head, as `lm_flops`' scalar-gated rule (Dk = Dv).
  rule = hl * (4 * c * d + c * 2 * d + 2 * c * c / 3 + 6 * d * d
               + 2 * c * d)
  h = model["num_attention_heads"]
  nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
  dv, kv_rank = model["v_head_dim"], model["kv_lora_rank"]
  mla_proj = 2.0 * (m * h * (nope + rope) + m * (kv_rank + rope)
                    + kv_rank * h * (nope + dv) + h * dv * m)
  if assignments_here_share is None:
    assignments_here_share = (model["experts_held"]
                              / model["num_experts"])
  f = model["moe_intermediate_size"]
  return {
      "kda_projections": kda_layers * (kda_proj + conv),
      "kda_rule": kda_layers * rule,
      "mla_projections": mla_layers * mla_proj,
      # Q K^T at the keys' width and P V at the values', a pair and head.
      "mla_attention": mla_layers * 2.0 * (nope + rope + dv) * h
                       * (t + 1) / 2,
      "dense_ffn": dense_layers * 3 * 2 * m * model["intermediate_size"],
      "router": expert_layers * 2 * m * model["num_experts"],
      "routed_experts": expert_layers * model["num_experts_per_token"]
                        * assignments_here_share * 3 * 2 * m * f,
      "shared_experts": expert_layers * model["num_shared_experts"]
                        * 3 * 2 * m * f,
      "head": 2 * m * model["vocab_size"],
  }


def step_flops(model: dict, batch: int,
               assignments_here_share: Optional[float] = None) -> float:
  """Model FLOPs of one training step on `batch` rows: forward once,
  backward twice that."""
  forward = sum(forward_flops_per_position(
      model, assignments_here_share).values())
  return 3.0 * forward * batch * model["sequence_length"]


def attention_kernel_costs(model: dict, batch: int, positions: int,
                           bytes_per_element: int = 2
                           ) -> Dict[str, Dict[str, float]]:
  """One call of each Pallas program of `ops/flash_attention.py` on
  `batch` rows of `positions` positions, all heads, causal: the FLOPs
  of the products it makes over the pairs the mask keeps, and the bytes
  it must move at the least: each operand read once, each result
  written once, the two row vectors (logsumexp, delta) in float32. q,
  k, dq and dk are nope + rope wide; v, o, dO and dv `v_head_dim`.
  `backward` is the ONE fused program that makes dK, dV and dQ from a
  score tile made once (five products a pair); `dkdv` and `dq` the pair
  of programs that run where its accumulators do not fit (each makes
  the scores and dO V^T anew)."""
  h = model["num_attention_heads"]
  dk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
  dv = model["v_head_dim"]
  pairs = batch * h * positions * (positions + 1) / 2
  rows = batch * h * positions
  wide, narrow = rows * dk * bytes_per_element, \
      rows * dv * bytes_per_element
  row_vector = rows * 4
  return {
      # s = q k^T; o = p v.  Reads q, k, v; writes o and the logsumexp.
      "forward": {"flops": pairs * 2 * (dk + dv),
                  "bytes": 2 * wide + 2 * narrow + row_vector},
      # s; dp = dO v^T; dv = p^T dO; dk = ds^T q; dq = ds k.  Reads q,
      # k, v, dO and the two row vectors; writes dq, dk and dv.
      "backward": {"flops": pairs * 2 * (3 * dk + 2 * dv),
                   "bytes": 4 * wide + 3 * narrow + 2 * row_vector},
      "dkdv": {"flops": pairs * 2 * (2 * dk + 2 * dv),
               "bytes": 3 * wide + 3 * narrow + 2 * row_vector},
      "dq": {"flops": pairs * 2 * (2 * dk + dv),
             "bytes": 3 * wide + 2 * narrow + 2 * row_vector},
  }


def walk_kernel_costs(model: dict, rows: float, positions: int,
                      bytes_per_element: int = 2
                      ) -> Dict[str, Dict[str, float]]:
  """One call of each Pallas program of `ops/delta_rule_walk.py` on
  `rows` rows of `positions` positions, all heads (a share of one row
  where a call takes a group of its heads: the cost is linear in the
  heads), in chunks of `CHUNK`, where `end_decay` is a vector over the
  key channels: the
  FLOPs of its products and the bytes it must move at the least, a head
  and chunk (the state [D, D] stays on the chip; `writes`, `new`,
  `carried` and their cotangents are float32 [C, D], the three key
  operands and their cotangents [C, D] in the products' type,
  `end_decay` and its cotangent D float32 each). The programs and
  their products are `lm_flops.walk_kernel_costs`'. No reader costs a
  traced call by it yet: inside the cell's step a call of 8 heads takes
  0.113 ms where the HBM needs 0.18 for these bytes and the program
  alone takes 0.243 (PERF.md section 5, PR 47), so a share of this
  bound read 103.6 and `lm_kda_walk_roofline` was withheld."""
  linear = model["linear_attn_config"]
  d, c = linear["head_dim"], CHUNK
  units = rows * linear["num_heads"] * -(-positions // c)
  product = 2 * c * d * d
  wide = c * d * 4  # a float32 [C, D] tile
  operand = c * d * bytes_per_element
  state, decay = d * d * 4, d * 4
  forward = wide + 3 * operand + decay + 2 * wide
  return {
      "forward": {"flops": units * 3 * product,
                  "bytes": units * forward},
      "forward_saving_states": {"flops": units * 3 * product,
                                "bytes": units * (forward + state)},
      "backward": {"flops": units * 6 * product,
                   "bytes": units * (3 * operand + decay + wide + state
                                     + 2 * wide + wide + 3 * operand
                                     + decay)},
  }
