"""Model FLOPs of the latent-attention language model's training step
(`LatentAttentionLanguageModel` of
`tensor2robot_tpu/models/language_model.py`), and the FLOPs and HBM
bytes of one call of each of the attention kernel's three Pallas
programs: the benchmark's own count from a configuration's `model`
block, what the equations need, whatever the program does to get it.

Counted, forward, per position of a sequence of T (a multiply-add is
2): the latent attention's five projections; causal attention (Q K^T
at the keys' width nope + rope, P V at the values' width, over the
T (T + 1) / 2 pairs that the mask keeps); the dense feed-forward of the
leading layers; in every other layer the router, the routed experts AS
ROUTED (a gated unit for each assignment that falls on an expert held
here: the share of assignments is an argument, so a run's measured
share can stand in for the uniform held / routed) and the shared
experts; the head. The multi-token-prediction module is `eh_proj`, one
more expert layer and the head again over the T - 1 positions that
have a successor's successor. Elementwise work (norms, activations,
softmax, rotary) is not counted. A step is three forward passes' worth
(backward twice the forward); recomputation under `jax.checkpoint` or
inside a kernel is the program's business and not model FLOPs.
"""

from typing import Dict, Optional


def _mla_projections(model: dict) -> float:
  m, h = model["hidden_size"], model["num_attention_heads"]
  nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
  q_rank, kv_rank = model["q_lora_rank"], model["kv_lora_rank"]
  return 2.0 * (m * q_rank + q_rank * h * (nope + rope)
                + m * (kv_rank + rope)
                + kv_rank * h * (nope + model["v_head_dim"])
                + h * model["v_head_dim"] * m)


def forward_flops_per_position(model: dict,
                               assignments_here_share:
                               Optional[float] = None
                               ) -> Dict[str, float]:
  """Forward model FLOPs of one position, by part, averaged over the
  sequence's T positions and summed over the layers."""
  m, t = model["hidden_size"], model["sequence_length"]
  h = model["num_attention_heads"]
  layers = model["num_hidden_layers"]
  dense_layers = min(model["first_k_dense_replace"], layers)
  mtp = 1 if model["num_nextn_predict_layers"] else 0
  # Of a sequence's T positions, T - 1 go through the module.
  mtp_positions = mtp * (t - 1) / t
  expert_layers = layers - dense_layers + mtp_positions
  if assignments_here_share is None:
    assignments_here_share = (model["experts_held"]
                              / model["n_routed_experts"])
  f = model["moe_intermediate_size"]
  # n (n + 1) / 2 pairs over a sequence of n: (T + 1) / 2 a position in
  # the trunk, (T - 1) T / 2 / T in the module.
  pairs = layers * (t + 1) / 2 + mtp * (t - 1) / 2
  return {
      "mla_projections": (layers + mtp_positions)
                         * _mla_projections(model),
      # Q K^T at the keys' width and P V at the values', a pair and head.
      "mla_attention": 2.0 * (model["qk_nope_head_dim"]
                              + model["qk_rope_head_dim"]
                              + model["v_head_dim"]) * h * pairs,
      "dense_ffn": dense_layers * 3 * 2 * m * model["intermediate_size"],
      "router": expert_layers * 2 * m * model["n_routed_experts"],
      "routed_experts": expert_layers * model["num_experts_per_tok"]
                        * assignments_here_share * 3 * 2 * m * f,
      "shared_experts": expert_layers * model["n_shared_experts"]
                        * 3 * 2 * m * f,
      "mtp_combine": mtp_positions * 2 * 2 * m * m,
      "heads": (1 + mtp_positions) * 2 * m * model["vocab_size"],
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
  of the products it makes over the pairs the mask keeps (the backward
  programs make the scores, and dO V^T, anew each), and the bytes it
  must move at the least: each operand read once, each result written
  once, the two row vectors (logsumexp, delta) in float32. q, k, dq and
  dk are nope + rope wide; v, o, dO and dv are `v_head_dim` wide: a
  value padded to the keys' width would add (nope + rope - v) / v to
  every term in dv below."""
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
      # s; dv = p^T dO; dp = dO v^T; dk = ds^T q.  Reads q, k, v, dO
      # and the two row vectors; writes dk and dv.
      "dkdv": {"flops": pairs * 2 * (2 * dk + 2 * dv),
               "bytes": 3 * wide + 3 * narrow + 2 * row_vector},
      # s; dp; dq = ds k.  Reads the same; writes dq.
      "dq": {"flops": pairs * 2 * (2 * dk + dv),
             "bytes": 3 * wide + 2 * narrow + 2 * row_vector},
  }
