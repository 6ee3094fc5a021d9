"""Model FLOPs and bytes of the hybrid language model's training step
(`tensor2robot_tpu/models/language_model.py`), the benchmark's own
count from a configuration's `model` block: what the equations need,
whatever the program does to get it.

Counted, forward, per sequence of T positions (a multiply-add is 2):
every projection (2 * in * out a position); the depthwise convolution
(2 * kernel * channels); the gated delta rule in its chunked form at
chunk C = 64 (per chunk and value head: K K^T, Q K^T, the triangular
solve by forward substitution applied to [v | k], the two products
with the carried state, the product within the chunk and the state's
update); causal attention (Q K^T and P V over the T (T + 1) / 2 pairs
that the mask keeps); the router; the routed experts AS ROUTED (a
gated unit for each assignment that falls on an expert held here: the
share of assignments is an argument, so a run's measured share can
stand in for the uniform held / num_experts); the shared expert and
its gate; the head. Elementwise work (norms, activations, softmax,
rotary) is not counted. A step is three forward passes' worth
(backward twice the forward); recomputation under `jax.checkpoint` is
the program's business and not model FLOPs.
"""

from typing import Dict, Optional

CHUNK = 64


def _dims(model: dict):
  return dict(
      m=model["hidden_size"], t=model["sequence_length"],
      hk=model["linear_num_key_heads"],
      hv=model["linear_num_value_heads"],
      dk=model["linear_key_head_dim"], dv=model["linear_value_head_dim"],
      h=model["num_attention_heads"], kv=model["num_key_value_heads"],
      d=model["head_dim"])


def forward_flops_per_position(model: dict,
                               assignments_here_share:
                               Optional[float] = None
                               ) -> Dict[str, float]:
  """Forward model FLOPs of one position, by part, averaged over the
  sequence (causal attention's pairs) and summed over the layers."""
  s = _dims(model)
  m, t, c = s["m"], s["t"], CHUNK
  layers = model["num_hidden_layers"]
  attention_layers = sum(
      (i + 1) % model["full_attention_interval"] == 0
      for i in range(layers))
  delta_layers = layers - attention_layers
  key_dim, value_dim = s["hk"] * s["dk"], s["hv"] * s["dv"]
  delta_proj = 2 * m * (2 * key_dim + 2 * value_dim + 2 * s["hv"]) \
      + 2 * value_dim * m
  conv = 2 * model["linear_conv_kernel_dim"] * (2 * key_dim + value_dim)
  dk, dv = s["dk"], s["dv"]
  # Per position and value head: K K^T and Q K^T (2 C dk each), the
  # solve applied to v and k (C (dk + dv): a triangle of C^2 / 2
  # multiply-adds a column), its own substitution (C^2 / 3), k S and
  # q S and the update k^T v (2 dk dv each), the product in the chunk
  # (2 C dv).
  rule = s["hv"] * (4 * c * dk + c * (dk + dv) + 2 * c * c / 3
                    + 6 * dk * dv + 2 * c * dv)
  attention_proj = 2 * m * (2 * s["h"] * s["d"] + 2 * s["kv"] * s["d"]) \
      + 2 * s["h"] * s["d"] * m
  # (T + 1) / 2 keys a query on average, two products of 2 d each.
  attention = 2 * 2 * s["d"] * s["h"] * (t + 1) / 2
  if assignments_here_share is None:
    assignments_here_share = model["experts_held"] / model["num_experts"]
  f, fs = (model["moe_intermediate_size"],
           model["shared_expert_intermediate_size"])
  routed = (model["num_experts_per_tok"] * assignments_here_share
            * 3 * 2 * m * f)
  return {
      "gated_delta_projections": delta_layers * (delta_proj + conv),
      "gated_delta_rule": delta_layers * rule,
      "attention_projections": attention_layers * attention_proj,
      "attention": attention_layers * attention,
      "router": layers * 2 * m * model["num_experts"],
      "routed_experts": layers * routed,
      "shared_expert": layers * (3 * 2 * m * fs + 2 * m),
      "head": 2 * m * model["vocab_size"],
  }


def step_flops(model: dict, batch: int,
               assignments_here_share: Optional[float] = None) -> float:
  """Model FLOPs of one training step on `batch` rows: forward once,
  backward twice that."""
  forward = sum(forward_flops_per_position(
      model, assignments_here_share).values())
  return 3.0 * forward * batch * model["sequence_length"]

