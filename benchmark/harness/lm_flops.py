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



def attention_kernel_costs(model: dict, batch: int, positions: int,
                           bytes_per_element: int = 2
                           ) -> Dict[str, Dict[str, float]]:
  """One call of each Pallas program of `ops/flash_attention.py` under
  `GatedAttention`, on `batch` rows of `positions` positions, causal:
  the FLOPs of the products it makes over the pairs the mask keeps
  (the backward programs make the scores, and dO V^T, anew each), for
  all `num_attention_heads` query heads, and the bytes it must move at
  the least: each operand read once, each result written once, the
  two row vectors (logsumexp, delta) in float32. Grouped queries: the
  mathematics has `num_key_value_heads` keys and values of `head_dim`,
  so k, v, dk and dv count at that many heads, whatever the program
  repeats before the kernel."""
  s = _dims(model)
  d = s["d"]
  pairs = batch * s["h"] * positions * (positions + 1) / 2
  queries = batch * s["h"] * positions * d * bytes_per_element
  keys = batch * s["kv"] * positions * d * bytes_per_element
  row_vector = batch * s["h"] * positions * 4
  return {
      # s = q k^T; o = p v.  Reads q, k, v; writes o and the logsumexp.
      "forward": {"flops": pairs * 2 * 2 * d,
                  "bytes": 2 * queries + 2 * keys + row_vector},
      # s; dv = p^T dO; dp = dO v^T; dk = ds^T q.  Reads q, k, v, dO
      # and the two row vectors; writes dk and dv.
      "dkdv": {"flops": pairs * 2 * 4 * d,
               "bytes": 2 * queries + 4 * keys + 2 * row_vector},
      # s; dp; dq = ds k.  Reads the same; writes dq.
      "dq": {"flops": pairs * 2 * 3 * d,
             "bytes": 3 * queries + 2 * keys + 2 * row_vector},
  }


def walk_kernel_costs(model: dict, rows: int, positions: int,
                      bytes_per_element: int = 2
                      ) -> Dict[str, Dict[str, float]]:
  """One call of each Pallas program of `ops/delta_rule_walk.py` on
  `rows` rows of `positions` positions, all value heads, in chunks of
  `CHUNK`: the FLOPs of its products and the bytes it must move at the
  least, a head and chunk (C = CHUNK, the state [dk, dv] stays on the
  chip; `writes`, `new`, `carried` and their cotangents are float32
  [C, dv], the three key operands and their cotangents [C, dk] in the
  products' type, `end_decay` and its cotangent one float32):

    forward: k_decayed S, q_decayed S, k_to_end^T new: 3 products of
      2 C dk dv. Reads `writes` and the three operands, writes `new`
      and `carried`: 144 KB at the cell's widths in bfloat16.
    forward_saving_states: the same, and the state at the chunk's
      start written in float32 for the backward program (208 KB): what
      a forward call costs that a backward call follows.
    backward: six products of 2 C dk dv. Reads the three operands,
      `new`, the saved state, d `new`, d `carried`; writes d `writes`
      and the three operands' cotangents (288 KB).
  """
  s = _dims(model)
  dk, dv, c = s["dk"], s["dv"], CHUNK
  units = rows * s["hv"] * -(-positions // c)  # heads times chunks
  product = 2 * c * dk * dv
  wide = c * dv * 4  # a float32 [C, dv] tile
  operand = c * dk * bytes_per_element
  state = dk * dv * 4
  forward = wide + 3 * operand + 4 + 2 * wide
  return {
      "forward": {"flops": units * 3 * product,
                  "bytes": units * forward},
      "forward_saving_states": {"flops": units * 3 * product,
                                "bytes": units * (forward + state)},
      "backward": {"flops": units * 6 * product,
                   "bytes": units * (3 * operand + 4 + wide + state
                                     + 2 * wide + wide + 3 * operand
                                     + 4)},
  }
