"""Model FLOPs of the windowed-attention language model's training step
(`WindowedAttentionLanguageModel` of
`tensor2robot_tpu/models/language_model.py`), and the FLOPs and HBM
bytes of one call of each of the attention kernel's three Pallas
programs on a full layer and on a sliding-window layer: the
benchmark's own count from a configuration's `model` block, what the
equations need, whatever the program does to get it.

Counted, forward, per position of a sequence of T (a multiply-add is
2), layer by layer as `layer_types` and `mlp_layer_types` say: the
attention's projections at the layer's own number of query heads
(queries and gates, keys, values, the output); Q K^T and P V over the
pairs that the layer's mask keeps, T (T + 1) / 2 under the causal mask
and sum_i min(i + 1, window) under the band; the dense feed-forward;
in a sparse layer the router, the routed experts AS ROUTED (a gated
unit for each assignment that falls on an expert held here: the share
of assignments is an argument, so a run's measured share can stand in
for the uniform held / routed) and the shared expert; the head.
Elementwise work (norms, gates, softmax, rotary) is not counted. A
step is three forward passes' worth (backward twice the forward);
recomputation under `jax.checkpoint` or inside a kernel is the
program's business and not model FLOPs.
"""

from typing import Dict, Optional

FULL, SLIDING = "full_attention", "sliding_attention"


def seen_pairs(kind: str, positions: int, window: int) -> float:
  """The (query, key) pairs one head keeps over `positions` positions:
  the causal triangle, or the band of `window` under it."""
  if kind == FULL or window >= positions:
    return positions * (positions + 1) / 2
  # The first `window` rows see 1 .. window keys, the others `window`.
  return window * (window + 1) / 2 + (positions - window) * window


def heads_of(model: dict, kind: str) -> int:
  """The query heads of the layers of `kind`: one number, or the kind's
  calls have no one cost."""
  heads = {model["num_attention_heads_per_layer"][i]
           for i in range(model["num_hidden_layers"])
           if model["layer_types"][i] == kind}
  if len(heads) != 1:
    raise ValueError(f"layers of {kind} have {sorted(heads)} query "
                     "heads: a call's cost needs one number")
  return heads.pop()


def forward_flops_per_position(model: dict,
                               assignments_here_share:
                               Optional[float] = None
                               ) -> Dict[str, float]:
  """Forward model FLOPs of one position, by part, averaged over the
  sequence's T positions and summed over the layers."""
  m, t = model["hidden_size"], model["sequence_length"]
  d, kv = model["head_dim"], model["num_key_value_heads"]
  if assignments_here_share is None:
    assignments_here_share = (model["experts_held"]
                              / model["num_experts"])
  f = model["moe_intermediate_size"]
  parts = dict.fromkeys(
      ("full_projections", "full_attention", "window_projections",
       "window_attention", "dense_ffn", "router", "routed_experts",
       "shared_experts"), 0.0)
  for i in range(model["num_hidden_layers"]):
    kind = model["layer_types"][i]
    h = model["num_attention_heads_per_layer"][i]
    name = "full" if kind == FULL else "window"
    # q and gate, k and v, o.
    parts[f"{name}_projections"] += 2.0 * m * d * (2 * h + 2 * kv + h)
    # Q K^T and P V, each `d` wide, a pair and head.
    parts[f"{name}_attention"] += 2.0 * 2 * d * h * seen_pairs(
        kind, t, model["sliding_window"]) / t
    if model["mlp_layer_types"][i] == "dense":
      parts["dense_ffn"] += 3 * 2.0 * m * model["intermediate_size"]
    else:
      parts["router"] += 2.0 * m * model["num_experts"]
      parts["routed_experts"] += (model["num_experts_per_tok"]
                                  * assignments_here_share
                                  * 3 * 2.0 * m * f)
      parts["shared_experts"] += (
          3 * 2.0 * m * model["shared_expert_intermediate_size"])
  parts["head"] = 2.0 * m * model["vocab_size"]
  return parts


def step_flops(model: dict, batch: int,
               assignments_here_share: Optional[float] = None) -> float:
  """Model FLOPs of one training step on `batch` rows: forward once,
  backward twice that."""
  forward = sum(forward_flops_per_position(
      model, assignments_here_share).values())
  return 3.0 * forward * batch * model["sequence_length"]


def _kernel_costs(model: dict, kind: str, batch: int, positions: int,
                  bytes_per_element: int) -> Dict[str, Dict[str, float]]:
  h, kv = heads_of(model, kind), model["num_key_value_heads"]
  d = model["head_dim"]
  pairs = batch * h * seen_pairs(kind, positions,
                                 model["sliding_window"])
  rows = batch * positions
  per_query = rows * h * d * bytes_per_element   # q, o, dO, dq
  per_key = rows * kv * d * bytes_per_element    # k, v, dk, dv
  row_vector = rows * h * 4                      # logsumexp, delta
  return {
      # s = q k^T; o = p v.  Reads q, k, v; writes o and the logsumexp.
      "forward": {"flops": pairs * 2 * 2 * d,
                  "bytes": 2 * per_query + 2 * per_key + row_vector},
      # s; dv = p^T dO; dp = dO v^T; dk = ds^T q.  Reads q, k, v, dO
      # and the two row vectors; writes dk and dv.
      "dkdv": {"flops": pairs * 2 * 4 * d,
               "bytes": 2 * per_query + 4 * per_key + 2 * row_vector},
      # s; dp; dq = ds k.  Reads the same; writes dq.
      "dq": {"flops": pairs * 2 * 3 * d,
             "bytes": 3 * per_query + 2 * per_key + 2 * row_vector},
  }


def attention_kernel_costs(model: dict, batch: int, positions: int,
                           bytes_per_element: int = 2
                           ) -> Dict[str, Dict[str, float]]:
  """One call of each Pallas program of `ops/flash_attention.py` for a
  full-attention layer on `batch` rows of `positions` positions, all
  its query heads: the FLOPs of the products it makes over the pairs
  the causal mask keeps (the backward programs make the scores, and dO
  V^T, anew each), and the bytes it must move at the least: each
  operand read once, each result written once, keys and values (and
  their gradients) at their own 8 heads, not repeated to the query
  heads, the two row vectors (logsumexp, delta) in float32."""
  return _kernel_costs(model, FULL, batch, positions, bytes_per_element)


def window_kernel_costs(model: dict, batch: int, positions: int,
                        bytes_per_element: int = 2
                        ) -> Dict[str, Dict[str, float]]:
  """`attention_kernel_costs` for a sliding-window layer: the products
  over the band's pairs alone, sum_i min(i + 1, window) a head, so that
  the share reads the same work whatever tiles implement it."""
  return _kernel_costs(model, SLIDING, batch, positions,
                       bytes_per_element)
