"""Plain reference of Laguna-XS.2's next-token loss, written from the
layer equations (ISSUE 43, Tentpole; the published `config.json` of
poolside/Laguna-XS.2 gives every size, YaRN's report, arXiv:2309.00071,
the blended frequencies) and nothing of the program: float32, every
matrix product at `highest`, no kernels, no sort.

  layer i:  x <- x + attention_i(norm(x));  x <- x + ffn_i(norm(x))
            norms are x / rms(x) * (1 + w)
  attention_i (`layer_types[i]`): H_i = `num_attention_heads_per_layer
            [i]` query heads and a gate each from one projection
            (columns: queries | gates), 8 key-value heads; norms over
            the head dimension of q and of k; rotary (rotate-half) over
            the first `partial_rotary_factor` of a head's dims with the
            layer type's frequencies; query head h attends over
            key-value head h // (H_i / 8), position i over the keys j
            with 0 <= i - j (full_attention) or 0 <= i - j <
            `sliding_window` (sliding_attention), the mask built from
            the positions, materialised a block of queries at a time;
            out * sigmoid(gate); W_o
  rotary frequencies over d dims: f_j = theta^(-2 j / d), j < d / 2.
            `rope_type` yarn, L = original_max_position_embeddings:
            pair(beta) = d ln(L / (2 pi beta)) / (2 ln theta);
            low = max(floor(pair(beta_fast)), 0),
            high = min(ceil(pair(beta_slow)), d - 1);
            r_j = clip((j - low) / (high - low), 0, 1);
            f'_j = (1 - r_j) f_j + r_j f_j / factor;
            cos and sin times `attention_factor`
  ffn_i (`mlp_layer_types[i]`): dense: down(silu(gate x) * up x); sparse:
            s = sigmoid(x W_r) over ALL experts; chosen = the k largest
            of s; w = scale * s[chosen] / sum(s[chosen]); sum over the
            chosen experts THAT ARE HELD (`first_expert .. first_expert
            + experts_held - 1`), by a loop over the held experts with
            masks, of w_e down_e(silu(gate_e x) * up_e x); plus the
            shared expert, the same unit, ungated
  loss:     final norm, untied head over the vocabulary slice, mean
            next-token cross-entropy over every position

A row of the batch is one document (no packing), so the loss is the
mean of the rows' losses. Each layer runs over all rows: its attention
and its dense feed-forward a row at a time (`lax.map`, each row under
`jax.checkpoint`), its experts over all rows' tokens at once under one
`jax.checkpoint`; the head a row at a time. Beside `follow`'s 16 bytes
a parameter stand every row's input to each mixer and feed-forward and
one row's activations of one of them.

`control` is the same one precision lower: every matrix product's
operands rounded to float8 (e4m3, scaled to the tensor's largest
magnitude) AND the elementwise math (norms, softmax, sigmoid, SiLU) in
bfloat16. `True` lowers everything; `"attention"` the attention alone
(projections, norms, scores, softmax, gate), `"router"` the router's
product and sigmoid alone.

Departures from the published model, as the configuration file states
them under `assumed`: the form of the gate, the router's score
function and the norms on q and k are not in the published file; the
layout of `q_proj`'s columns (queries | gates) is this benchmark's.

The rounding helpers are the Qwen3-Next reference's, and the dense
unit, the head's loss and the naming of a lowered part the JoyAI
reference's, imported: one definition of "one precision lower" for
every cell.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference.joyai_llm_flash import (
    _dense_ffn,
    _head_loss,
    _lowered,
    _sub,
)
from benchmark.reference.qwen3_next import (
    F32,
    _dot,
    _ew,
    _gated_unit,
    _rms_norm,
    _sigmoid,
)

QUERY_BLOCK = 256  # queries whose scores stand at once (64 heads)
SLIDING = "sliding_attention"



def rotary_frequencies(rotary_dim: int, rope: dict):
  """(the d / 2 pairs' frequencies, the amplitude of cos and sin) of
  one layer type's `rope_parameters` block."""
  theta, d = float(rope["rope_theta"]), rotary_dim
  pairs = jnp.arange(d // 2, dtype=F32)
  freq = theta ** (-2.0 * pairs / d)
  if rope["rope_type"] != "yarn":
    return freq, 1.0
  context = rope["original_max_position_embeddings"]

  def pair(beta):
    return d * math.log(context / (2 * math.pi * beta)) / (
        2 * math.log(theta))

  low = max(math.floor(pair(rope["beta_fast"])), 0)
  high = min(math.ceil(pair(rope["beta_slow"])), d - 1)
  ramp = jnp.clip((pairs - low) / (high - low), 0.0, 1.0)
  return ((1.0 - ramp) * freq + ramp * freq / rope["factor"],
          rope["attention_factor"])


def _rotary(x, rotary_dim: int, rope: dict):
  """x [T, H, D]; rotate-half on the first `rotary_dim` dims."""
  half = rotary_dim // 2
  freq, amplitude = rotary_frequencies(rotary_dim, rope)
  angles = jnp.arange(x.shape[0], dtype=F32)[:, None] * freq
  cos = amplitude * jnp.cos(angles)[:, None, :]
  sin = amplitude * jnp.sin(angles)[:, None, :]
  x1, x2, rest = (x[..., :half], x[..., half:rotary_dim],
                  x[..., rotary_dim:])
  return jnp.concatenate(
      [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(x, p, layer: int, model, control):
  """x [T, M] (normed) -> [T, M]: layer `layer`'s gated attention."""
  c = _lowered(control, "attention")
  kind = model["layer_types"][layer]
  h = model["num_attention_heads_per_layer"][layer]
  kv, d = model["num_key_value_heads"], model["head_dim"]
  eps, t = model["rms_norm_eps"], x.shape[0]
  rope = model["rope_parameters"][kind]
  rotary_dim = int(d * rope["partial_rotary_factor"])
  window = model["sliding_window"] if kind == SLIDING else t
  q_gate = _dot(x, p["q_proj/kernel"], c).reshape(t, 2 * h, d)
  q, gate = q_gate[:, :h], q_gate[:, h:]
  k = _dot(x, p["k_proj/kernel"], c).reshape(t, kv, d)
  v = _dot(x, p["v_proj/kernel"], c).reshape(t, kv, d)
  q = _rotary(_rms_norm(q, p["q_norm/weight"], eps, c), rotary_dim,
              rope)
  k = _rotary(_rms_norm(k, p["k_norm/weight"], eps, c), rotary_dim,
              rope)
  block = min(QUERY_BLOCK, t)
  pad = -t % block  # queries past the end stand at the last position
  q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
  q = q.reshape(-1, block, kv, h // kv, d)  # the query heads of a kv head
  keys = jnp.arange(t)

  @jax.checkpoint
  def attend(q_block, first):
    scores = _dot(q_block, k, c, "qgrd,kgd->grqk") * d ** -0.5
    rows = jnp.minimum(first + jnp.arange(block), t - 1)
    behind = rows[:, None] - keys[None, :]
    seen = (behind >= 0) & (behind < window)
    scores = jnp.where(seen, scores, -jnp.inf).astype(_ew(c))
    probs = jax.nn.softmax(scores, axis=-1).astype(F32)
    return _dot(probs, v, c, "grqk,kgd->qgrd")

  out = jax.lax.map(lambda args: attend(*args),
                    (q, jnp.arange(0, t + pad, block)))
  out = out.reshape(-1, h, d)[:t] * _sigmoid(gate, c)
  return _dot(out.reshape(t, h * d), p["o_proj/kernel"], c)



def _expert_ffn(x, p, model, control):
  """x [N, M] (normed) -> the held experts' part of the layer's sum
  plus the shared expert."""
  c = control is True
  held, first = model["experts_held"], model.get("first_expert", 0)
  scores = _sigmoid(_dot(x, p["router"], _lowered(control, "router")),
                    _lowered(control, "router"))
  weights, chosen = jax.lax.top_k(scores, model["num_experts_per_tok"])
  if model["norm_topk_prob"]:
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
  weights = weights * model["moe_routed_scaling_factor"]

  @jax.checkpoint
  def one_expert(x, weight, gate, up, down):
    return weight[:, None] * _gated_unit(x, gate, up, down, c)

  def body(total, expert):
    index, gate, up, down = expert
    weight = jnp.sum(jnp.where(chosen == index, weights, 0.0), axis=-1)
    return total + one_expert(x, weight, gate, up, down), None

  routed, _ = jax.lax.scan(
      body, jnp.zeros_like(x),
      (first + jnp.arange(held), p["experts_gate"], p["experts_up"],
       p["experts_down"]))
  return routed + _gated_unit(
      x, p["shared_gate/kernel"], p["shared_up/kernel"],
      p["shared_down/kernel"], c)



def _layer(x, p, layer: int, model, control):
  """x [B, T, M] -> [B, T, M]."""
  eps, c = model["rms_norm_eps"], control is True
  mixer, ffn = _sub(p, "mixer/"), _sub(p, "ffn/")
  x = x + jax.lax.map(
      jax.checkpoint(lambda row: _attention(
          _rms_norm(row, p["ln_attn/weight"], eps, c), mixer,
          layer, model, control)), x)
  if model["mlp_layer_types"][layer] == "dense":
    return x + jax.lax.map(
        jax.checkpoint(lambda row: _dense_ffn(
            _rms_norm(row, p["ln_mlp/weight"], eps, c), ffn, control)),
        x)
  tokens = x.reshape(-1, x.shape[-1])
  tokens = tokens + jax.checkpoint(lambda tokens: _expert_ffn(
      _rms_norm(tokens, p["ln_mlp/weight"], eps, c), ffn, model,
      control))(tokens)
  return tokens.reshape(x.shape)



def loss(config, params, stats, batch, rng, control=False):
  """`harness/follow.py`'s contract: (loss, aux, new_stats)."""
  del stats, rng  # no running statistics; the model draws nothing
  model = config["model"]
  c = control is True
  ids = batch["features"]["token_ids"]
  x = params["embed_tokens"][ids[:, :-1]]  # [B, T, M]
  for i in range(model["num_hidden_layers"]):
    x = _layer(x, _sub(params, f"trunk/blocks_{i}/"), i, model, control)
  h = _rms_norm(x, params["trunk/norm_out/weight"],
                model["rms_norm_eps"], c)
  losses = jax.lax.map(
      jax.checkpoint(lambda row: _head_loss(*row, params["lm_head"], c)),
      (h, ids[:, 1:]))
  return jnp.mean(losses), {}, {}
