"""Plain reference of JoyAI-LLM-Flash's training loss, written from the
layer equations (ISSUE 36, Tentpole (1); the published `config.json` of
jdopensource/JoyAI-LLM-Flash gives every size, DeepSeek-V3's report,
arXiv:2412.19437, the forms it names) and nothing of the program:
float32, every matrix product at `highest`, no kernels, no sort.

  layer i:  x <- x + attention(norm(x));  x <- x + ffn_i(norm(x))
            norms are x / rms(x) * (1 + w)
  latent attention (H heads): c_q = norm(x W_qa); q = c_q W_qb, per
            head q_n (nope) | q_r (rope); x W_kva = c_kv | k_r, ONE
            k_r for all heads; norm(c_kv) W_kvb per head k_n (nope) |
            v; rotary over q_r and k_r, pairs (2 j, 2 j + 1) turned by
            position * theta^(-2 j / rope); q_h = [q_n ; q_r], k_h =
            [k_n ; k_r]; softmax_causal(q_h k_h^T / sqrt(nope + rope))
            v_h, materialised a block of queries at a time; W_o
  ffn_i:    i < first_k_dense_replace: down(silu(gate x) * up x);
            else s = sigmoid(x W_r) over ALL experts; chosen = the k
            largest of s + b; w = scale * s[chosen] / sum(s[chosen]);
            sum over the chosen experts THAT ARE HELD (`first_expert ..
            first_expert + experts_held - 1`), by a loop over the held
            experts with masks, of w_e down_e(silu(gate_e x) * up_e x);
            plus the shared expert, the same unit, ungated
  loss:     L_main = mean next-token cross-entropy of head(norm(x_L))
            over every position; with h = norm(x_L) and E the
            embedding, for the T - 1 positions i that have a
            successor's successor: h'_i = [norm_h(h_i) ;
            norm_e(E[t_{i+1}])] W_eh, one more expert layer over them
            (positions 0 .. T - 2), its own final norm, the SAME head,
            L_mtp = mean cross-entropy against t_{i+2};
            loss = L_main + mtp_loss_weight * L_mtp

A row of the batch is one document (no packing), so each loss is the
mean of the rows' losses. Each layer runs over all rows: its attention
and its dense feed-forward a row at a time (`lax.map`, each row under
`jax.checkpoint`), its experts over all rows' tokens at once under one
`jax.checkpoint` (their gradients are made once); the heads a row at a
time. Beside `follow`'s 16 bytes a parameter stand every row's input to
each mixer and feed-forward and one row's activations of one of them.

`control` is the same one precision lower: every matrix product's
operands rounded to float8 (e4m3, scaled to the tensor's largest
magnitude) AND the elementwise math (norms, softmax, sigmoid, SiLU) in
bfloat16. `True` lowers everything; `"attention"` the latent attention
alone (projections, norms, scores, softmax), `"router"` the router's
product and sigmoid alone.

The rounding helpers are the Qwen3-Next reference's, imported: one
definition of "one precision lower" for both cells.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.qwen3_next import (
    F32,
    _dot,
    _ew,
    _gated_unit,
    _rms_norm,
    _sigmoid,
)

QUERY_BLOCK = 256  # queries whose scores stand at once (32 heads)


def _lowered(control, part: str) -> bool:
  return control is True or control == part


def _rotary_pairs(x, theta):
  """x [T, H, R]: pair j = dims (2 j, 2 j + 1), turned by
  position * theta^(-2 j / R)."""
  t, _, r = x.shape
  inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
  angles = jnp.arange(t, dtype=F32)[:, None] * inv_freq
  cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
  x1, x2 = x[..., 0::2], x[..., 1::2]
  return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                   axis=-1).reshape(x.shape)


def _latent_attention(x, p, model, control):
  """x [T, M] (normed) -> [T, M]."""
  c = _lowered(control, "attention")
  h, eps, t = model["num_attention_heads"], model["rms_norm_eps"], \
      x.shape[0]
  nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
  dv, rank = model["v_head_dim"], model["kv_lora_rank"]
  theta = model["rope_theta"]
  c_q = _rms_norm(_dot(x, p["q_a_proj/kernel"], c),
                  p["q_a_norm/weight"], eps, c)
  q = _dot(c_q, p["q_b_proj/kernel"], c).reshape(t, h, nope + rope)
  kv_a = _dot(x, p["kv_a_proj/kernel"], c)
  c_kv = _rms_norm(kv_a[:, :rank], p["kv_a_norm/weight"], eps, c)
  kv = _dot(c_kv, p["kv_b_proj/kernel"], c).reshape(t, h, nope + dv)
  k_r = _rotary_pairs(kv_a[:, None, rank:], theta)  # one head
  q = jnp.concatenate(
      [q[..., :nope], _rotary_pairs(q[..., nope:], theta)], axis=-1)
  k = jnp.concatenate(
      [kv[..., :nope], jnp.broadcast_to(k_r, (t, h, rope))], axis=-1)
  v = kv[..., nope:]
  block = min(QUERY_BLOCK, t)
  pad = -t % block  # queries past the end see every key; cut off below
  q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
  keys = jnp.arange(t)

  @jax.checkpoint
  def attend(q_block, first):
    scores = _dot(q_block, k, c, "qhd,khd->hqk") * (nope + rope) ** -0.5
    seen = keys[None, :] <= (first + jnp.arange(block))[:, None]
    scores = jnp.where(seen, scores, -jnp.inf).astype(_ew(c))
    probs = jax.nn.softmax(scores, axis=-1).astype(F32)
    return _dot(probs, v, c, "hqk,khd->qhd")

  out = jax.lax.map(
      lambda args: attend(*args),
      (q.reshape(-1, block, h, nope + rope),
       jnp.arange(0, t + pad, block)))
  return _dot(out.reshape(-1, h * dv)[:t], p["o_proj/kernel"], c)


def _dense_ffn(x, p, control):
  c = control is True
  return _gated_unit(x, p["gate_proj/kernel"], p["up_proj/kernel"],
                     p["down_proj/kernel"], c)


def _expert_ffn(x, p, model, control):
  """x [N, M] (normed) -> the held experts' part of the layer's sum
  plus the shared expert."""
  c = control is True
  held, first = model["experts_held"], model.get("first_expert", 0)
  scores = _sigmoid(_dot(x, p["router"], _lowered(control, "router")),
                    _lowered(control, "router"))
  _, chosen = jax.lax.top_k(scores + p["router_bias"],
                            model["num_experts_per_tok"])
  weights = jnp.take_along_axis(scores, chosen, axis=-1)
  if model["norm_topk_prob"]:
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
  weights = weights * model["routed_scaling_factor"]

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


def _sub(params, prefix):
  return {k[len(prefix):]: v for k, v in params.items()
          if k.startswith(prefix)}


def _layer(x, p, dense, model, control):
  """x [B, T, M] -> [B, T, M]."""
  eps, c = model["rms_norm_eps"], control is True
  mixer, ffn = _sub(p, "mixer/"), _sub(p, "ffn/")
  x = x + jax.lax.map(
      jax.checkpoint(lambda row: _latent_attention(
          _rms_norm(row, p["ln_attn/weight"], eps, c), mixer, model,
          control)), x)
  if dense:
    return x + jax.lax.map(
        jax.checkpoint(lambda row: _dense_ffn(
            _rms_norm(row, p["ln_mlp/weight"], eps, c), ffn, control)),
        x)
  tokens = x.reshape(-1, x.shape[-1])
  tokens = tokens + jax.checkpoint(lambda tokens: _expert_ffn(
      _rms_norm(tokens, p["ln_mlp/weight"], eps, c), ffn, model,
      control))(tokens)
  return tokens.reshape(x.shape)


def _head_loss(h, targets, head, control):
  """The mean cross-entropy of one row: h [N, M] already normed."""
  logits = _dot(h, head, control).astype(_ew(control)).astype(F32)
  picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
  return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def _rows_loss(h, targets, head, control):
  return jnp.mean(jax.lax.map(
      jax.checkpoint(lambda row: _head_loss(*row, head, control)),
      (h, targets)))


def loss(config, params, stats, batch, rng, control=False):
  """`harness/follow.py`'s contract: (loss, aux, new_stats); `aux`
  holds the two losses under the names the program's step logs."""
  del stats, rng  # no running statistics; the model draws nothing
  model = config["model"]
  eps, c = model["rms_norm_eps"], control is True
  ids = batch["features"]["token_ids"]
  embed, head = params["embed_tokens"], params["lm_head"]
  x = embed[ids[:, :-1]]  # [B, T, M]
  for i in range(model["num_hidden_layers"]):
    x = _layer(x, _sub(params, f"trunk/blocks_{i}/"),
               i < model["first_k_dense_replace"], model, control)
  h = _rms_norm(x, params["trunk/norm_out/weight"], eps, c)
  main = _rows_loss(h, ids[:, 1:], head, c)
  if not model["num_nextn_predict_layers"]:
    return main, {"lm.loss_main": main}, {}
  both = jnp.concatenate(
      [_rms_norm(h[:, :-1], params["mtp/hnorm/weight"], eps, c),
       _rms_norm(embed[ids[:, 1:-1]], params["mtp/enorm/weight"], eps,
                 c)], axis=-1)
  y = _dot(both, params["mtp/eh_proj/kernel"], c)
  y = _layer(y, _sub(params, "mtp/block/"), False, model, control)
  y = _rms_norm(y, params["mtp/norm_out/weight"], eps, c)
  mtp = _rows_loss(y, ids[:, 2:], head, c)
  total = main + model["mtp_loss_weight"] * mtp
  return total, {"lm.loss_main": main, "lm.loss_mtp": mtp}, {}
