"""Plain reference of Kimi-Linear's next-token loss, written from the
layer equations (ISSUE 47, Tentpole; the published `config.json` of
moonshotai/Kimi-Linear-48B-A3B-Instruct gives every size, the report,
arXiv:2510.26692, the layer) and nothing of the program: float32, every
matrix product at `highest`, no kernels, no chunked form, no sort.

  layer i:  x <- x + mixer_i(norm(x));  x <- x + ffn_i(norm(x))
            mixer_i is Kimi Delta Attention where i + 1 is in
            `linear_attn_config.kda_layers` (1-based, as published),
            latent attention where it is in `full_attn_layers`; norms
            are x / rms(x) * (1 + w)
  Kimi Delta Attention (H heads of D): q, k, v each from its own
            projection, causal depthwise convolution (kernel 4) and
            SiLU; q, k L2-normalised a head, q / sqrt(D);
            beta = sigmoid(x W_b) a head;
            g = -exp(A_log) softplus(x W_fa W_fb + dt_bias), A_log a
            head, dt_bias and g A CHANNEL; per head, from S = 0:
            S <- diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);
            S <- S + k_t d^T;  o_t = S^T q_t
            -- AS THE RECURRENCE OVER POSITIONS (`lax.scan`), a chunk of
            64 positions under `jax.checkpoint`;
            out = w * o / rms(o) * sigmoid(x W_ga W_gb + b_g);  W_o
  latent attention (H heads): q = x W_q, per head q_n (nope) | q_r
            (rope); x W_kva = c_kv | k_r, ONE k_r for all heads;
            norm(c_kv) W_kvb per head k_n (nope) | v; NOTHING is turned
            (`mla_use_nope`): q_h = [q_n ; q_r], k_h = [k_n ; k_r];
            softmax_causal(q_h k_h^T / sqrt(nope + rope)) v_h,
            materialised a block of queries at a time; W_o
  ffn_i:    i < first_k_dense_replace: down(silu(gate x) * up x);
            else s = sigmoid(x W_r) over ALL experts; chosen = the k
            largest of s + b; w = scale * s[chosen] / sum(s[chosen]);
            sum over the chosen experts THAT ARE HELD, by a loop over
            the held experts with masks, of
            w_e down_e(silu(gate_e x) * up_e x); plus the shared
            expert, the same unit, ungated
  loss:     final norm, untied head over the vocabulary slice, mean
            next-token cross-entropy over every position

A row of the batch is one document (no packing), so the loss is the
mean of the rows' losses. Each layer runs over all rows: its mixer and
its dense feed-forward a row at a time (`lax.map`, each row under
`jax.checkpoint`), its experts over all rows' tokens at once under one
`jax.checkpoint`; the head a row at a time.

`control=True` is the same one precision lower: every matrix product's
operands rounded to float8 (e4m3, scaled to the tensor's largest
magnitude) AND the elementwise math (norms, softmax, sigmoid, SiLU, the
gates, the recurrence and its state) in bfloat16.

The rounding helpers are the Qwen3-Next reference's, and the dense
unit, the expert layer and the head's loss the JoyAI reference's (the
same equations under this configuration's key names), imported: one
definition of "one precision lower" for every cell.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.joyai_llm_flash import (
    _dense_ffn,
    _expert_ffn,
    _rows_loss,
    _sub,
)
from benchmark.reference.qwen3_next import (
    CHUNK,
    F32,
    HIGHEST,
    _dot,
    _ew,
    _rms_norm,
    _sigmoid,
    _silu,
)

QUERY_BLOCK = 256  # queries whose scores stand at once (32 heads)


def _channel_delta_rule(q, k, v, g, beta, control):
  """The recurrence over positions for one row: q, k, g [T, H, D],
  v [T, H, Dv], beta [T, H] -> o [T, H, Dv]."""
  dtype = _ew(control)
  t, h, dk = q.shape
  pad = -t % CHUNK  # beta 0 writes nothing, g 0 decays nothing
  q, k, v, g, beta = (
      jnp.pad(x.astype(dtype), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
      for x in (q, k, v, g, beta))

  def position(state, xs):
    q_t, k_t, v_t, g_t, beta_t = xs
    state = state * jnp.exp(g_t)[:, :, None]  # every row by its own
    read = jnp.einsum("hk,hkv->hv", k_t, state, precision=HIGHEST)
    delta = beta_t[:, None] * (v_t - read)
    state = state + k_t[:, :, None] * delta[:, None, :]
    return state, jnp.einsum("hk,hkv->hv", q_t, state,
                             precision=HIGHEST)

  @jax.checkpoint
  def chunk(state, xs):
    return jax.lax.scan(position, state, xs)

  xs = tuple(x.reshape((-1, CHUNK) + x.shape[1:])
             for x in (q, k, v, g, beta))
  _, out = jax.lax.scan(chunk, jnp.zeros((h, dk, v.shape[-1]), dtype),
                        xs)
  return out.reshape((-1,) + out.shape[2:])[:t].astype(F32)


def _conv_silu(x, taps, control):
  """x [T, C], taps [K, C]: y[t] = sum_j taps[j] x[t - (K - 1) + j]."""
  ew, t = _ew(control), x.shape[0]
  padded = jnp.pad(x, ((taps.shape[0] - 1, 0), (0, 0))).astype(ew)
  return _silu(sum(padded[j:j + t] * taps[j].astype(ew)
                   for j in range(taps.shape[0])), control)


def _kimi_delta_attention(x, p, model, control):
  """x [T, M] (normed) -> [T, M]."""
  linear = model["linear_attn_config"]
  h, d = linear["num_heads"], linear["head_dim"]
  eps, ew, t = model["rms_norm_eps"], _ew(control), x.shape[0]
  q, k, v = (
      _conv_silu(_dot(x, p[f"{name}_proj/kernel"], control),
                 p[f"{name}_conv"], control).reshape(t, h, d)
      for name in "qkv")
  beta = _sigmoid(_dot(x, p["b_proj/kernel"], control), control)
  decay = _dot(_dot(x, p["f_a_proj/kernel"], control),
               p["f_b_proj/kernel"], control)
  g = (-jnp.exp(p["A_log"]).astype(ew)[:, None]
       * jax.nn.softplus((decay + p["dt_bias"]).astype(ew)
                         ).reshape(t, h, d)).astype(F32)
  gate = _dot(_dot(x, p["g_a_proj/kernel"], control),
              p["g_b_proj/kernel"], control) + p["g_b_proj/bias"]

  def l2(y):
    y = y.astype(ew)
    return (y * jax.lax.rsqrt(jnp.sum(jnp.square(y), -1, keepdims=True)
                              + jnp.asarray(eps, ew))).astype(F32)

  out = _channel_delta_rule(l2(q) * d ** -0.5, l2(k), v, g, beta,
                            control)
  out = _rms_norm(out, p["norm"], eps, control, zero_centred=False)
  out = out * _sigmoid(gate.reshape(t, h, d), control)
  return _dot(out.reshape(t, h * d), p["o_proj/kernel"], control)


def _latent_attention(x, p, model, control):
  """x [T, M] (normed) -> [T, M]; no query latent, nothing turned."""
  c = control
  h, eps, t = model["num_attention_heads"], model["rms_norm_eps"], \
      x.shape[0]
  nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
  dv, rank = model["v_head_dim"], model["kv_lora_rank"]
  q = _dot(x, p["q_proj/kernel"], c).reshape(t, h, nope + rope)
  kv_a = _dot(x, p["kv_a_proj/kernel"], c)
  c_kv = _rms_norm(kv_a[:, :rank], p["kv_a_norm/weight"], eps, c)
  kv = _dot(c_kv, p["kv_b_proj/kernel"], c).reshape(t, h, nope + dv)
  k = jnp.concatenate(
      [kv[..., :nope],
       jnp.broadcast_to(kv_a[:, None, rank:], (t, h, rope))], axis=-1)
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


def _router_model(model):
  """The expert layer's sizes under the names the JoyAI reference's
  `_expert_ffn` reads them by."""
  return {"experts_held": model["experts_held"],
          "first_expert": model.get("first_expert", 0),
          "num_experts_per_tok": model["num_experts_per_token"],
          "norm_topk_prob": model["moe_renormalize"],
          "routed_scaling_factor": model["routed_scaling_factor"]}


def _layer(x, p, layer, model, control):
  """x [B, T, M] -> [B, T, M]; `layer` 0-based."""
  eps = model["rms_norm_eps"]
  mixer, ffn = _sub(p, "mixer/"), _sub(p, "ffn/")
  mix = (_kimi_delta_attention
         if layer + 1 in model["linear_attn_config"]["kda_layers"]
         else _latent_attention)
  x = x + jax.lax.map(
      jax.checkpoint(lambda row: mix(
          _rms_norm(row, p["ln_attn/weight"], eps, control), mixer,
          model, control)), x)
  if layer < model["first_k_dense_replace"]:
    return x + jax.lax.map(
        jax.checkpoint(lambda row: _dense_ffn(
            _rms_norm(row, p["ln_mlp/weight"], eps, control), ffn,
            control)), x)
  tokens = x.reshape(-1, x.shape[-1])
  tokens = tokens + jax.checkpoint(lambda tokens: _expert_ffn(
      _rms_norm(tokens, p["ln_mlp/weight"], eps, control), ffn,
      _router_model(model), control))(tokens)
  return tokens.reshape(x.shape)


def loss(config, params, stats, batch, rng, control=False):
  """`harness/follow.py`'s contract: (loss, aux, new_stats)."""
  del stats, rng  # no running statistics; the model draws nothing
  model, control = config["model"], bool(control)
  if model["moe_router_activation_func"] != "sigmoid":
    raise ValueError("the reference scores by sigmoid")
  ids = batch["features"]["token_ids"]
  x = params["embed_tokens"][ids[:, :-1]]  # [B, T, M]
  for i in range(model["num_hidden_layers"]):
    x = _layer(x, _sub(params, f"trunk/blocks_{i}/"), i, model, control)
  h = _rms_norm(x, params["trunk/norm_out/weight"],
                model["rms_norm_eps"], control)
  return _rows_loss(h, ids[:, 1:], params["lm_head"], control), {}, {}
