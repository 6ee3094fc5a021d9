"""Plain reference of the Qwen3-Next language model's next-token loss,
written from the layer equations (ISSUE 34, Tentpole §1; the published
`config.json` of Qwen/Qwen3-Next-80B-A3B-Instruct gives every size) and
nothing of the program: float32, every matrix product at `highest`, no
kernels, no chunked form, no sort.

  layer i:  x <- x + mixer_i(norm(x));  x <- x + ffn(norm(x))
            mixer_i is gated attention where (i + 1) % interval == 0,
            else Gated DeltaNet; norms are x / rms(x) * (1 + w)
  Gated DeltaNet: q, k, v, z from one projection, b, a from another;
            causal depthwise convolution (kernel 4) and SiLU on q|k|v;
            beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias);
            q, k L2-normalised, q / sqrt(Dk); per head, from S = 0:
            S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);
            S <- S + k_t d^T;  o_t = S^T q_t
            -- AS THE RECURRENCE OVER POSITIONS (`lax.scan`), a chunk of
            64 positions under `jax.checkpoint` so the backward holds 128
            states and not 8,192; out = w * o / rms(o) * silu(z)
  gated attention: q and a gate per query head, 2 key-value heads; norms
            over the head dimension of q and k; rotary (rotate-half) on
            the first quarter of each head; causal softmax attention,
            materialised a block of queries at a time; * sigmoid(gate)
  ffn:      p = softmax(x W_r) over ALL experts; the k largest, divided
            by their sum; sum over the chosen experts THAT ARE HELD
            (`first_expert .. first_expert + experts_held - 1`), by a
            loop over the held experts with masks, of
            w_e down_e(silu(gate_e x) * up_e x); plus sigmoid(x w_s)
            times the shared expert, the same gated unit
  loss:     final norm, untied head over the vocabulary slice, mean
            next-token cross-entropy over every position

A row of the batch is one document (no packing), so the loss is the
mean of the rows' losses. Each layer runs over all rows: its mixer a
row at a time (`lax.map`, each row under `jax.checkpoint`), its
feed-forward over all rows' tokens at once under one `jax.checkpoint`;
the head again a row at a time. Beside `follow`'s 16 bytes a parameter
stand every row's input to each mixer and feed-forward and one row's
activations of one mixer.

`control=True` is the same one precision lower: every matrix product's
operands rounded to float8 (e4m3, scaled to the tensor's largest
magnitude) AND the elementwise math (norms, softmax, SiLU, the gates,
the recurrence and its state) in bfloat16.

Departures from the published model, as the configuration file states
them: no multi-token-prediction module, no auxiliary router loss, the
layout of `in_proj_qkvz`'s and `q_proj`'s columns is this benchmark's
(q | k | v | z; queries | gates).
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
CHUNK = 64          # positions of the recurrence under one checkpoint
QUERY_BLOCK = 1024  # queries whose scores stand at once


def _round(x, control):
  """Rounding to fp8 e4m3 under a scale that puts the largest
  magnitude at the format's 448 (straight-through), for the control."""
  if not control:
    return x
  scale = jnp.maximum(jnp.max(jnp.abs(x)) / 448.0, 1e-12)
  rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(F32)
  return x + jax.lax.stop_gradient(rounded * scale - x)


def _dot(x, w, control, spec=None):
  x, w = _round(x.astype(F32), control), _round(w, control)
  if spec is None:
    return jnp.dot(x, w, precision=HIGHEST)
  return jnp.einsum(spec, x, w, precision=HIGHEST)


def _ew(control):
  """The dtype of the elementwise math."""
  return jnp.bfloat16 if control else F32


def _rms_norm(x, weight, eps, control, zero_centred=True):
  x = x.astype(_ew(control))
  weight = weight.astype(x.dtype)
  x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + jnp.asarray(eps, x.dtype))
  return (x * (1 + weight if zero_centred else weight)).astype(F32)


def _silu(x, control):
  return jax.nn.silu(x.astype(_ew(control))).astype(F32)


def _sigmoid(x, control):
  return jax.nn.sigmoid(x.astype(_ew(control))).astype(F32)


def _gated_unit(x, gate, up, down, control):
  hidden = _silu(_dot(x, gate, control), control) * _dot(x, up, control)
  return _dot(hidden, down, control)


def _delta_rule(q, k, v, g, beta, control):
  """The recurrence over positions for one row: q, k [T, H, Dk],
  v [T, H, Dv], g, beta [T, H] -> o [T, H, Dv]."""
  dtype = _ew(control)
  t, h, dk = q.shape
  pad = -t % CHUNK
  q, k, v, g, beta = (
      jnp.pad(x.astype(dtype), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
      for x in (q, k, v, g, beta))

  def position(state, xs):
    q_t, k_t, v_t, g_t, beta_t = xs
    state = state * jnp.exp(g_t)[:, None, None]
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


def _gated_delta_net(x, p, model, control):
  """x [T, M] (normed) -> [T, M]."""
  hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
  dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
  key_dim, value_dim = hk * dk, hv * dv
  eps, ew = model["rms_norm_eps"], _ew(control)
  t = x.shape[0]
  qkvz = _dot(x, p["in_proj_qkvz/kernel"], control)
  ba = _dot(x, p["in_proj_ba/kernel"], control)
  qkv, z = qkvz[:, :2 * key_dim + value_dim], qkvz[:, -value_dim:]
  taps = p["conv"].shape[0]
  padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0))).astype(ew)
  qkv = sum(padded[j:j + t] * p["conv"][j].astype(ew)
            for j in range(taps))
  qkv = _silu(qkv, control)
  q = qkv[:, :key_dim].reshape(t, hk, dk)
  k = qkv[:, key_dim:2 * key_dim].reshape(t, hk, dk)
  v = qkv[:, 2 * key_dim:].reshape(t, hv, dv)
  beta = _sigmoid(ba[:, :hv], control)
  g = (-jnp.exp(p["A_log"]).astype(ew)
       * jax.nn.softplus((ba[:, hv:] + p["dt_bias"]).astype(ew))
       ).astype(F32)

  def l2(y):
    y = y.astype(ew)
    return (y * jax.lax.rsqrt(jnp.sum(jnp.square(y), -1, keepdims=True)
                              + jnp.asarray(eps, ew))).astype(F32)

  q, k = l2(q) * dk ** -0.5, l2(k)
  q, k = (jnp.repeat(y, hv // hk, axis=1) for y in (q, k))
  out = _delta_rule(q, k, v, g, beta, control)
  out = _rms_norm(out, p["norm"], eps, control, zero_centred=False)
  out = out * _silu(z.reshape(t, hv, dv), control)
  return _dot(out.reshape(t, value_dim), p["out_proj/kernel"], control)


def _rotary(x, rotary_dim, theta):
  """x [T, H, D]; rotate-half on the first `rotary_dim` dims."""
  half = rotary_dim // 2
  inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
  angles = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv_freq
  cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
  x1, x2, rest = (x[..., :half], x[..., half:rotary_dim],
                  x[..., rotary_dim:])
  return jnp.concatenate(
      [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _gated_attention(x, p, model, control):
  """x [T, M] (normed) -> [T, M]."""
  h, kv = model["num_attention_heads"], model["num_key_value_heads"]
  d, eps, t = model["head_dim"], model["rms_norm_eps"], x.shape[0]
  rotary_dim = int(d * model["partial_rotary_factor"])
  q_gate = _dot(x, p["q_proj/kernel"], control).reshape(t, 2 * h, d)
  q, gate = q_gate[:, :h], q_gate[:, h:]
  k = _dot(x, p["k_proj/kernel"], control).reshape(t, kv, d)
  v = _dot(x, p["v_proj/kernel"], control).reshape(t, kv, d)
  q = _rotary(_rms_norm(q, p["q_norm/weight"], eps, control),
              rotary_dim, model["rope_theta"])
  k = _rotary(_rms_norm(k, p["k_norm/weight"], eps, control),
              rotary_dim, model["rope_theta"])
  q = q.reshape(t, kv, h // kv, d)  # the query heads of a kv head
  block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
  keys = jnp.arange(t)

  @jax.checkpoint
  def attend(q_block, first):
    scores = _dot(q_block, k, control, "qgrd,kgd->grqk") * d ** -0.5
    seen = keys[None, :] <= (first + jnp.arange(block))[:, None]
    scores = jnp.where(seen, scores, -jnp.inf).astype(_ew(control))
    probs = jax.nn.softmax(scores, axis=-1).astype(F32)
    return _dot(probs, v, control, "grqk,kgd->qgrd")

  out = jax.lax.map(
      lambda args: attend(*args),
      (q.reshape(t // block, block, kv, h // kv, d),
       jnp.arange(0, t, block)))
  out = out.reshape(t, h, d) * _sigmoid(gate, control)
  return _dot(out.reshape(t, h * d), p["o_proj/kernel"], control)


def _ffn(x, p, model, control):
  """x [T, M] (normed) -> the held experts' part of the layer's sum
  plus the gated shared expert."""
  held, first = model["experts_held"], model.get("first_expert", 0)
  probs = jax.nn.softmax(
      _dot(x, p["router"], control).astype(_ew(control)), axis=-1
  ).astype(F32)
  weights, chosen = jax.lax.top_k(probs, model["num_experts_per_tok"])
  if model["norm_topk_prob"]:
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

  @jax.checkpoint
  def one_expert(x, weight, gate, up, down):
    return weight[:, None] * _gated_unit(x, gate, up, down, control)

  def body(total, expert):
    index, gate, up, down = expert
    weight = jnp.sum(jnp.where(chosen == index, weights, 0.0), axis=-1)
    return total + one_expert(x, weight, gate, up, down), None

  routed, _ = jax.lax.scan(
      body, jnp.zeros_like(x),
      (first + jnp.arange(held), p["experts_gate"], p["experts_up"],
       p["experts_down"]))
  shared = _gated_unit(x, p["shared_gate/kernel"], p["shared_up/kernel"],
                       p["shared_down/kernel"], control)
  gate = _sigmoid(_dot(x, p["shared_expert_gate/kernel"], control),
                  control)
  return routed + gate * shared


def _layer(x, p, kind, model, control):
  """x [B, T, M] -> [B, T, M]. The mixer a row at a time, each row
  under `jax.checkpoint` (a row's activations of one mixer stand at a
  time); the feed-forward, which knows no positions, over all rows'
  tokens at once under one `jax.checkpoint`: its experts' gradients
  are then made once, not carried, copied and added a row."""
  eps = model["rms_norm_eps"]
  mixer = {k[len("mixer/"):]: v for k, v in p.items()
           if k.startswith("mixer/")}
  ffn = {k[len("ffn/"):]: v for k, v in p.items()
         if k.startswith("ffn/")}
  mix = _gated_attention if kind == "attention" else _gated_delta_net
  x = x + jax.lax.map(
      jax.checkpoint(lambda row: mix(
          _rms_norm(row, p["ln_attn/weight"], eps, control), mixer,
          model, control)), x)
  tokens = x.reshape(-1, x.shape[-1])
  tokens = tokens + jax.checkpoint(lambda tokens: _ffn(
      _rms_norm(tokens, p["ln_mlp/weight"], eps, control), ffn, model,
      control))(tokens)
  return tokens.reshape(x.shape)


def layer_kinds(model):
  return ["attention"
          if (i + 1) % model["full_attention_interval"] == 0
          else "gated_delta" for i in range(model["num_hidden_layers"])]


def _head_loss(x, targets, norm, head, eps, control):
  """The mean next-token cross-entropy of one row: x [T, M] out of the
  last layer, targets [T]."""
  logits = _dot(_rms_norm(x, norm, eps, control), head, control)
  logits = logits.astype(_ew(control)).astype(F32)
  picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
  return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def loss(config, params, stats, batch, rng, control=False):
  """`harness/follow.py`'s contract: (loss, aux, new_stats).

  Layer by layer over all rows (`_layer`), then the head a row at a
  time. A loop over rows around the whole model carried, copied and
  added all 2.5 GB of parameter gradients a row: compiled for a
  described v5e it took 12.6 GB of temporaries beside `follow`'s 7.5 GB
  of state, this form 6.85 (PR 34)."""
  del stats, rng  # no running statistics; the model draws nothing
  model = config["model"]
  ids = batch["features"]["token_ids"]
  inputs, targets = ids[:, :-1], ids[:, 1:]
  x = params["embed_tokens"][inputs]  # [B, T, M]
  for i, kind in enumerate(layer_kinds(model)):
    prefix = f"trunk/blocks_{i}/"
    x = _layer(x, {k[len(prefix):]: v for k, v in params.items()
                   if k.startswith(prefix)}, kind, model, control)
  norm, head = params["trunk/norm_out/weight"], params["lm_head"]
  per_row = jax.lax.map(
      jax.checkpoint(lambda row: _head_loss(
          *row, norm, head, model["rms_norm_eps"], control)),
      (x, targets))
  return jnp.mean(per_row), {}, {}
