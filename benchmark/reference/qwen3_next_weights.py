"""The benchmark's weights for the Qwen3-Next language model, made from
`--seed` on the device in one jitted call, as flat dicts by the path of
the program's parameter tree (`harness/weights.place` refuses a leaf
that is missing or of another shape).

The scales keep activations of order 1 through four layers (the
configuration file lists them under `assumed`): embedding rows are
standard normal; every projection is normal with variance 1 / fan_in,
and the four projections back into the residual stream (`out_proj`,
`o_proj`, `experts_down`, `shared_down`) half that deviation; the
convolution's four taps have deviation 1/2; the zero-centred norm
weights are 0.1 n and the Gated DeltaNet's plain one 1 + 0.1 n; `A_log`
and `dt_bias` are drawn as the published modelling code initialises
them (A uniform in [1, 16]; dt log-uniform in [0.001, 0.1], `dt_bias`
its inverse softplus), so a chunk of 64 positions decays the state by
exp(-3) to exp(-50).
"""

import jax
import jax.numpy as jnp
import numpy as np

# Adam's second moment in the start checkpoint, every element: the
# square of a gradient element of 1e-4. This model's gradient elements
# read 1e-5 to 1e-4 (a mean over 32,768 positions), so the resumed
# run's updates follow the gradients' sizes and not only their signs.
ADAM_NU0 = 1e-8


def param_shapes(model: dict) -> dict:
  m, vocab = model["hidden_size"], model["vocab_size"]
  hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
  dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
  h, kv, d = (model["num_attention_heads"],
              model["num_key_value_heads"], model["head_dim"])
  held, f = model["experts_held"], model["moe_intermediate_size"]
  fs = model["shared_expert_intermediate_size"]
  shapes = {"embed_tokens": (vocab, m), "lm_head": (m, vocab),
            "trunk/norm_out/weight": (m,)}
  for i in range(model["num_hidden_layers"]):
    p = f"trunk/blocks_{i}/"
    shapes[p + "ln_attn/weight"] = shapes[p + "ln_mlp/weight"] = (m,)
    if (i + 1) % model["full_attention_interval"] == 0:
      shapes.update({
          p + "mixer/q_proj/kernel": (m, 2 * h * d),
          p + "mixer/k_proj/kernel": (m, kv * d),
          p + "mixer/v_proj/kernel": (m, kv * d),
          p + "mixer/o_proj/kernel": (h * d, m),
          p + "mixer/q_norm/weight": (d,),
          p + "mixer/k_norm/weight": (d,)})
    else:
      shapes.update({
          p + "mixer/in_proj_qkvz/kernel": (m, 2 * hk * dk + 2 * hv * dv),
          p + "mixer/in_proj_ba/kernel": (m, 2 * hv),
          p + "mixer/conv": (model["linear_conv_kernel_dim"],
                             2 * hk * dk + hv * dv),
          p + "mixer/A_log": (hv,), p + "mixer/dt_bias": (hv,),
          p + "mixer/norm": (dv,),
          p + "mixer/out_proj/kernel": (hv * dv, m)})
    shapes.update({
        p + "ffn/router": (m, model["num_experts"]),
        p + "ffn/experts_gate": (held, m, f),
        p + "ffn/experts_up": (held, m, f),
        p + "ffn/experts_down": (held, f, m),
        p + "ffn/shared_gate/kernel": (m, fs),
        p + "ffn/shared_up/kernel": (m, fs),
        p + "ffn/shared_down/kernel": (fs, m),
        p + "ffn/shared_expert_gate/kernel": (m, 1)})
  return shapes


def _leaf(key, name: str, shape):
  noise = jax.random.normal(key, shape, jnp.float32)
  last = name.rsplit("/", 1)[-1]
  if name == "embed_tokens":
    return noise
  if last == "weight":          # zero-centred norms
    return 0.1 * noise
  if last == "norm":            # the Gated DeltaNet's plain norm
    return 1.0 + 0.1 * noise
  if last == "conv":
    return 0.5 * noise
  if last == "A_log":
    return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                      maxval=16.0))
  if last == "dt_bias":
    dt = jnp.exp(jax.random.uniform(
        key, shape, minval=np.log(0.001), maxval=np.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
  scale = shape[-2] ** -0.5     # a projection [..., fan_in, fan_out]
  if any(part in name for part in ("out_proj", "o_proj", "experts_down",
                                   "shared_down")):
    scale = 0.5 * scale
  return scale * noise


def make_weights(seed: int, config: dict):
  """(params, stats): float32 on the default device, flat by path; the
  model has no running statistics."""
  items = tuple(sorted(param_shapes(config["model"]).items()))

  @jax.jit
  def make(key):
    return {name: _leaf(jax.random.fold_in(key, index), name, shape)
            for index, (name, shape) in enumerate(items)}

  return make(jax.random.PRNGKey(seed % (2 ** 31 - 1))), {}
