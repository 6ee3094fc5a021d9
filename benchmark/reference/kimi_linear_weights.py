"""The benchmark's weights for the Kimi-Linear language model, made
from `--seed` on the device in one jitted call, as flat dicts by the
path of the program's parameter tree (`harness/weights.place` refuses a
leaf that is missing or of another shape).

The scales are the other language-model files' (the configuration file
lists them under `assumed`): embedding rows are standard normal; every
projection and the router are normal with variance 1 / fan_in, and the
four projections back into the residual stream (`o_proj`,
`experts_down`, `shared_down`, the dense layer's `down_proj`) half that
deviation; a convolution's four taps have deviation 1/2; the
zero-centred norm weights are 0.1 n and Kimi Delta Attention's plain
one 1 + 0.1 n; the output gate's bias and the router's selection bias
0.02 n. `A_log` (a head) and `dt_bias` (a key channel) are drawn as the
public `fla` layer of the name initialises them (A uniform in [1, 16];
dt log-uniform in [0.001, 0.1], `dt_bias` its inverse softplus); the
low-rank gate's projection of an input of order 1 stands beside
`dt_bias`, so a channel decays by exp(-0.001) to exp(-10) a position:
a chunk of 64 by up to exp(-600), where a factored `exp(-G)` overflows.
"""

import jax
import jax.numpy as jnp
import numpy as np

# Adam's second moment in the start checkpoint, every element: the
# other language-model cells' value (the square of a gradient element of
# 1e-4), so that the resumed run's first updates are not the gradients'
# signs (`harness/program.seeded_adam`).
ADAM_NU0 = 1e-8


def _block_shapes(model: dict, layer: int) -> dict:
  m = model["hidden_size"]
  linear = model["linear_attn_config"]
  shapes = {"ln_attn/weight": (m,), "ln_mlp/weight": (m,)}
  if layer + 1 in linear["kda_layers"]:
    h, d = linear["num_heads"], linear["head_dim"]
    taps, rank = linear["short_conv_kernel_size"], d
    for name in "qkv":
      shapes[f"mixer/{name}_proj/kernel"] = (m, h * d)
      shapes[f"mixer/{name}_conv"] = (taps, h * d)
    shapes.update({
        "mixer/f_a_proj/kernel": (m, rank),
        "mixer/f_b_proj/kernel": (rank, h * d),
        "mixer/A_log": (h,), "mixer/dt_bias": (h * d,),
        "mixer/b_proj/kernel": (m, h),
        "mixer/g_a_proj/kernel": (m, rank),
        "mixer/g_b_proj/kernel": (rank, h * d),
        "mixer/g_b_proj/bias": (h * d,),
        "mixer/norm": (d,),
        "mixer/o_proj/kernel": (h * d, m)})
  else:
    h = model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    dv, rank = model["v_head_dim"], model["kv_lora_rank"]
    shapes.update({
        "mixer/q_proj/kernel": (m, h * (nope + rope)),
        "mixer/kv_a_proj/kernel": (m, rank + rope),
        "mixer/kv_a_norm/weight": (rank,),
        "mixer/kv_b_proj/kernel": (rank, h * (nope + dv)),
        "mixer/o_proj/kernel": (h * dv, m)})
  if layer < model["first_k_dense_replace"]:
    f = model["intermediate_size"]
    shapes.update({"ffn/gate_proj/kernel": (m, f),
                   "ffn/up_proj/kernel": (m, f),
                   "ffn/down_proj/kernel": (f, m)})
  else:
    held, f = model["experts_held"], model["moe_intermediate_size"]
    fs = model["num_shared_experts"] * f
    shapes.update({
        "ffn/router": (m, model["num_experts"]),
        "ffn/router_bias": (model["num_experts"],),
        "ffn/experts_gate": (held, m, f),
        "ffn/experts_up": (held, m, f),
        "ffn/experts_down": (held, f, m),
        "ffn/shared_gate/kernel": (m, fs),
        "ffn/shared_up/kernel": (m, fs),
        "ffn/shared_down/kernel": (fs, m)})
  return {f"trunk/blocks_{layer}/{name}": shape
          for name, shape in shapes.items()}


def param_shapes(model: dict) -> dict:
  m, vocab = model["hidden_size"], model["vocab_size"]
  shapes = {"embed_tokens": (vocab, m), "lm_head": (m, vocab),
            "trunk/norm_out/weight": (m,)}
  for layer in range(model["num_hidden_layers"]):
    shapes.update(_block_shapes(model, layer))
  return shapes


def _leaf(key, name: str, shape):
  noise = jax.random.normal(key, shape, jnp.float32)
  last = name.rsplit("/", 1)[-1]
  if name == "embed_tokens":
    return noise
  if last == "weight":          # zero-centred norms
    return 0.1 * noise
  if last == "norm":            # Kimi Delta Attention's plain norm
    return 1.0 + 0.1 * noise
  if last in ("router_bias", "bias"):
    return 0.02 * noise
  if last.endswith("_conv"):
    return 0.5 * noise
  if last == "A_log":
    return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                      maxval=16.0))
  if last == "dt_bias":
    dt = jnp.exp(jax.random.uniform(
        key, shape, minval=np.log(0.001), maxval=np.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
  scale = shape[-2] ** -0.5     # a projection [..., fan_in, fan_out]
  if any(part in name for part in ("o_proj", "experts_down",
                                   "shared_down", "down_proj")):
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
