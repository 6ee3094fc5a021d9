"""The benchmark's weights for the JoyAI-LLM-Flash language model, made
from `--seed` on the device in one jitted call, as flat dicts by the
path of the program's parameter tree (`harness/weights.place` refuses a
leaf that is missing or of another shape).

The scales are the Qwen3-Next file's (the configuration file lists them
under `assumed`): embedding rows are standard normal; every projection
and the router are normal with variance 1 / fan_in, and the five
projections back into the residual stream (`o_proj`, `experts_down`,
`shared_down`, the dense layer's `down_proj`, the multi-token-prediction
module's `eh_proj`) half that deviation; the zero-centred norm weights
are 0.1 n; the router's selection bias is 0.02 n (of the order of the
gap between the eighth and the ninth score of 256, so that it moves a
share of the choices and not all of them).
"""

import jax
import jax.numpy as jnp

# Adam's second moment in the start checkpoint, every element: the
# square of a gradient element of 1e-4, as in the Qwen3-Next cell, so
# that the resumed run's updates follow the gradients' sizes and not
# only their signs.
ADAM_NU0 = 1e-8


def _block_shapes(model: dict, prefix: str, dense: bool) -> dict:
  m, h = model["hidden_size"], model["num_attention_heads"]
  nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
  dv = model["v_head_dim"]
  q_rank, kv_rank = model["q_lora_rank"], model["kv_lora_rank"]
  shapes = {
      "ln_attn/weight": (m,), "ln_mlp/weight": (m,),
      "mixer/q_a_proj/kernel": (m, q_rank),
      "mixer/q_a_norm/weight": (q_rank,),
      "mixer/q_b_proj/kernel": (q_rank, h * (nope + rope)),
      "mixer/kv_a_proj/kernel": (m, kv_rank + rope),
      "mixer/kv_a_norm/weight": (kv_rank,),
      "mixer/kv_b_proj/kernel": (kv_rank, h * (nope + dv)),
      "mixer/o_proj/kernel": (h * dv, m)}
  if dense:
    f = model["intermediate_size"]
    shapes.update({"ffn/gate_proj/kernel": (m, f),
                   "ffn/up_proj/kernel": (m, f),
                   "ffn/down_proj/kernel": (f, m)})
  else:
    held, f = model["experts_held"], model["moe_intermediate_size"]
    fs = model["n_shared_experts"] * f
    shapes.update({
        "ffn/router": (m, model["n_routed_experts"]),
        "ffn/router_bias": (model["n_routed_experts"],),
        "ffn/experts_gate": (held, m, f),
        "ffn/experts_up": (held, m, f),
        "ffn/experts_down": (held, f, m),
        "ffn/shared_gate/kernel": (m, fs),
        "ffn/shared_up/kernel": (m, fs),
        "ffn/shared_down/kernel": (fs, m)})
  return {prefix + name: shape for name, shape in shapes.items()}


def param_shapes(model: dict) -> dict:
  m, vocab = model["hidden_size"], model["vocab_size"]
  shapes = {"embed_tokens": (vocab, m), "lm_head": (m, vocab),
            "trunk/norm_out/weight": (m,)}
  for i in range(model["num_hidden_layers"]):
    shapes.update(_block_shapes(model, f"trunk/blocks_{i}/",
                                i < model["first_k_dense_replace"]))
  if model["num_nextn_predict_layers"]:
    shapes.update(_block_shapes(model, "mtp/block/", False))
    shapes.update({"mtp/hnorm/weight": (m,), "mtp/enorm/weight": (m,),
                   "mtp/norm_out/weight": (m,),
                   "mtp/eh_proj/kernel": (2 * m, m)})
  return shapes


def _leaf(key, name: str, shape):
  noise = jax.random.normal(key, shape, jnp.float32)
  last = name.rsplit("/", 1)[-1]
  if name == "embed_tokens":
    return noise
  if last == "weight":          # zero-centred norms
    return 0.1 * noise
  if last == "router_bias":
    return 0.02 * noise
  scale = shape[-2] ** -0.5     # a projection [..., fan_in, fan_out]
  if any(part in name for part in ("o_proj", "experts_down",
                                   "shared_down", "down_proj",
                                   "eh_proj")):
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
