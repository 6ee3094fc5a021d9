"""The benchmark's weights for the Laguna-XS.2 language model, made
from `--seed` on the device in one jitted call, as flat dicts by the
path of the program's parameter tree (`harness/weights.place` refuses a
leaf that is missing or of another shape).

The scales are the Qwen3-Next file's (the configuration file lists them
under `assumed`): embedding rows are standard normal; every projection
and the router are normal with variance 1 / fan_in, and the four
projections back into the residual stream (`o_proj`, `experts_down`,
`shared_down`, the dense layer's `down_proj`) half that deviation; the
zero-centred norm weights are 0.1 n.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.joyai_llm_flash_weights import (  # noqa: F401
    ADAM_NU0,  # 1e-8 in every element of Adam's second moment at the start
    _leaf,     # the scales above, by a leaf's name
)


def _block_shapes(model: dict, layer: int) -> dict:
  m, d = model["hidden_size"], model["head_dim"]
  h = model["num_attention_heads_per_layer"][layer]
  kv = model["num_key_value_heads"]
  shapes = {
      "ln_attn/weight": (m,), "ln_mlp/weight": (m,),
      "mixer/q_proj/kernel": (m, 2 * h * d),  # queries | gates
      "mixer/k_proj/kernel": (m, kv * d),
      "mixer/v_proj/kernel": (m, kv * d),
      "mixer/q_norm/weight": (d,), "mixer/k_norm/weight": (d,),
      "mixer/o_proj/kernel": (h * d, m)}
  if model["mlp_layer_types"][layer] == "dense":
    f = model["intermediate_size"]
    shapes.update({"ffn/gate_proj/kernel": (m, f),
                   "ffn/up_proj/kernel": (m, f),
                   "ffn/down_proj/kernel": (f, m)})
  else:
    held, f = model["experts_held"], model["moe_intermediate_size"]
    fs = model["shared_expert_intermediate_size"]
    shapes.update({
        "ffn/router": (m, model["num_experts"]),
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



def make_weights(seed: int, config: dict):
  """(params, stats): float32 on the default device, flat by path; the
  model has no running statistics."""
  items = tuple(sorted(param_shapes(config["model"]).items()))

  @jax.jit
  def make(key):
    return {name: _leaf(jax.random.fold_in(key, index), name, shape)
            for index, (name, shape) in enumerate(items)}

  return make(jax.random.PRNGKey(seed % (2 ** 31 - 1))), {}
