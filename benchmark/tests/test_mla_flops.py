"""`harness/mla_lm_flops.py` (the latent-attention language model's
FLOP count and its attention kernels' FLOPs and bytes) held against
XLA's own cost analysis of the plain reference's forward pass, part by
part, and against the numbers ISSUE 36 reckons for the cell."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import mla_lm_flops
from benchmark.reference import joyai_llm_flash as ref
from benchmark.reference import joyai_llm_flash_weights

CONFIG = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                      "configs", "joyai_llm_flash_ep16.json")
MODEL = dict(
    vocab_size=512, sequence_length=64, hidden_size=128,
    num_hidden_layers=2, num_attention_heads=4, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=24, rope_theta=32e6, first_k_dense_replace=1,
    intermediate_size=256, n_routed_experts=8, experts_held=1,
    first_expert=0, num_experts_per_tok=2, norm_topk_prob=True,
    routed_scaling_factor=2.5, n_shared_experts=1,
    moe_intermediate_size=64, num_nextn_predict_layers=1,
    mtp_loss_weight=0.3, rms_norm_eps=1e-6)


def _xla_flops(fn, *args) -> float:
  return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def _cell_model():
  with open(CONFIG) as f:
    return json.load(f)["model"]


def test_mla_lm_flops_against_xlas_cost_analysis_of_the_reference():
  """At a size where the matrix products dominate, with what differs
  by design taken out: XLA counts a loop's body once (so the
  reference's attention runs as one block of queries and one held
  expert), all T x T pairs of an attention that the count takes the
  causal half of, and the elementwise work that the count leaves out."""
  t, m = MODEL["sequence_length"], MODEL["hidden_size"]
  counted = mla_lm_flops.forward_flops_per_position(
      MODEL, assignments_here_share=1.0 / MODEL["n_routed_experts"])
  params, _ = joyai_llm_flash_weights.make_weights(3, {"model": MODEL})
  x = jax.random.normal(jax.random.PRNGKey(0), (t, m))
  layers = 2 + (t - 1) / t  # the trunk's two and the module's
  pairs = 2 * (t + 1) / 2 + (t - 1) / 2

  def near(xla, want, slack=0.15):
    assert want <= xla <= (1 + slack) * want, (xla, want)

  xla = _xla_flops(
      lambda x, p: ref._latent_attention(x, p, MODEL, False), x,
      ref._sub(params, "trunk/blocks_1/mixer/"))
  near(xla, t * (counted["mla_projections"] / layers
                 + counted["mla_attention"] / pairs * t))
  xla = _xla_flops(lambda x, p: ref._dense_ffn(x, p, False), x,
                   ref._sub(params, "trunk/blocks_0/ffn/"))
  near(xla, t * counted["dense_ffn"])
  # One expert layer: the router, the shared expert, and the one held
  # expert on every position (the masks multiply, they do not skip),
  # where the count takes the 2 / 8 of a position's assignments.
  xla = _xla_flops(lambda x, p: ref._expert_ffn(x, p, MODEL, False), x,
                   ref._sub(params, "trunk/blocks_1/ffn/"))
  expert_layers = 1 + (t - 1) / t
  every_position = 3 * 2 * m * MODEL["moe_intermediate_size"]
  near(xla, t * ((counted["router"] + counted["shared_experts"])
                 / expert_layers + every_position))
  assert counted["routed_experts"] / expert_layers == pytest.approx(
      every_position * 2 / 8)
  xla = _xla_flops(lambda x, w: jnp.dot(x, w), x, params["lm_head"])
  assert xla * expert_layers == pytest.approx(t * counted["heads"])
  both = jnp.concatenate([x, x], axis=-1)[:-1]
  xla = _xla_flops(lambda x, w: jnp.dot(x, w), both,
                   params["mtp/eh_proj/kernel"])
  assert xla == pytest.approx(t * counted["mtp_combine"])


def test_mla_lm_flops_of_the_cell_are_the_issues():
  """1,133 MFLOP a position forward, 55.7 TFLOP a step of 16,384
  tokens; the shares of ISSUE 36's `why`: latent attention 72 % (the
  kernel's products 44, its projections 28), the two heads 12, the
  dense FFN 8, the expert FFNs 7."""
  model = _cell_model()
  parts = mla_lm_flops.forward_flops_per_position(model)
  total = sum(parts.values())
  assert total == pytest.approx(1.133e9, rel=0.002)
  assert mla_lm_flops.step_flops(model, 2) == pytest.approx(55.7e12,
                                                            rel=0.002)
  share = lambda *names: 100 * sum(parts[n] for n in names) / total  # noqa: E731
  assert share("mla_attention") == pytest.approx(44, abs=1)
  assert share("mla_projections") == pytest.approx(28, abs=1)
  assert share("heads") == pytest.approx(12, abs=1)
  assert share("dense_ffn") == pytest.approx(8, abs=1)
  assert share("router", "routed_experts", "shared_experts") == \
      pytest.approx(7, abs=1)
  # One expert layer alone: 151.8 MFLOP a position; the dense one 224.7.
  one = lambda dense: mla_lm_flops.forward_flops_per_position(dict(  # noqa: E731
      model, num_hidden_layers=1, first_k_dense_replace=dense,
      num_nextn_predict_layers=0))
  assert sum(one(0).values()) - one(0)["heads"] == pytest.approx(
      151.8e6, rel=0.001)
  assert sum(one(1).values()) - one(1)["heads"] == pytest.approx(
      224.7e6, rel=0.001)
  # As routed: twice the assignments here, twice the routed FLOPs.
  double = mla_lm_flops.forward_flops_per_position(model, 2 * 16 / 256)
  assert double["routed_experts"] == 2 * parts["routed_experts"]


def test_attention_kernel_costs_at_the_cells_widths():
  """One call on 2 rows of 8,192 positions and 32 heads in bfloat16:
  the three programs' FLOPs stand as 320 : 640 : 512 (2 dk + 2 dv,
  4 dk + 4 dv, 4 dk + 2 dv a pair), together the model's 3 x forward
  plus the scores and dO V^T made anew; each is bound by the FLOP peak,
  not the HBM; a value padded to 192 would cost a fifth more."""
  model = _cell_model()
  costs = mla_lm_flops.attention_kernel_costs(model, 2, 8192)
  pairs = 2 * 32 * 8192 * 8193 / 2
  assert costs["forward"]["flops"] == pairs * 2 * 320
  assert costs["dkdv"]["flops"] == pairs * 2 * 640
  assert costs["dq"]["flops"] == pairs * 2 * 512
  rows = 2 * 32 * 8192
  assert costs["forward"]["bytes"] == rows * (2 * (192 + 192 + 128 + 128)
                                              + 4)
  for cost in costs.values():
    assert cost["flops"] / 197e12 > 5 * cost["bytes"] / 819e9
  padded = mla_lm_flops.attention_kernel_costs(
      dict(model, v_head_dim=192), 2, 8192)
  assert padded["forward"]["flops"] == pytest.approx(
      1.2 * costs["forward"]["flops"])
  # The model's attention FLOPs of a step are three forward passes of
  # the six calls (five layers and the module's T - 1 positions).
  parts = mla_lm_flops.forward_flops_per_position(model)
  assert 6 * costs["forward"]["flops"] == pytest.approx(
      parts["mla_attention"] * 2 * 8192, rel=1e-3)
