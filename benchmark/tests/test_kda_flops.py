"""`harness/kda_lm_flops.py` (the channel-gated delta-rule language
model's FLOP count, its walk's and its attention kernels' FLOPs and
bytes) held against XLA's own cost analysis of the plain reference's
forward pass, part by part, and against the numbers ISSUE 47 reckons
for the cell; and the family's nine readers on a made-up trace."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import kda_lm_flops, lm_flops
from benchmark.layer_metrics import (
    lm_kda_attention_device_ms,
    lm_kda_attention_roofline,
    lm_kda_delta_device_ms,
    lm_kda_delta_recompute_device_ms,
    lm_kda_kernel_share,
    lm_kda_moe_device_ms,
    lm_kda_moe_rounds_run,
    lm_kda_other_device_ms,
    lm_kda_step_mfu,
)
from benchmark.reference import kimi_linear as ref
from benchmark.reference import kimi_linear_weights

KDA_CONFIG = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                          "configs", "kimi_linear_48b_a3b_ep32.json")
KDA_MODEL = dict(
    vocab_size=512, sequence_length=64, hidden_size=128,
    num_hidden_layers=2,
    linear_attn_config={"kda_layers": [1], "full_attn_layers": [2],
                        "num_heads": 2, "head_dim": 32,
                        "short_conv_kernel_size": 4},
    num_attention_heads=4, q_lora_rank=None, kv_lora_rank=32,
    qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=24,
    mla_use_nope=True, first_k_dense_replace=1, intermediate_size=256,
    num_experts=8, experts_held=1, first_expert=0,
    num_experts_per_token=2, moe_renormalize=True,
    moe_router_activation_func="sigmoid", routed_scaling_factor=2.446,
    num_shared_experts=1, moe_intermediate_size=64, rms_norm_eps=1e-5)


def _xla_flops(fn, *args) -> float:
  return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def _kda_cell_config():
  with open(KDA_CONFIG) as f:
    return json.load(f)


def test_kda_lm_flops_against_xlas_cost_analysis_of_the_reference():
  """At a size where the matrix products dominate, with what differs
  by design taken out: XLA counts a loop's body once (so the
  reference's attention runs as one block of queries, one held expert
  and the recurrence not at all: the projections are held against the
  layer less its rule), all T x T pairs of an attention that the count
  takes the causal half of, and the elementwise work that the count
  leaves out."""
  t, m = KDA_MODEL["sequence_length"], KDA_MODEL["hidden_size"]
  counted = kda_lm_flops.forward_flops_per_position(
      KDA_MODEL, assignments_here_share=1.0 / KDA_MODEL["num_experts"])
  params, _ = kimi_linear_weights.make_weights(3, {"model": KDA_MODEL})
  x = jax.random.normal(jax.random.PRNGKey(0), (t, m))

  def near(xla, want, slack=0.15):
    assert want <= xla <= (1 + slack) * want, (xla, want)

  mixer = ref._sub(params, "trunk/blocks_0/mixer/")
  # The recurrence is a scan of one chunk: its body counts once, 2
  # heads x (2 + 2 + 2) x 32 x 32 multiply-adds and the elementwise.
  xla = _xla_flops(
      lambda x, p: ref._kimi_delta_attention(x, p, KDA_MODEL, False),
      x, mixer)
  near(xla, t * counted["kda_projections"], slack=0.2)
  xla = _xla_flops(
      lambda x, p: ref._latent_attention(x, p, KDA_MODEL, False), x,
      ref._sub(params, "trunk/blocks_1/mixer/"))
  near(xla, t * (counted["mla_projections"]
                 + counted["mla_attention"] / ((t + 1) / 2) * t))
  xla = _xla_flops(lambda x, p: ref._dense_ffn(x, p, False), x,
                   ref._sub(params, "trunk/blocks_0/ffn/"))
  near(xla, t * counted["dense_ffn"])
  xla = _xla_flops(
      lambda x, p: ref._expert_ffn(x, p, ref._router_model(KDA_MODEL),
                                   False),
      x, ref._sub(params, "trunk/blocks_1/ffn/"))
  every_position = 3 * 2 * m * KDA_MODEL["moe_intermediate_size"]
  near(xla, t * (counted["router"] + counted["shared_experts"]
                 + every_position))
  assert counted["routed_experts"] == pytest.approx(
      every_position * 2 / 8)
  xla = _xla_flops(lambda x, w: jnp.dot(x, w), x, params["lm_head"])
  assert xla == pytest.approx(t * counted["head"])


def test_kda_lm_flops_of_the_cell_are_the_issues():
  """777 MFLOP a position forward, 76.4 TFLOP a step of 32,768 tokens;
  the shares of ISSUE 47's `why`: the four KDA layers 44 % (the rule's
  chunked products 21 MFLOP of their 337: the issue's 26 counts its
  decays' multiplications), the latent-attention layer 18 (84 MFLOP of
  it the causal products), the dense FFN 16, the head 12, the four
  expert FFNs 10."""
  model = _kda_cell_config()["model"]
  assert kda_lm_flops.layer_counts(model) == (4, 1)
  parts = kda_lm_flops.forward_flops_per_position(model)
  total = sum(parts.values())
  assert total == pytest.approx(776.8e6, rel=0.001)
  assert kda_lm_flops.step_flops(model, 4) == pytest.approx(76.36e12,
                                                            rel=0.001)
  share = lambda *names: 100 * sum(parts[n] for n in names) / total  # noqa: E731
  assert parts["kda_projections"] == pytest.approx(316.1e6, rel=0.001)
  assert parts["kda_rule"] == pytest.approx(21.3e6, rel=0.01)
  assert parts["mla_attention"] == pytest.approx(83.9e6, rel=0.001)
  assert share("kda_projections", "kda_rule") == pytest.approx(44, abs=1)
  assert share("mla_projections", "mla_attention") == pytest.approx(
      18, abs=1)
  assert share("dense_ffn") == pytest.approx(16, abs=1)
  assert share("head") == pytest.approx(12, abs=1)
  assert share("router", "routed_experts", "shared_experts") == \
      pytest.approx(10, abs=1)
  # The rule's count is the scalar gate's at equal widths and heads.
  scalar = lm_flops.forward_flops_per_position(dict(
      model, num_hidden_layers=1, full_attention_interval=4,
      linear_num_key_heads=32, linear_num_value_heads=32,
      linear_key_head_dim=128, linear_value_head_dim=128,
      linear_conv_kernel_dim=4, num_key_value_heads=32, head_dim=128,
      num_experts_per_tok=8, shared_expert_intermediate_size=1024))
  assert parts["kda_rule"] == 4 * scalar["gated_delta_rule"]
  # As routed: twice the assignments here, twice the routed FLOPs.
  double = kda_lm_flops.forward_flops_per_position(model, 2 * 8 / 256)
  assert double["routed_experts"] == 2 * parts["routed_experts"]


def test_kda_kernel_costs_at_the_cells_widths():
  """The flash kernel's fused backward program is ONE call of five
  products a pair, 2 x (3 x 192 + 2 x 128), where the pair of programs
  makes seven; every attention program is bound by the FLOP peak. The
  walk's programs move `end_decay` and its cotangent at 128 floats a
  head and chunk where the scalar gate's move one, and are bound by
  the HBM."""
  model = _kda_cell_config()["model"]
  costs = kda_lm_flops.attention_kernel_costs(model, 4, 8192)
  pairs = 4 * 32 * 8192 * 8193 / 2
  assert costs["forward"]["flops"] == pairs * 2 * 320
  assert costs["backward"]["flops"] == pairs * 2 * 832
  assert costs["dkdv"]["flops"] + costs["dq"]["flops"] == \
      pairs * 2 * (640 + 512)
  rows = 4 * 32 * 8192
  assert costs["backward"]["bytes"] == rows * (
      2 * (4 * 192 + 3 * 128) + 2 * 4)
  for cost in costs.values():
    assert cost["flops"] / 197e12 > cost["bytes"] / 819e9
  walk = kda_lm_flops.walk_kernel_costs(model, 1, 8192)
  scalar = lm_flops.walk_kernel_costs(dict(
      model, linear_num_key_heads=32, linear_num_value_heads=32,
      linear_key_head_dim=128, linear_value_head_dim=128,
      num_key_value_heads=32, head_dim=128), 1, 8192)
  units = 32 * 128
  for name, more in (("forward", 1), ("forward_saving_states", 1),
                     ("backward", 2)):
    assert walk[name]["flops"] == scalar[name]["flops"]
    assert walk[name]["bytes"] - scalar[name]["bytes"] == \
        units * more * (128 - 1) * 4
    assert walk[name]["bytes"] / 819e9 > walk[name]["flops"] / 197e12
  assert walk["forward"]["bytes"] / units == pytest.approx(147968)


def _kda_run(records=(), trace=None):
  return {"records": list(records), "trace": trace, "k": 2, "batch": 4,
          "chips": 1, "device_kind": "TPU v5 lite",
          "config": _kda_cell_config()}


def _kda_trace(walk_calls=4):
  """Two whole programs of two steps: ns by scope, and the kernels of
  four KDA layers and one of latent attention a step. A walk's call
  covers a row or, at `walk_calls` 4, a quarter of its heads."""
  calls = lambda name, scope, which, n, ns: {  # noqa: E731
      "name": name, "scope": scope, "pass": which,
      "primitive": "pallas_call", "calls": n, "ns": ns}
  passes = lambda f, r, b: {  # noqa: E731
      "forward": f, "recompute": r, "backward": b}
  per_step = 4 * 4 * walk_calls  # layers x rows x calls a row
  return {
      "program_runs": 2, "program_busy_s": 6.2, "program_self_s": 6.0,
      "scope_ns": {
          "gated_delta/scan": passes(0.8e9, 0.8e9, 1.2e9),
          "gated_delta/conv": passes(0.05e9, 0.05e9, 0.1e9),
          "mla/attend": passes(0.15e9, 0.0, 0.35e9),
          "mla/q_proj": passes(0.02e9, 0.02e9, 0.04e9),
          "mla/kv_proj": passes(0.01e9, 0.01e9, 0.02e9),
          "mla/o_proj": passes(0.02e9, 0.02e9, 0.04e9),
          "moe/route": passes(0.05e9, 0.05e9, 0.1e9),
          "moe/experts": passes(0.05e9, 0.05e9, 0.1e9),
          "dense_ffn": passes(0.2e9, 0.2e9, 0.4e9),
          "lm_head_loss": passes(0.1e9, 0.0, 0.2e9),
          "other": passes(0.2e9, 0.1e9, 0.2e9),
          "unnamed": passes(0.1e9, 0.0, 0.1e9)},
      "kernels": [
          calls("_forward_kernel", "gated_delta/scan", "forward",
                4 * per_step, 4 * 16 * 2.0e6),
          calls("_forward_kernel", "gated_delta/scan", "recompute",
                4 * per_step, 4 * 16 * 2.4e6),
          calls("_backward_kernel", "gated_delta/scan", "backward",
                4 * per_step, 4 * 16 * 3.6e6),
          calls("flash_attention", "mla/attend", "forward", 4,
                4 * 20.0e6),
          calls("flash_attention", "mla/attend", "backward", 4,
                4 * 50.0e6),
          {"name": "ragged-dot-none", "scope": "unnamed",
           "pass": "forward", "primitive": "ragged-dot-none",
           "calls": 48, "ns": 0.1e9}]}


@pytest.mark.parametrize("walk_calls", [1, 4])
def test_kda_readers_on_a_made_up_trace(walk_calls):
  from tensor2robot_tpu.telemetry import metrics as tmetrics

  tmetrics.registry().reset()
  tmetrics.counter("flash_attention.backward.fused_traces").inc(1)
  run = _kda_run([{"moe.assignments_here_share": 0.03125,
                   "moe.rounds_run": 1.0},
                  {"moe.assignments_here_share": 0.03125,
                   "moe.rounds_run": 2.0}], _kda_trace(walk_calls))
  assert lm_kda_delta_device_ms.read(run) == pytest.approx(750.0)
  assert lm_kda_delta_recompute_device_ms.read(run) == pytest.approx(
      200.0)
  assert lm_kda_attention_device_ms.read(run) == pytest.approx(175.0)
  assert lm_kda_moe_device_ms.read(run) == pytest.approx(125.0)
  # The four rows add up to the program's self time a step.
  assert (lm_kda_delta_device_ms.read(run)
          + lm_kda_attention_device_ms.read(run)
          + lm_kda_moe_device_ms.read(run)
          + lm_kda_other_device_ms.read(run)) == pytest.approx(
              6.0e3 / 4)
  # A step of 76.36 TFLOP in 6.2 / 4 s is a quarter of 197 TFLOP/s.
  assert lm_kda_step_mfu.read(run) == pytest.approx(25.0, abs=0.1)
  assert lm_kda_moe_rounds_run.read(run) == 1.5
  # 4 rows' forward program 13.95 ms at the least, the fused backward
  # 36.28; the pair would be 27.91 + 22.33.
  assert lm_kda_attention_roofline.read(run) == pytest.approx(
      100 * (13.95 + 36.28) / (20.0 + 50.0), abs=0.3)
  tmetrics.registry().reset()
  tmetrics.counter("flash_attention.backward.paired_traces").inc(1)
  paired = _kda_run(trace=_kda_trace(walk_calls))
  paired["trace"]["kernels"][4]["calls"] = 8
  assert lm_kda_attention_roofline.read(paired) == pytest.approx(
      100 * (13.95 + 27.91 + 22.33) / (20.0 + 50.0), abs=0.3)
  paired["trace"]["kernels"][4]["calls"] = 7  # not pairs
  assert lm_kda_attention_roofline.read(paired) is None
  tmetrics.registry().reset()


def test_kda_readers_find_nothing_without_a_trace_or_counters():
  from tensor2robot_tpu.telemetry import metrics as tmetrics

  tmetrics.registry().reset()
  untraced = _kda_run()
  cut = _kda_run(trace={"program_runs": 0, "program_busy_s": 0.0,
                        "scope_ns": {}, "kernels": []})
  for reader in (lm_kda_step_mfu, lm_kda_delta_device_ms,
                 lm_kda_delta_recompute_device_ms,
                 lm_kda_attention_device_ms, lm_kda_moe_device_ms,
                 lm_kda_other_device_ms, lm_kda_attention_roofline):
    assert reader.read(untraced) is None, reader.__name__
    assert reader.read(cut) is None, reader.__name__
  # The parent's program has neither counter: no line, no error. A
  # traced attention kernel whose backward passes nobody counted (or
  # both programs did) is no reading either.
  assert lm_kda_kernel_share.read(untraced) is None
  assert lm_kda_moe_rounds_run.read(untraced) is None
  assert lm_kda_attention_roofline.read(
      _kda_run(trace=_kda_trace())) is None
  tmetrics.counter("gated_delta.channel_gate.kernel_traces").inc(3)
  tmetrics.counter("gated_delta.channel_gate.scan_traces").inc(1)
  assert lm_kda_kernel_share.read(untraced) == 75.0
  tmetrics.registry().reset()
