"""`harness/swa_lm_flops.py` (the windowed-attention language model's
FLOP count and its attention kernels' FLOPs and bytes) held against
XLA's own cost analysis of the plain reference's forward pass, part by
part, and against the numbers ISSUE 43 reckons for the cell; the
family's readers on made-up records, and `None` without what they
read."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import swa_lm_flops
from benchmark.layer_metrics import (
    lm_swa_band_over_tile_pairs,
    lm_swa_full_attention_device_ms,
    lm_swa_full_attention_roofline,
    lm_swa_moe_device_ms,
    lm_swa_step_mfu,
    lm_swa_window_attention_roofline,
    lm_swa_window_kernel_share,
)
from benchmark.reference import laguna_xs2 as ref
from benchmark.reference import laguna_xs2_weights

CONFIG = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                      "configs", "laguna_xs2_ep16.json")
FULL, SLIDING = "full_attention", "sliding_attention"
SWA_MODEL = dict(
    vocab_size=512, sequence_length=64, hidden_size=128,
    num_hidden_layers=2, layer_types=[FULL, SLIDING],
    num_attention_heads_per_layer=[4, 6], num_key_value_heads=2,
    head_dim=32, sliding_window=16,
    rope_parameters={
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 4,
               "original_max_position_embeddings": 32, "beta_slow": 1,
               "beta_fast": 4, "attention_factor": 1.1386,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}},
    mlp_layer_types=["dense", "sparse"], intermediate_size=256,
    num_experts=8, experts_held=1, first_expert=0,
    num_experts_per_tok=2, norm_topk_prob=True,
    moe_routed_scaling_factor=2.5, moe_intermediate_size=64,
    shared_expert_intermediate_size=64, rms_norm_eps=1e-6)


def _xla_flops(fn, *args) -> float:
  return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def _swa_cell_config():
  with open(CONFIG) as f:
    return json.load(f)


def test_swa_lm_flops_against_xlas_cost_analysis_of_the_reference():
  """At a size where the matrix products dominate, with what differs
  by design taken out: XLA counts a loop's body once (so the
  reference's attention runs as one block of queries and one held
  expert), all T x T pairs of an attention that the count takes the
  causal half or the band of, and the elementwise work that the count
  leaves out."""
  t, m = SWA_MODEL["sequence_length"], SWA_MODEL["hidden_size"]
  counted = swa_lm_flops.forward_flops_per_position(
      SWA_MODEL, assignments_here_share=1.0 / SWA_MODEL["num_experts"])
  params, _ = laguna_xs2_weights.make_weights(3, {"model": SWA_MODEL})
  x = jax.random.normal(jax.random.PRNGKey(0), (t, m))

  def near(xla, want, slack=0.15):
    assert want <= xla <= (1 + slack) * want, (xla, want)

  for layer, name, kind in ((0, "full", FULL), (1, "window", SLIDING)):
    xla = _xla_flops(
        lambda x, p, layer=layer: ref._attention(x, p, layer, SWA_MODEL,
                                                 False),
        x, ref._sub(params, f"trunk/blocks_{layer}/mixer/"))
    pairs = swa_lm_flops.seen_pairs(kind, t, 16)
    near(xla, t * counted[f"{name}_projections"]
         + counted[f"{name}_attention"] * t / pairs * t * t)
  assert swa_lm_flops.seen_pairs(SLIDING, t, 16) == sum(
      min(i + 1, 16) for i in range(t))
  assert swa_lm_flops.seen_pairs(FULL, t, 16) == t * (t + 1) / 2
  assert swa_lm_flops.seen_pairs(SLIDING, t, 64) == t * (t + 1) / 2
  xla = _xla_flops(lambda x, p: ref._dense_ffn(x, p, False), x,
                   ref._sub(params, "trunk/blocks_0/ffn/"))
  near(xla, t * counted["dense_ffn"])
  # The expert layer: the router, the shared expert, and the one held
  # expert on every position (the masks multiply, they do not skip),
  # where the count takes the 2 / 8 of a position's assignments.
  xla = _xla_flops(lambda x, p: ref._expert_ffn(x, p, SWA_MODEL, False),
                   x, ref._sub(params, "trunk/blocks_1/ffn/"))
  every_position = 3 * 2 * m * SWA_MODEL["moe_intermediate_size"]
  near(xla, t * (counted["router"] + counted["shared_experts"]
                 + every_position))
  assert counted["routed_experts"] == pytest.approx(
      every_position * 2 / 8)
  xla = _xla_flops(lambda x, w: jnp.dot(x, w), x, params["lm_head"])
  assert xla == pytest.approx(t * counted["head"])


def test_swa_lm_flops_of_the_cell_are_the_issues():
  """939 MFLOP a position forward, 92 TFLOP a step of 32,768 tokens;
  the shares of ISSUE 43: the windowed layers 43 % (their projections
  35, the band's products 5, their expert layers 3), the full layers'
  attention 39 (products 21), the dense FFN 11, the head 5; at the
  causal kernel's cost the three bands would be 403 M and not 49."""
  model = _swa_cell_config()["model"]
  parts = swa_lm_flops.forward_flops_per_position(model)
  total = sum(parts.values())
  assert total == pytest.approx(939e6, rel=0.001)
  assert swa_lm_flops.step_flops(model, 4) == pytest.approx(92.3e12,
                                                            rel=0.001)
  share = lambda *names: 100 * sum(parts[n] for n in names) / total  # noqa: E731
  assert share("window_projections") == pytest.approx(35, abs=1)
  assert share("window_attention") == pytest.approx(5, abs=0.5)
  assert share("full_projections", "full_attention") == \
      pytest.approx(39, abs=1)
  assert share("full_attention") == pytest.approx(21, abs=1)
  assert share("dense_ffn") == pytest.approx(11, abs=0.5)
  assert share("head") == pytest.approx(5, abs=0.6)
  experts = share("router", "routed_experts", "shared_experts")
  assert experts * 3 / 4 == pytest.approx(3, abs=0.5)
  assert parts["window_attention"] == pytest.approx(49e6, rel=0.01)
  causal = swa_lm_flops.forward_flops_per_position(
      dict(model, sliding_window=8192))["window_attention"]
  assert causal == pytest.approx(403e6, rel=0.001)
  double = swa_lm_flops.forward_flops_per_position(model, 2 * 16 / 256)
  assert double["routed_experts"] == 2 * parts["routed_experts"]


def test_swa_kernel_costs_at_the_cells_widths():
  """One call on 4 rows of 8,192 positions in bfloat16: the three
  programs' FLOPs stand as 2 : 4 : 3; a sliding layer's band is 12 % of
  a causal triangle a head (6 % of the T x T square), a call's FLOPs a sixth of a full layer's
  at 64 heads against 48; keys and values count at their own 8 heads;
  every program is bound by the FLOP peak (197 TFLOP/s against 819
  GB/s) and not by the HBM."""
  model = _swa_cell_config()["model"]
  window = swa_lm_flops.window_kernel_costs(model, 4, 8192)
  full = swa_lm_flops.attention_kernel_costs(model, 4, 8192)
  for costs in (window, full):
    assert costs["dkdv"]["flops"] == 2 * costs["forward"]["flops"]
    assert costs["dq"]["flops"] == 1.5 * costs["forward"]["flops"]
    for cost in costs.values():
      assert cost["flops"] / 197e12 > cost["bytes"] / 819e9
  band = sum(min(i + 1, 512) for i in range(8192))
  assert window["forward"]["flops"] == 4 * 64 * band * 2 * 2 * 128
  assert full["forward"]["flops"] == 4 * 48 * (8192 * 8193 / 2) * 512
  assert band / (8192 * 8193 / 2) == pytest.approx(0.121, abs=0.001)
  rows = 4 * 8192
  assert window["forward"]["bytes"] == (
      2 * rows * 64 * 128 * 2 + 2 * rows * 8 * 128 * 2 + rows * 64 * 4)
  assert swa_lm_flops.heads_of(model, SLIDING) == 64
  with pytest.raises(ValueError, match="one number"):
    swa_lm_flops.heads_of(dict(
        model, num_attention_heads_per_layer=[48, 64, 32, 64, 48]),
        SLIDING)


def _swa_run(records=(), trace=None):
  return {"records": list(records), "trace": trace, "k": 2, "batch": 4,
          "chips": 1, "device_kind": "TPU v5 lite",
          "config": _swa_cell_config()}


def _swa_trace(window_scope):
  """Two whole programs of two steps: ns by scope, and the kernels of
  3 sliding and 2 full layers a step (a forward call, a recomputation
  saved, a dK/dV and a dQ call each)."""
  calls = lambda scope, which, n, ns: {  # noqa: E731
      "name": "flash_attention", "scope": scope, "pass": which,
      "primitive": "pallas_call", "calls": n, "ns": ns}
  passes = lambda f, r, b: {  # noqa: E731
      "forward": f, "recompute": r, "backward": b}
  return {
      "program_runs": 2, "program_busy_s": 7.5, "program_self_s": 7.4,
      "scope_ns": {
          window_scope: passes(0.6e9, 0.6e9, 1.2e9),
          "gated_attention": passes(0.5e9, 0.5e9, 1.0e9),
          "moe/route": passes(0.1e9, 0.1e9, 0.2e9),
          "moe/experts": passes(0.1e9, 0.1e9, 0.2e9),
          "dense_ffn": passes(0.2e9, 0.2e9, 0.4e9),
          "lm_head_loss": passes(0.1e9, 0.0, 0.2e9),
          "unnamed": passes(0.4e9, 0.0, 0.4e9)},
      "kernels": [
          calls(window_scope, "forward", 12, 12 * 10.8e6),
          calls(window_scope, "backward", 24, 12 * 37.8e6),
          calls("gated_attention", "forward", 8, 8 * 33.5e6),
          calls("gated_attention", "backward", 16, 8 * 117.2e6),
          {"name": "ragged-dot-none", "scope": "unnamed",
           "pass": "forward", "primitive": "ragged-dot-none",
           "calls": 48, "ns": 0.2e9}]}


@pytest.mark.parametrize("window_scope", ["other", "window_attention"])
def test_swa_readers_on_a_made_up_trace(window_scope):
  """The sliding layers' operations stand under `other` while the
  accepted scope list lacks `window_attention`, and under their own
  name once it has it: the banded kernel's roofline share reads the
  same either way, and no device-time row reads that scope."""
  run = _swa_run([{"moe.assignments_here_share": 0.0625}] * 2,
                 _swa_trace(window_scope))
  assert lm_swa_full_attention_device_ms.read(run) == 500.0
  # Two scopes and ragged-dot.
  assert lm_swa_moe_device_ms.read(run) == pytest.approx(250.0)
  # A step of 92.3 TFLOP in 7.5 / 4 s is a quarter of 197 TFLOP/s.
  assert lm_swa_step_mfu.read(run) == pytest.approx(25.0, abs=0.1)
  # The least time of a sliding layer's three programs is 2.70, 5.41
  # and 4.06 ms, of a full layer's 16.7, 33.5 and 25.1.
  assert lm_swa_window_attention_roofline.read(run) == pytest.approx(
      100 * (2.704 + 5.407 + 4.055) / (10.8 + 37.8), abs=0.2)
  assert lm_swa_full_attention_roofline.read(run) == pytest.approx(
      100 * (16.75 + 33.49 + 25.12) / (33.5 + 117.2), abs=0.2)


def test_swa_readers_find_nothing_without_a_trace_or_counters():
  from tensor2robot_tpu.telemetry import metrics as tmetrics

  tmetrics.registry().reset()
  untraced = _swa_run()
  cut = _swa_run(trace={"program_runs": 0, "program_busy_s": 0.0,
                        "scope_ns": {}, "kernels": []})
  for reader in (lm_swa_full_attention_device_ms, lm_swa_moe_device_ms,
                 lm_swa_step_mfu,
                 lm_swa_window_attention_roofline,
                 lm_swa_full_attention_roofline):
    assert reader.read(untraced) is None, reader.__name__
    assert reader.read(cut) is None, reader.__name__
  # The parent's program has neither counter: no line, no error.
  assert lm_swa_window_kernel_share.read(untraced) is None
  assert lm_swa_band_over_tile_pairs.read(untraced) is None
  tmetrics.counter("attention.window.kernel_traces").inc(3)
  tmetrics.counter("attention.window.materialised_traces").inc(1)
  tmetrics.counter("attention.window.band_pairs").inc(3 * 4063488)
  tmetrics.counter("attention.window.tile_pairs").inc(3 * 8126464)
  assert lm_swa_window_kernel_share.read(untraced) == 75.0
  assert lm_swa_band_over_tile_pairs.read(untraced) == pytest.approx(
      50.0, abs=0.01)  # 31 tiles of 512 x 512 a head for 16 rows of tiles
  tmetrics.registry().reset()
