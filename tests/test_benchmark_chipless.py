"""The benchmark's chipless tests of the kind that the first
`train_eval` cell uses, in tier-1 (PERF.md section 7 asked it of "a PR
that may"; ISSUE 34): the cases of `benchmark/tests/test_follow.py`
(the reference side's donated step, the leaf-by-leaf comparison) and of
`benchmark/tests/test_trace_reduce.py` (whole programs of a recording)
and `benchmark/tests/test_lm_gdn_recompute_device_ms.py`,
`benchmark/tests/test_lm_gdn_fused_forward_share.py` and
`benchmark/tests/test_lm_flash_backward_fused_share.py` (ISSUE 41's,
ISSUE 42's and ISSUE 45's readers), run here as they are; `harness/lm_flops.py` held against
XLA's own cost analysis of the reference's forward pass; the new
readers on made-up records; BENCHMARK.json's new entries resolved to
their files.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import lm_flops  # noqa: E402
from benchmark.layer_metrics import (  # noqa: E402
    lm_expert_load_max_over_mean,
    lm_moe_rounds_run,
    lm_step_mfu,
)
from benchmark.reference import qwen3_next as ref  # noqa: E402
from benchmark.reference import qwen3_next_weights  # noqa: E402
from benchmark.tests import test_benchmark_json as bench_json  # noqa: E402
from benchmark.tests.test_follow import *  # noqa: E402,F401,F403
from benchmark.tests.test_lm_flash_backward_fused_share import *  # noqa: E402,F401,F403
from benchmark.tests.test_lm_gdn_fused_forward_share import *  # noqa: E402,F401,F403
from benchmark.tests.test_lm_gdn_recompute_device_ms import *  # noqa: E402,F401,F403
from benchmark.tests.test_trace_reduce import *  # noqa: E402,F401,F403
from tensor2robot_tpu import config as gin  # noqa: E402


@pytest.fixture(autouse=True)
def _no_gin_bindings_between_tests():
  gin.clear_config()
  yield
  gin.clear_config()


MODEL = dict(
    vocab_size=512, sequence_length=64, hidden_size=128,
    num_hidden_layers=4, full_attention_interval=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=64,
    partial_rotary_factor=0.25, rope_theta=1e7, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=32,
    linear_value_head_dim=32, linear_conv_kernel_dim=4, num_experts=8,
    experts_held=1, first_expert=0, num_experts_per_tok=2,
    norm_topk_prob=True, moe_intermediate_size=64,
    shared_expert_intermediate_size=64, rms_norm_eps=1e-6)


def _xla_flops(fn, *args) -> float:
  return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def _layer_params(kind: str):
  params, _ = qwen3_next_weights.make_weights(3, {"model": MODEL})
  index = 3 if kind == "attention" else 0
  prefix = f"trunk/blocks_{index}/"
  return params, {k[len(prefix):]: v for k, v in params.items()
                  if k.startswith(prefix)}


def _strip(layer, part):
  return {k[len(part) + 1:]: v for k, v in layer.items()
          if k.startswith(part + "/")}


def test_lm_flops_against_xlas_cost_analysis_of_the_reference():
  """Part by part, at a size where the matrix products dominate, with
  what differs by design taken out: XLA counts a loop's body once (so
  the reference's attention and experts run as one block and one held
  expert, and the recurrence over positions counts one position), all
  T x T pairs of an attention that the count takes the causal half
  of, and the elementwise work that the count leaves out (under a
  sixth at this size)."""
  t = MODEL["sequence_length"]
  counted = lm_flops.forward_flops_per_position(
      MODEL, assignments_here_share=1.0 / MODEL["num_experts"])
  x = jax.random.normal(jax.random.PRNGKey(0), (t, 128))
  params, attention = _layer_params("attention")
  _, delta = _layer_params("gated_delta")

  def near(xla, want, slack):
    assert want <= xla <= (1 + slack) * want, (xla, want)

  # One attention layer: its projections, and all pairs where the
  # count has (T + 1) / 2 of T.
  xla = _xla_flops(lambda x, p: ref._gated_attention(x, p, MODEL, False),
                   x, _strip(attention, "mixer"))
  near(xla, t * (counted["attention_projections"]
                 + counted["attention"] * 2 * t / (t + 1)), 0.15)
  # One Gated DeltaNet layer of three: projections and convolution;
  # of the rule XLA sees one position.
  xla = _xla_flops(lambda x, p: ref._gated_delta_net(x, p, MODEL, False),
                   x, _strip(delta, "mixer"))
  near(xla, t * counted["gated_delta_projections"] / 3, 0.15)
  # One FFN of four: the router, the shared expert, and the one held
  # expert on every position (the masks multiply, they do not skip),
  # where the count takes the 2 / 8 of a position's assignments.
  xla = _xla_flops(lambda x, p: ref._ffn(x, p, MODEL, False), x,
                   _strip(delta, "ffn"))
  every_position = 3 * 2 * 128 * MODEL["moe_intermediate_size"]
  near(xla, t * ((counted["router"] + counted["shared_expert"]) / 4
                 + every_position), 0.15)
  assert counted["routed_experts"] / 4 == pytest.approx(
      every_position * 2 / 8)
  # The head.
  xla = _xla_flops(lambda x, w: jnp.dot(x, w), x, params["lm_head"])
  assert xla == pytest.approx(t * counted["head"])


def test_lm_flops_of_the_cell_are_the_issues():
  """0.47 GFLOP a position forward, 46 TFLOP a step of 32,768 tokens;
  the shares of ISSUE 34's `why`: the three Gated-DeltaNet layers 47 %,
  attention 26, the head 17, the four FFNs 10."""
  with open(os.path.join(ROOT, "benchmark", "configs",
                         "qwen3next_80b_a3b_ep16.json")) as f:
    model = json.load(f)["model"]
  parts = lm_flops.forward_flops_per_position(model)
  total = sum(parts.values())
  assert total == pytest.approx(0.467e9, rel=0.01)
  assert lm_flops.step_flops(model, 4) == pytest.approx(45.9e12,
                                                        rel=0.01)
  share = lambda *names: 100 * sum(parts[n] for n in names) / total  # noqa: E731
  assert share("gated_delta_projections", "gated_delta_rule") == \
      pytest.approx(47, abs=1)
  assert share("attention_projections", "attention") == \
      pytest.approx(26, abs=1)
  assert share("head") == pytest.approx(17, abs=1)
  assert share("router", "routed_experts", "shared_expert") == \
      pytest.approx(10, abs=1)
  # As routed: twice the assignments here, twice the routed FLOPs.
  double = lm_flops.forward_flops_per_position(model, 2 * 32 / 512)
  assert double["routed_experts"] == 2 * parts["routed_experts"]


def _run_record(records, trace=None):
  with open(os.path.join(ROOT, "benchmark", "configs",
                         "qwen3next_80b_a3b_ep16.json")) as f:
    config = json.load(f)
  return {"records": records, "trace": trace, "k": 2, "batch": 4,
          "chips": 1, "device_kind": "TPU v5 lite", "config": config}


def test_lm_readers_on_made_up_records():
  none = _run_record([{"step": 2, "loss": 1.0}])
  assert lm_expert_load_max_over_mean.read(none) is None
  assert lm_moe_rounds_run.read(none) is None  # the parent's records
  assert lm_step_mfu.read(none) is None  # untraced
  records = [{"moe.expert_load_max_over_mean": 1.1,
              "moe.assignments_here_share": 0.0625,
              "moe.rounds_run": 1.0},
             {"moe.expert_load_max_over_mean": 1.3,
              "moe.assignments_here_share": 0.0625,
              "moe.rounds_run": 2.0}]
  # Two whole programs of two steps each in 4.66 s of device time: a
  # step of 45.9 TFLOP in 1.165 s is a fifth of 197 TFLOP/s.
  run = _run_record(records, {"program_runs": 2,
                              "program_busy_s": 4.66})
  assert lm_expert_load_max_over_mean.read(run) == pytest.approx(1.2)
  assert lm_moe_rounds_run.read(run) == pytest.approx(1.5)
  assert lm_step_mfu.read(run) == pytest.approx(20.0, abs=0.1)
  cut = _run_record(records, {"program_runs": 0, "program_busy_s": 0.0})
  assert lm_step_mfu.read(cut) is None


def test_read_controls_batches_are_the_loops_first(monkeypatch):
  """`tools/read_control.py` makes a seed's first K batches without
  the loop: the ones `harness/seeded_rows.SeededRows` yields first to
  the model's specs."""
  from benchmark import run as run_lib
  from benchmark.harness import program, seeded_rows
  from benchmark.tools import read_control
  from tensor2robot_tpu.data.abstract_input_generator import Mode

  _, _, config, _ = run_lib.load_cell(
      "qwen3next_80b_a3b_ep16.train_eval")
  config = run_lib.rehearsal_config(config)
  train = config["train"]
  rows = seeded_rows.SeededRows(
      train["data_rows"], 1234567, keep=2,
      int_below=train["int_below"], batch_size=4)
  rows.set_specification_from_model(program.build_model(config),
                                    Mode.TRAIN)
  stream = iter(rows.create_dataset(Mode.TRAIN))
  next(stream), next(stream), next(stream)
  made = read_control.first_batches(config, 1234567, batch=4, k=2)
  assert len(rows.kept) == len(made) == 2
  for kept, batch in zip(rows.kept, made):
    assert kept["labels"] == batch["labels"] == {}
    assert list(kept["features"]) == ["token_ids"]
    assert (kept["features"]["token_ids"]
            == batch["features"]["token_ids"]).all()
    assert batch["features"]["token_ids"].shape == (
        4, config["model"]["sequence_length"] + 1)


@pytest.fixture(scope="module")
def bench():
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    return json.load(f)


@pytest.mark.parametrize("case", [
    "test_keys_and_shapes", "test_names_units_and_lines",
    "test_every_entry_resolves_to_its_files",
    "test_widths_are_the_sources"])
def test_benchmark_json_with_the_new_cell(bench, case):
  """`benchmark/tests/test_benchmark_json.py`'s cases on the root's
  file as this PR leaves it: the new configuration, cell, traffic mix,
  limits, pin and readers resolve by name."""
  getattr(bench_json, case)(bench)
  assert "qwen3next_80b_a3b_ep16.train_eval" in {
      w["name"] for w in bench["workloads"]}
