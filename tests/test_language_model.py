"""The next-token language model (`models/language_model.py`; ISSUE 34)
against the plain reference at a small size, its shipped gin file at
the published widths, and the benchmark's cell of it rehearsed on the
CPU through `benchmark/run.py`: `correct` for the shipped step, not
`correct` with a mixer taken out underneath."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as run_lib  # noqa: E402
from benchmark.harness import program  # noqa: E402
from benchmark.harness import weights as weights_lib  # noqa: E402
from benchmark.reference import qwen3_next as ref  # noqa: E402
from benchmark.reference import qwen3_next_weights  # noqa: E402
from tensor2robot_tpu import config as gin  # noqa: E402
from tensor2robot_tpu.data.abstract_input_generator import (  # noqa: E402
    Mode)
from tensor2robot_tpu.layers import gated_delta  # noqa: E402
from tensor2robot_tpu.models.language_model import (  # noqa: E402
    NextTokenLanguageModel, next_token_loss)
from tensor2robot_tpu.specs import TensorSpecStruct  # noqa: E402

CELL = "qwen3next_80b_a3b_ep16.train_eval"
TINY = dict(
    vocab_size=50, hidden_size=16, num_hidden_layers=4,
    full_attention_interval=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, partial_rotary_factor=0.5,
    rope_theta=1e4, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8,
    linear_conv_kernel_dim=4, num_experts=8, experts_held=4,
    first_expert=2, num_experts_per_tok=3, norm_topk_prob=True,
    moe_intermediate_size=8, shared_expert_intermediate_size=8,
    rms_norm_eps=1e-6)


@pytest.fixture(autouse=True)
def _no_gin_bindings_between_tests():
  gin.clear_config()
  yield
  gin.clear_config()


@pytest.mark.parametrize("remat_policy", ["full", None])
def test_loss_and_gradients_equal_the_references(remat_policy):
  """150 positions: no multiple of the delta rule's chunk of 64 nor of
  the loss's block; the chip holds experts 2-5 of 8."""
  t = 150
  model = NextTokenLanguageModel(
      sequence_length=t, device_dtype=jnp.float32, loss_block=64,
      attention_impl="reference", remat_policy=remat_policy, **TINY)
  config = {"model": TINY}
  params, _ = qwen3_next_weights.make_weights(5, config)
  shapes = jax.eval_shape(lambda: model.create_inference_state(
      jax.random.PRNGKey(0), batch_size=2))
  tree = weights_lib.place(shapes.params, params)
  ids = jax.random.randint(jax.random.PRNGKey(1), (3, t + 1), 0, 50)

  def program_loss(tree):
    loss, (scalars, _) = model.loss_fn(
        tree, {}, {"token_ids": ids}, TensorSpecStruct(), None,
        Mode.TRAIN)
    return loss, scalars

  (got, scalars), got_grads = jax.value_and_grad(
      program_loss, has_aux=True)(tree)
  want, want_grads = jax.value_and_grad(
      lambda p: ref.loss(config, p, {}, {"features": {"token_ids": ids}},
                         None)[0])(params)
  np.testing.assert_allclose(got, want, rtol=1e-5)
  got_grads = weights_lib.flatten(got_grads)
  for name, grad in want_grads.items():
    err = float(jnp.linalg.norm(got_grads[name] - grad)
                / (jnp.linalg.norm(grad) + 1e-12))
    assert err < 5e-4, (name, err)
  assert float(scalars["moe.dropped_assignments"]) == 0.0
  assert 0.3 < float(scalars["moe.assignments_here_share"]) < 0.7
  assert float(scalars["moe.rounds_run"]) == 1.0  # the worst layer's
  # One precision lower is another number.
  control = ref.loss(config, params, {},
                     {"features": {"token_ids": ids}}, None,
                     control=True)[0]
  assert abs(float(control) - float(want)) > 1e-4


def test_loss_in_blocks_equals_the_loss_at_once():
  hidden = jax.random.normal(jax.random.PRNGKey(0), (96, 8))
  head = jax.random.normal(jax.random.PRNGKey(1), (8, 11))
  targets = jax.random.randint(jax.random.PRNGKey(2), (96,), 0, 11)
  logits = hidden @ head
  want = jnp.mean(jax.nn.logsumexp(logits, -1)
                  - logits[jnp.arange(96), targets])
  for block in (32, 96, 40):  # 40 does not divide 96: all at once
    np.testing.assert_allclose(
        next_token_loss(hidden, head, targets, block, jnp.float32),
        want, rtol=1e-6)


def _cell_config():
  _, _, config, _ = run_lib.load_cell(CELL)
  return config


def test_shipped_gin_file_builds_the_cells_625_667_136_parameters():
  """The shipped gin file under the cell's four bindings builds the
  published widths with one period, 32 experts a layer and the
  vocabulary's slice: the parameter count ISSUE 34 reckons, part by
  part; the benchmark's weights have the program's tree."""
  config = _cell_config()
  model = program.build_model(config)
  shapes = jax.eval_shape(lambda: model.create_train_state(
      jax.random.PRNGKey(0), batch_size=1))
  flat = weights_lib.flatten(shapes.params)
  count = lambda prefix: sum(  # noqa: E731
      int(np.prod(leaf.shape)) for name, leaf in flat.items()
      if name.startswith(prefix))
  assert count("trunk/blocks_0/mixer") == 33_718_464
  assert count("trunk/blocks_3/mixer") == 27_263_488
  assert count("trunk/blocks_1/ffn") == 4_196_352 + 32 * 3_145_728
  assert (count("embed_tokens") + count("lm_head")
          + count("trunk/norm_out")) == 77_793_280
  assert count("") == 625_667_136
  want = qwen3_next_weights.param_shapes(config["model"])
  assert {k: tuple(v.shape) for k, v in flat.items()} == want
  spec = model.get_feature_specification(Mode.TRAIN)
  assert tuple(spec["token_ids"].shape) == (8193,)


def test_the_configuration_file_holds_the_published_config():
  """Every key of the catalog's row, at the top level and (where the
  model takes it) in the `model` block; only depth and vocabulary
  differ, and `experts_held` counts the chip's share."""
  config = _cell_config()
  with open(os.path.join(run_lib.HERE, "tests", "data", "widths",
                         "qwen3next_80b_a3b_ep16.json")) as f:
    pin = json.load(f)
  published = {k: v for k, v in pin.items()
               if k not in ("_note", "model")}
  assert len(published) == 27  # the row has 29 keys
  for key, value in published.items():
    assert config[key] == value, key
    if key in config["model"]:
      assert config["model"][key] == value, key
  assert (config["num_hidden_layers"], config["vocab_size"],
          config["experts_held"]) == (4, 18992, 32)
  assert sorted(config["reduced"]) == ["experts_held",
                                       "num_hidden_layers", "vocab_size"]
  assert "16 chips" in config["deployment"]


def _rehearse(capsys, monkeypatch, trace="0"):
  monkeypatch.setattr(sys, "argv", [
      "run.py", "--workload", CELL, "--seed", "2147483659",
      "--seconds", "1", "--trace", trace, "--rehearse-cpu"])
  assert run_lib.main() == 0
  lines = capsys.readouterr().out.strip().splitlines()
  return json.loads(lines[-1]), lines


def test_rehearsed_cell_is_correct(capsys, monkeypatch):
  result, lines = _rehearse(capsys, monkeypatch, trace="1")
  assert result["correct"] is True, lines
  assert result["failed"] == 0 and result["attempted"] > 0
  assert len(result["check"]) >= 5
  assert {"lm_expert_load_max_over_mean", "lm_moe_rounds_run"} <= set(
      result["metric_names"])
  # The cell saves every 1000 steps: its kind's window closes on a
  # whole log period, and no save falls in it.
  window = json.loads(next(line for line in lines
                           if line.startswith("window:"))[7:])
  assert window["checkpoint_stalls_ms"] == []
  assert 0 < window["steps"] < 1000 and window["steps"] % 2 == 0


def test_rehearsed_cell_with_a_mixer_taken_out_is_not_correct(
    capsys, monkeypatch):
  """The timed path broken underneath: the delta rule mixes nothing
  and returns its values as they came in."""
  monkeypatch.setattr(
      gated_delta, "gated_delta_rule",
      lambda q, k, v, g, beta, **kwargs: v.astype(jnp.float32))
  result, lines = _rehearse(capsys, monkeypatch)
  assert result["correct"] is False
  assert any("FAILED" in line for line in lines)
