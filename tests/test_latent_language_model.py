"""The latent-attention language model (`LatentAttentionLanguageModel`
of `models/language_model.py`; ISSUE 36) against the plain reference
`benchmark/reference/joyai_llm_flash.py` at small sizes: the mixer
(through materialised attention and through the flash kernel at a key
width other than the value width), the sigmoid router with a selection
bias that moves choices, the dense block, the whole model's two losses
and gradients; the shares of an expert-parallel deployment adding up;
the shipped gin file at the published widths; the benchmark's cell of
it rehearsed on the CPU through `benchmark/run.py`, `correct` for the
shipped step and not with a part of the mathematics taken out; the
FLOP count's cases and the two readers."""

import functools
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
from benchmark.layer_metrics import (  # noqa: E402
    lm_mla_flash_share,
    lm_mla_step_mfu,
)
from benchmark.reference import joyai_llm_flash as ref  # noqa: E402
from benchmark.reference import joyai_llm_flash_weights  # noqa: E402
from benchmark.tests.test_mla_flops import *  # noqa: E402,F401,F403
from tensor2robot_tpu import config as gin  # noqa: E402
from tensor2robot_tpu import ops  # noqa: E402
from tensor2robot_tpu.data.abstract_input_generator import (  # noqa: E402
    Mode)
from tensor2robot_tpu.layers import transformer  # noqa: E402
from tensor2robot_tpu.models import language_model  # noqa: E402
from tensor2robot_tpu.models.language_model import (  # noqa: E402
    LatentAttentionLanguageModel)
from tensor2robot_tpu.parallel import moe  # noqa: E402
from tensor2robot_tpu.specs import TensorSpecStruct  # noqa: E402
from tensor2robot_tpu.telemetry import metrics as tmetrics  # noqa: E402

CELL = "joyai_llm_flash_ep16.train_eval"
KERNEL = "mla.attend.kernel_traces"
MATERIALISED = "mla.attend.materialised_traces"
# Keys 8 + 4 wide over values 6 wide; a dense layer, two expert layers
# and the module's; the chip holds experts 2-5 of 8.
TINY = dict(
    vocab_size=50, hidden_size=16, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=12, kv_lora_rank=8,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=6,
    rope_theta=1e4, first_k_dense_replace=1, intermediate_size=24,
    n_routed_experts=8, experts_held=4, first_expert=2,
    num_experts_per_tok=3, norm_topk_prob=True,
    routed_scaling_factor=2.5, n_shared_experts=1,
    moe_intermediate_size=8, num_nextn_predict_layers=1,
    mtp_loss_weight=0.3, rms_norm_eps=1e-6)
CONFIG = {"model": TINY}


@pytest.fixture(autouse=True)
def _fresh_gin_and_counters():
  gin.clear_config()
  tmetrics.registry().reset()
  yield
  gin.clear_config()
  tmetrics.registry().reset()


def _params(seed=5, model=TINY):
  return joyai_llm_flash_weights.make_weights(seed, {"model": model})[0]


def _tree(flat):
  """A flat dict by path as the nested dict flax takes."""
  tree = {}
  for path, leaf in flat.items():
    node = tree
    *parents, last = path.split("/")
    for part in parents:
      node = node.setdefault(part, {})
    node[last] = leaf
  return tree


def _mixer(impl="reference"):
  return transformer.LatentAttention(
      num_heads=4, q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=8,
      qk_rope_head_dim=4, v_head_dim=6, rope_theta=1e4,
      attention_impl=impl, dtype=jnp.float32)


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_latent_attention_equals_the_reference(monkeypatch, impl):
  """Output and gradients; `flash` is the Pallas kernel (interpreted)
  at keys of 12 over values of 6. 100 positions: more than one block
  of the reference's queries would need 256."""
  monkeypatch.setattr(ops, "flash_attention", functools.partial(
      ops.flash_attention, block_q=32, block_k=64, interpret=True))
  params = ref._sub(_params(), "trunk/blocks_1/mixer/")
  x = jax.random.normal(jax.random.PRNGKey(0), (2, 96, 16))
  probe = jax.random.normal(jax.random.PRNGKey(1), (2, 96, 16))

  def program_out(params, x):
    return jnp.sum(_mixer(impl).apply({"params": _tree(params)}, x)
                   * probe)

  def reference_out(params, x):
    return jnp.sum(jax.vmap(
        lambda row: ref._latent_attention(row, params, TINY, False))(x)
                   * probe)

  got, got_grads = jax.value_and_grad(program_out, (0, 1))(params, x)
  want, want_grads = jax.value_and_grad(reference_out, (0, 1))(params, x)
  np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)
  for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                  jax.tree_util.tree_leaves(want_grads)):
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-4)
  counts = tmetrics.registry().scalars("mla.attend.")
  assert counts == {KERNEL if impl == "flash" else MATERIALISED: 1.0}


def test_reference_attends_in_blocks_of_queries(monkeypatch):
  """70 positions in blocks of 32 queries (the last one padded) equal
  all at once."""
  params = ref._sub(_params(), "trunk/blocks_0/mixer/")
  x = jax.random.normal(jax.random.PRNGKey(2), (70, 16))
  whole = ref._latent_attention(x, params, TINY, False)
  monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
  np.testing.assert_allclose(
      ref._latent_attention(x, params, TINY, False), whole, atol=1e-6)


def test_rotary_turns_interleaved_pairs():
  """Pair j = dims (2 j, 2 j + 1) by position * theta^(-j / half); the
  reference's, and the rotate-half layout under the permutation."""
  x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 3, 8))
  got = transformer.rotary(x, 8, 1e4, interleaved=True)
  want = jax.vmap(lambda row: ref._rotary_pairs(row, 1e4))(x)
  np.testing.assert_allclose(got, want, atol=1e-6)
  order = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
  np.testing.assert_allclose(
      transformer.rotary(x[..., order], 8, 1e4), got[..., order],
      atol=1e-6)
  np.testing.assert_allclose(got[:, 0], x[:, 0])  # position 0 stays


def test_the_mixers_path_is_read_off_the_platform(monkeypatch):
  """`auto` is the kernel on a TPU and materialised attention
  elsewhere; the reader of the two counters says which ran."""
  assert lm_mla_flash_share.read({}) is None
  assert transformer._resolve_impl("auto") == "reference"
  monkeypatch.setattr(transformer, "_on_tpu", lambda: True)
  assert transformer._resolve_impl("auto") == "flash"
  tmetrics.counter(KERNEL).inc(3)
  tmetrics.counter(MATERIALISED).inc(1)
  assert lm_mla_flash_share.read({}) == 75.0


def _expert_layer(held=8, first=0, shared=8):
  return moe.SparseMoE(
      num_experts=8, experts_held=held, first_expert=first, k=3,
      expert_width=8, shared_width=shared, scoring="sigmoid",
      selection_bias=True, routed_scaling_factor=2.5,
      shared_gated=False, dtype=jnp.float32)


def _expert_params(bias_scale):
  """All 8 experts' weights of a layer, the bias `bias_scale` wide."""
  params = ref._sub(_params(model=dict(TINY, experts_held=8,
                                       first_expert=0)),
                    "trunk/blocks_1/ffn/")
  params["router_bias"] = params["router_bias"] * bias_scale / 0.02
  return params


def _share(params, first, held):
  share = dict(params)
  for name in ("experts_gate", "experts_up", "experts_down"):
    share[name] = params[name][first:first + held]
  return share


def _apply(layer, params, x):
  out, sown = layer.apply({"params": _tree(params)}, x,
                          mutable=["moe_counters"])
  return out, {name: float(value[0])
               for name, value in sown["moe_counters"].items()}


def test_sigmoid_router_with_a_bias_that_moves_choices():
  """A bias of deviation 0.5 against scores in (0, 1): the chosen are
  the largest of score + bias, the weights the unbiased scores of the
  chosen over their sum, times 2.5; no gradient reaches the bias."""
  params = _expert_params(0.5)
  x = jax.random.normal(jax.random.PRNGKey(4), (200, 16))
  experts, weights = moe.route_top_k(
      x, params["router"], 3, scoring="sigmoid",
      bias=params["router_bias"], scale=2.5)
  scores = jax.nn.sigmoid(x @ params["router"])
  np.testing.assert_array_equal(
      experts, jax.lax.top_k(scores + params["router_bias"], 3)[1])
  unbiased = jax.lax.top_k(scores, 3)[1]
  moved = np.mean(np.sort(experts, -1) != np.sort(unbiased, -1))
  assert moved > 0.2
  picked = jnp.take_along_axis(scores, experts, -1)
  np.testing.assert_allclose(
      weights, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
  np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
  bias_grad = jax.grad(lambda b: jnp.sum(moe.route_top_k(
      x, params["router"], 3, scoring="sigmoid", bias=b)[1] ** 2))(
          params["router_bias"])
  assert not np.any(np.asarray(bias_grad))


@pytest.mark.parametrize("first,held", [(0, 8), (2, 4)])
def test_expert_layer_equals_the_reference(first, held):
  """Output, gradients and the counter of moved choices, all experts
  held and a chip's share."""
  params = _share(_expert_params(0.5), first, held)
  model = dict(TINY, experts_held=held, first_expert=first)
  x = jax.random.normal(jax.random.PRNGKey(5), (2, 150, 16))
  probe = jax.random.normal(jax.random.PRNGKey(6), (300, 16))
  layer = _expert_layer(held, first)

  def program_out(params, x):
    return jnp.sum(_apply(layer, params, x)[0].reshape(-1, 16) * probe)

  def reference_out(params, x):
    return jnp.sum(ref._expert_ffn(x.reshape(-1, 16), params, model,
                                   False) * probe)

  got, got_grads = jax.value_and_grad(program_out, (0, 1))(params, x)
  want, want_grads = jax.value_and_grad(reference_out, (0, 1))(params, x)
  np.testing.assert_allclose(got, want, rtol=1e-4)
  for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                  jax.tree_util.tree_leaves(want_grads)):
    np.testing.assert_allclose(a, b, atol=3e-4, rtol=2e-3)
  assert "shared_expert_gate" not in layer.init(
      jax.random.PRNGKey(0), x)["params"]
  counters = _apply(layer, params, x)[1]
  assert counters["dropped_assignments"] == 0.0
  assert counters["bias_moved_choice_share"] > 0.1
  none_moved = _apply(layer, dict(params, router_bias=jnp.zeros(8)), x)
  assert none_moved[1]["bias_moved_choice_share"] == 0.0


def test_the_shares_add_up_to_the_uncut_layer():
  """Four chips of two experts each (model-configs guide, section 4):
  the routed parts of all shares, with the shared expert that every
  chip computes alike counted once, equal the uncut reference layer."""
  params = _expert_params(0.5)
  x = jax.random.normal(jax.random.PRNGKey(7), (2, 60, 16))
  no_shared = {k: v for k, v in params.items()
               if not k.startswith("shared")}
  whole, _ = _apply(_expert_layer(), params, x)
  shared_only = whole - _apply(_expert_layer(shared=0), no_shared, x)[0]
  parts = [_apply(_expert_layer(2, first, shared=0),
                  _share(no_shared, first, 2), x)
           for first in (0, 2, 4, 6)]
  want = ref._expert_ffn(x.reshape(-1, 16), params,
                         dict(TINY, experts_held=8, first_expert=0),
                         False)
  np.testing.assert_allclose(
      (sum(out for out, _ in parts) + shared_only).reshape(-1, 16),
      want, atol=3e-5, rtol=1e-4)
  assert abs(sum(counters["assignments_here_share"]
                 for _, counters in parts) - 1.0) < 1e-6


def test_dense_block_equals_the_reference():
  params = ref._sub(_params(), "trunk/blocks_0/")
  block = transformer.TransformerBlock(
      norm="rms", mixer=_mixer(),
      ffn=transformer.GatedMLP(width=24, dtype=jnp.float32),
      dtype=jnp.float32)
  x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, 16))
  got = block.apply({"params": _tree(params)}, x)
  want = ref._layer(x, params, True, TINY, False)
  np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("remat_policy", ["full", None])
def test_loss_and_gradients_equal_the_references(monkeypatch,
                                                 remat_policy):
  """Both losses and every gradient, the module on. 150 positions:
  three blocks of the reference's queries (the third padded), no
  multiple of the loss's block, nor in the module's 149."""
  t = 150
  monkeypatch.setattr(ref, "QUERY_BLOCK", 64)
  model = LatentAttentionLanguageModel(
      sequence_length=t, device_dtype=jnp.float32, loss_block=64,
      attention_impl="reference", remat_policy=remat_policy, **TINY)
  params = _params()
  shapes = jax.eval_shape(lambda: model.create_inference_state(
      jax.random.PRNGKey(0), batch_size=2))
  tree = weights_lib.place(shapes.params, params)
  ids = jax.random.randint(jax.random.PRNGKey(1), (3, t + 1), 0, 50)
  batch = {"features": {"token_ids": ids}}

  def program_loss(tree):
    loss, (scalars, _) = model.loss_fn(
        tree, {}, {"token_ids": ids}, TensorSpecStruct(), None,
        Mode.TRAIN)
    return loss, scalars

  (got, scalars), got_grads = jax.value_and_grad(
      program_loss, has_aux=True)(tree)
  (want, aux), want_grads = jax.value_and_grad(
      lambda p: ref.loss(CONFIG, p, {}, batch, None)[:2],
      has_aux=True)(params)
  np.testing.assert_allclose(got, want, rtol=1e-5)
  for name in ("lm.loss_main", "lm.loss_mtp"):
    np.testing.assert_allclose(scalars[name], aux[name], rtol=1e-5)
  np.testing.assert_allclose(
      got, scalars["lm.loss_main"] + 0.3 * scalars["lm.loss_mtp"],
      rtol=1e-6)
  got_grads = weights_lib.flatten(got_grads)
  for name, grad in want_grads.items():
    err = float(jnp.linalg.norm(got_grads[name] - grad)
                / (jnp.linalg.norm(grad) + 1e-12))
    assert err < 5e-4, (name, err)
  assert float(scalars["moe.dropped_assignments"]) == 0.0
  assert 0.3 < float(scalars["moe.assignments_here_share"]) < 0.7
  assert 0.0 < float(scalars["moe.bias_moved_choice_share"]) < 0.3
  assert float(scalars["moe.rounds_run"]) == 1.0  # the worst layer's
  # One precision lower is another number, as a whole and in parts.
  for control in (True, "attention", "router"):
    lowered = ref.loss(CONFIG, params, {}, batch, None,
                       control=control)[0]
    assert abs(float(lowered) - float(want)) > 1e-5, control


def test_the_modules_loss_counts_the_positions_that_have_a_target():
  """`next_token_loss` with `counted`: the mean over the marked
  positions, in blocks and at once."""
  hidden = jax.random.normal(jax.random.PRNGKey(0), (96, 8))
  head = jax.random.normal(jax.random.PRNGKey(1), (8, 11))
  targets = jax.random.randint(jax.random.PRNGKey(2), (96,), 0, 11)
  counted = jnp.arange(96) % 12 != 11
  logits = hidden @ head
  each = jax.nn.logsumexp(logits, -1) - logits[jnp.arange(96), targets]
  want = jnp.sum(jnp.where(counted, each, 0.0)) / jnp.sum(counted)
  for block in (32, 96, 40):
    np.testing.assert_allclose(
        language_model.next_token_loss(hidden, head, targets, block,
                                       jnp.float32, counted),
        want, rtol=1e-6)


def _cell_config():
  _, _, config, _ = run_lib.load_cell(CELL)
  return config


def test_shipped_gin_file_builds_the_cells_680_441_088_parameters():
  """The shipped gin file under the cell's four bindings builds the
  published widths with the dense layer, four expert layers of 16
  experts, the module and the vocabulary's slice: the count ISSUE 36
  reckons, part by part; the benchmark's weights have the program's
  tree; unbound it is the published model."""
  config = _cell_config()
  model = program.build_model(config)
  shapes = jax.eval_shape(lambda: model.create_train_state(
      jax.random.PRNGKey(0), batch_size=1))
  flat = weights_lib.flatten(shapes.params)
  count = lambda prefix: sum(  # noqa: E731
      int(np.prod(leaf.shape)) for name, leaf in flat.items()
      if name.startswith(prefix))
  assert count("trunk/blocks_0/mixer") == 26_347_520
  assert count("trunk/blocks_0/") == 70_391_808
  assert count("trunk/blocks_1/") == 107_092_224
  assert count("trunk/blocks_4/ffn") == (
      16 * 4_718_592 + 4_718_592 + 524_288 + 256)
  assert count("mtp/") == 115_486_976
  assert count("embed_tokens") == count("lm_head") == 33_095_680
  assert count("") == 680_441_088
  want = joyai_llm_flash_weights.param_shapes(config["model"])
  assert {k: tuple(v.shape) for k, v in flat.items()} == want
  spec = model.get_feature_specification(Mode.TRAIN)
  assert tuple(spec["token_ids"].shape) == (8193,)
  published = program.build_model(dict(config, gin_bindings=[], model={}))
  assert (published._num_hidden_layers, published._vocab_size,
          published._experts_held) == (40, 129280, 256)


def test_the_configuration_file_holds_the_published_config():
  """Every key of the catalog's row, at the top level and (where the
  model takes it) in the `model` block; only depth and vocabulary
  differ, and `experts_held` counts the chip's share."""
  config = _cell_config()
  with open(os.path.join(run_lib.HERE, "tests", "data", "widths",
                         "joyai_llm_flash_ep16.json")) as f:
    pin = json.load(f)
  published = {k: v for k, v in pin.items()
               if k not in ("_note", "model")}
  assert len(published) == 34  # the row has 36 keys
  for key, value in published.items():
    assert config[key] == value, key
    if key in config["model"]:
      assert config["model"][key] == value, key
  assert (config["num_hidden_layers"], config["vocab_size"],
          config["experts_held"]) == (5, 16160, 16)
  assert sorted(config["reduced"]) == ["experts_held",
                                       "num_hidden_layers", "vocab_size"]
  assert "16 chips" in config["deployment"]
  for key in ("sequence_length", "multi_token_prediction",
              "router_bias", "norms", "weights", "adam_nu0"):
    assert key in config["assumed"], key
  tiny = config["rehearse_cpu"]["model"]
  assert tiny["qk_nope_head_dim"] + tiny["qk_rope_head_dim"] != \
      tiny["v_head_dim"]
  assert tiny["experts_held"] < tiny["n_routed_experts"]
  assert 0 < tiny["first_k_dense_replace"] < tiny["num_hidden_layers"]


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
  # ISSUE 37's share is read off the checkpoints' policy: the shipped
  # gin file's `save_attention`, whatever backend the mixer took.
  # ISSUE 38's seven read the account of the start that the loop closed
  # at its first log; ISSUE 39's the rounds that the expert layers ran.
  assert result["metric_names"] == [
      "lm_attention_saved_share", "lm_mla_flash_share",
      "lm_moe_rounds_run", "startup_cache_hit_share", "startup_compile_s",
      "startup_first_metrics_s", "startup_init_state_s",
      "startup_programs", "startup_restore_s", "startup_unnamed_share"]
  window = json.loads(next(line for line in lines
                           if line.startswith("window:"))[7:])
  assert window["checkpoint_stalls_ms"] == []
  assert 0 < window["steps"] < 1000 and window["steps"] % 2 == 0


def _no_mtp_loss(monkeypatch):
  real = language_model.next_token_loss
  # The module's loss is the one that counts a part of its positions.
  monkeypatch.setattr(
      language_model, "next_token_loss",
      lambda *args: real(*args) * (1.0 if len(args) == 5 else 0.0))


def _no_bias(monkeypatch):
  real = moe.choose_top_k
  monkeypatch.setattr(
      moe, "choose_top_k",
      lambda scores, k, normalise=True, bias=None, scale=1.0: real(
          scores, k, normalise, None, scale))


def _no_rope_half_of_the_keys(monkeypatch):
  real = transformer.rotary

  def rotary(x, *args, **kwargs):
    # k_r is the one head that all heads share.
    return jnp.zeros_like(x) if x.shape[2] == 1 else real(
        x, *args, **kwargs)

  monkeypatch.setattr(transformer, "rotary", rotary)


@pytest.mark.parametrize("take_out", [
    _no_mtp_loss, _no_bias, _no_rope_half_of_the_keys])
def test_rehearsed_cell_with_a_part_taken_out_is_not_correct(
    capsys, monkeypatch, take_out):
  """The timed path broken underneath: the module's loss left out of
  the sum, the selection bias ignored, the shared rope key zeroed."""
  take_out(monkeypatch)
  result, lines = _rehearse(capsys, monkeypatch)
  assert result["correct"] is False
  assert any("FAILED" in line for line in lines)


def _run_record(records, trace=None):
  return {"records": records, "trace": trace, "k": 2, "batch": 2,
          "chips": 1, "device_kind": "TPU v5 lite",
          "config": _cell_config()}


def test_lm_mla_step_mfu_on_made_up_records():
  assert lm_mla_step_mfu.read(_run_record([{"step": 2}])) is None
  records = [{"moe.assignments_here_share": 0.0625}] * 2
  # Two whole programs of two steps each in 4.52 s of device time: a
  # step of 55.7 TFLOP in 1.13 s is a quarter of 197 TFLOP/s.
  run = _run_record(records, {"program_runs": 2,
                              "program_busy_s": 4.52})
  assert lm_mla_step_mfu.read(run) == pytest.approx(25.0, abs=0.1)
  cut = _run_record(records, {"program_runs": 0, "program_busy_s": 0.0})
  assert lm_mla_step_mfu.read(cut) is None


def test_benchmark_json_has_the_new_entries_and_no_other():
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  # ISSUE 47 appended a fourth family's configuration, its cell and
  # its nine metrics (tests/test_channel_gated_language_model.py),
  # ISSUE 43 a third's with its seven
  # (tests/test_windowed_language_model.py).
  assert bench["configs"][-3]["name"] == "joyai_llm_flash_ep16"
  assert bench["workloads"][-3] == {
      "name": CELL, "config": "joyai_llm_flash_ep16",
      "traffic": "train_eval", "chips": 1,
      "why": bench["workloads"][-3]["why"]}
  newest = [m for m in bench["per_layer"]
            if m["name"].startswith("lm_kda_")]
  assert len(newest) == 9
  assert bench["per_layer"][-9:] == newest
  bench["per_layer"] = bench["per_layer"][:-9]
  # ISSUE 45 appended one entry of the flash kernel's backward pass,
  # which lists this cell from its birth
  # (benchmark/tests/test_lm_flash_backward_fused_share.py).
  later = [m for m in bench["per_layer"]
           if m["name"].startswith("lm_swa_")
           or m["name"] == "lm_flash_backward_fused_share"]
  assert len(later) == 8
  assert bench["per_layer"][-8:] == later
  bench["per_layer"] = [m for m in bench["per_layer"] if m not in later]
  # ISSUE 42 appended the fused forward pass of the other family's
  # delta rule and ISSUE 41 its recomputation; ISSUE 39 the rounds
  # that both families' expert layers run; ISSUE 38 start-up's seven,
  # which every cell reports.
  assert bench["per_layer"][-1] == {
      "name": "lm_gdn_fused_forward_share", "unit": "%",
      "better": "higher", "source": "program_counter",
      "layer": "sequence trunk", "moves": "train_steps_per_s",
      "workloads": ["qwen3next_80b_a3b_ep16.train_eval"]}
  assert bench["per_layer"][-2] == {
      "name": "lm_gdn_recompute_device_ms", "unit": "ms",
      "better": "lower", "source": "device_trace",
      "layer": "sequence trunk", "moves": "train_steps_per_s",
      "workloads": ["qwen3next_80b_a3b_ep16.train_eval"]}
  assert bench["per_layer"][-3] == {
      "name": "lm_moe_rounds_run", "unit": "count", "better": "lower",
      "source": "program_counter", "layer": "expert layer",
      "moves": "train_steps_per_s",
      "workloads": ["qwen3next_80b_a3b_ep16.train_eval", CELL]}
  startup = [m for m in bench["per_layer"] if m["layer"] == "start-up"]
  assert bench["per_layer"][-10:-3] == startup
  per_layer = bench["per_layer"][:-10]
  assert [m["name"] for m in per_layer[-3:]] == [
      "lm_mla_step_mfu", "lm_mla_flash_share",
      "lm_attention_saved_share"]
  assert per_layer[-1] == {  # ISSUE 37's one entry
      "name": "lm_attention_saved_share", "unit": "%",
      "better": "higher", "source": "program_counter",
      "layer": "sequence trunk", "moves": "train_steps_per_s",
      "workloads": ["qwen3next_80b_a3b_ep16.train_eval", CELL]}
  for metric in per_layer[:-1]:
    assert (CELL in metric["workloads"]) == metric["name"].startswith(
        "lm_mla_")
