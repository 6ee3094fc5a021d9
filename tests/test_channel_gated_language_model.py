"""The channel-gated delta-rule language model
(`ChannelGatedDeltaLanguageModel` of `models/language_model.py`; ISSUE
47: Kimi-Linear) and what it forced, at small sizes: the delta rule
with a decay per key channel against the recurrence over positions,
forward and every gradient, at decays from gentle to what overflows a
factored form; the walk's kernels with a vector `end_decay` in the
Pallas interpreter against `scan_walk`; `KimiDeltaAttention` and
`LatentAttention` without a query latent or positions against the plain
reference `benchmark/reference/kimi_linear.py`; the whole model's loss
and gradients, and the control failing the limit the program meets; the
32 shares of an expert-parallel layer adding up; the shipped gin file
at the published widths; the benchmark's cell of it rehearsed on the
CPU through `benchmark/run.py`, `correct` for the shipped step and not
with a part of the mathematics taken out; the FLOP count's cases and
the readers; the walk's vector programs compiled for a v5e at the
cell's widths."""

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
from benchmark.harness import check as check_lib  # noqa: E402
from benchmark.harness import program  # noqa: E402
from benchmark.harness import weights as weights_lib  # noqa: E402
from benchmark.reference import kimi_linear as ref  # noqa: E402
from benchmark.reference import kimi_linear_weights  # noqa: E402
from benchmark.tests.test_kda_flops import *  # noqa: E402,F401,F403
from tensor2robot_tpu import config as gin  # noqa: E402
from tensor2robot_tpu import ops  # noqa: E402
from tensor2robot_tpu.data.abstract_input_generator import (  # noqa: E402
    Mode)
from tensor2robot_tpu.layers import gated_delta, transformer  # noqa: E402
from tensor2robot_tpu.models import language_model  # noqa: E402
from tensor2robot_tpu.models.language_model import (  # noqa: E402
    ChannelGatedDeltaLanguageModel)
from tensor2robot_tpu.ops import delta_rule_walk  # noqa: E402
from tensor2robot_tpu.parallel import moe  # noqa: E402
from tensor2robot_tpu.specs import TensorSpecStruct  # noqa: E402
from tensor2robot_tpu.telemetry import metrics as tmetrics  # noqa: E402

CELL = "kimi_linear_48b_a3b_ep32.train_eval"
# The cell's five layers: KDA with the dense FFN; KDA, KDA, latent
# attention, KDA with expert FFNs. Keys 8 + 4 wide over values 6 wide;
# the chip holds experts 2-5 of 8.
LINEAR = {"kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
          "num_heads": 4, "head_dim": 8, "short_conv_kernel_size": 4}
TINY = dict(
    vocab_size=50, hidden_size=16, num_hidden_layers=5,
    linear_attn_config=LINEAR, num_attention_heads=4, q_lora_rank=None,
    kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=6, mla_use_nope=True, first_k_dense_replace=1,
    intermediate_size=24, num_experts=8, experts_held=4, first_expert=2,
    num_experts_per_token=3, moe_renormalize=True,
    moe_router_activation_func="sigmoid", routed_scaling_factor=2.446,
    num_shared_experts=1, moe_intermediate_size=8, rms_norm_eps=1e-5)
CONFIG = {"model": TINY}


@pytest.fixture(autouse=True)
def _fresh_gin_and_counters():
  gin.clear_config()
  tmetrics.registry().reset()
  yield
  gin.clear_config()
  tmetrics.registry().reset()


def _params(seed=5, model=TINY):
  return kimi_linear_weights.make_weights(seed, {"model": model})[0]


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


# --- the rule with a decay per key channel -----------------------------

def _rule_inputs(t, h, dk, dv, decay, seed=0):
  """q, k as the layer hands them on; g a position between a fifth of
  `decay` and `decay` in every channel."""
  keys = jax.random.split(jax.random.PRNGKey(seed), 6)
  q = gated_delta.l2_normalize(
      jax.random.normal(keys[0], (2, t, h, dk))) * dk ** -0.5
  k = gated_delta.l2_normalize(jax.random.normal(keys[1], (2, t, h, dk)))
  v = jax.random.normal(keys[2], (2, t, h, dv))
  g = decay * jax.random.uniform(keys[3], (2, t, h, dk), minval=0.2,
                                 maxval=1.0)
  beta = jax.nn.sigmoid(jax.random.normal(keys[4], (2, t, h)))
  probe = jax.random.normal(keys[5], (2, t, h, dv))
  return (q, k, v, g, beta), probe


def _recurrence(q, k, v, g, beta):
  """The reference's recurrence over positions, a row at a time."""
  return jax.vmap(functools.partial(ref._channel_delta_rule,
                                    control=False))(q, k, v, g, beta)


def _assert_rule_equals_the_recurrence(operands, probe, **kwargs):
  got, got_grads = jax.value_and_grad(
      lambda *a: jnp.sum(gated_delta.gated_delta_rule(*a, chunk=64,
                                                      **kwargs) * probe),
      range(5))(*operands)
  want, want_grads = jax.value_and_grad(
      lambda *a: jnp.sum(_recurrence(*a) * probe), range(5))(*operands)
  out = gated_delta.gated_delta_rule(*operands, chunk=64, **kwargs)
  assert bool(jnp.all(jnp.isfinite(out)))
  np.testing.assert_allclose(out, _recurrence(*operands), atol=2e-5)
  np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
  for name, a, b in zip("qkvgb", got_grads, want_grads):
    assert bool(jnp.all(jnp.isfinite(a))), name
    scale = float(jnp.max(jnp.abs(b))) + 1e-6
    np.testing.assert_allclose(a / scale, b / scale, atol=2e-5,
                               err_msg=name)


@pytest.mark.parametrize("decay", [-0.01, -1.0, -12.0])
def test_channel_gated_rule_equals_the_recurrence(decay):
  """Output and all five gradients over 150 positions (two chunks and
  22 of a third) at a decay a position of -0.01 (nothing forgotten in
  a chunk), -1 and -12: at the last a chunk's running sum reaches
  -768, where exp(-G) is infinite in float32 and only reference points
  keep every exponential's argument <= 0."""
  operands, probe = _rule_inputs(150, 2, 16, 24, decay)
  with jax.default_matmul_precision("highest"):
    _assert_rule_equals_the_recurrence(operands, probe)
  counts = tmetrics.registry().scalars("gated_delta.")
  assert counts["gated_delta.channel_gate.scan_traces"] >= 1.0
  assert "gated_delta.channel_gate.kernel_traces" not in counts
  assert "gated_delta.forward.fused_traces" not in counts
  assert counts["gated_delta.forward.prepared_traces"] >= 1.0


def _aligned(k, spread, seed=7):
  """Keys that share a direction a head: the common one plus `spread`
  of each position's own."""
  common = jax.random.normal(jax.random.PRNGKey(seed),
                             k.shape[:1] + (1,) + k.shape[2:])
  return gated_delta.l2_normalize(common + spread * k)


@pytest.mark.parametrize("spread", [1.0, 0.2])
def test_channel_gated_rule_with_aligned_keys_equals_the_recurrence(spread):
  """A chunk's keys at a cosine of 0.5 and of 0.96 under beta near 1 and
  a decay that forgets nothing in a chunk: every entry of A below the
  diagonal is then of one sign and up to 0.96, where the inverse as a
  product of (I + A^2^i) loses everything to rounding (the unit test
  below) and the rule's output, exact in the recurrence, went to 1e9
  and on to NaN (the Kimi-Linear job's fifteenth step, PERF.md section
  6, PR 47)."""
  (q, k, v, g, beta), probe = _rule_inputs(150, 2, 16, 24, -0.01, seed=4)
  k = _aligned(k, spread)
  beta = 0.9 + 0.1 * beta
  with jax.default_matmul_precision("highest"):
    _assert_rule_equals_the_recurrence((q, k, v, g, beta), probe)


@pytest.mark.parametrize("entry", [0.15, 0.42, 0.9, -0.3])
def test_unit_lower_inverse_by_halves_is_the_inverse(entry):
  """Against float64 at 64 positions with every entry below the
  diagonal `entry`, value and gradient; the product form
  (`_unit_lower_inverse`, the scalar gate's) is held to the same only
  where it can be: at 0.42 it returns 26 for an inverse whose largest
  entry is 1."""
  c = 64
  rows = np.arange(c)
  a = np.where(rows[:, None] > rows[None, :], entry, 0.0)
  exact = np.linalg.inv(np.eye(c) + a)
  probe = np.random.default_rng(0).normal(size=(c, c))
  want_grad = -exact.T @ probe @ exact.T
  scale, grad_scale = np.abs(exact).max(), np.abs(want_grad).max()
  with jax.default_matmul_precision("highest"):
    for inverse in (gated_delta._unit_lower_inverse_by_halves,
                    gated_delta._unit_lower_inverse):
      if inverse is gated_delta._unit_lower_inverse and abs(entry) > 0.3:
        continue
      got, grad = jax.value_and_grad(
          lambda x: jnp.sum(inverse(x) * probe))(jnp.asarray(a, jnp.float32))
      del got
      np.testing.assert_allclose(
          np.asarray(inverse(jnp.asarray(a, jnp.float32))) / scale,
          exact / scale, atol=1e-4 if inverse is
          gated_delta._unit_lower_inverse else 2e-6)
      np.testing.assert_allclose(np.asarray(grad) / grad_scale,
                                 want_grad / grad_scale, atol=1e-4)


@pytest.mark.parametrize("decay", [-1.0, -12.0])
def test_channel_gated_rule_through_the_walks_kernels(decay):
  """The same with the walk's Pallas pair (interpreted) at widths that
  tile: a vector `end_decay` scales the rows of a state that the
  kernels hold transposed."""
  operands, probe = _rule_inputs(130, 2, 128, 128, decay, seed=1)
  with jax.default_matmul_precision("highest"):
    _assert_rule_equals_the_recurrence(operands, probe, interpret=True)
  counts = tmetrics.registry().scalars("gated_delta.channel_gate.")
  assert counts == {"gated_delta.channel_gate.kernel_traces":
                    counts["gated_delta.channel_gate.kernel_traces"]}


def test_a_gate_constant_over_the_channels_is_the_scalar_gates_rule():
  """g [B, T, H, Dk] with one value a head equals the call with g
  [B, T, H] on the same numbers: two preparations, one rule."""
  (q, k, v, g, beta), _ = _rule_inputs(130, 2, 16, 24, -1.0, seed=2)
  scalar = g[..., 0]
  with jax.default_matmul_precision("highest"):
    np.testing.assert_allclose(
        gated_delta.gated_delta_rule(
            q, k, v, jnp.broadcast_to(scalar[..., None], g.shape), beta),
        gated_delta.gated_delta_rule(q, k, v, scalar, beta), atol=2e-6)


def _walk_operands(vector, seed=3, n=3, b=1, h=3, c=16, dk=128, dv=128):
  keys = jax.random.split(jax.random.PRNGKey(seed), 7)
  wide = lambda key, d: jax.random.normal(key, (n, b, h, c, d))  # noqa: E731
  end_decay = jax.random.uniform(
      keys[4], (n, b, h) + ((dk,) if vector else ()), minval=0.1)
  return ((wide(keys[0], dv), 0.3 * wide(keys[1], dk),
           0.3 * wide(keys[2], dk), 0.3 * wide(keys[3], dk), end_decay),
          (wide(keys[5], dv), wide(keys[6], dv)))


@pytest.mark.parametrize("vector", [False, True])
def test_walk_kernels_equal_the_scan(vector):
  """`ops/delta_rule_walk.walk` in the Pallas interpreter against
  `scan_walk`, both results and all five cotangents, with `end_decay`
  a head's scalar and a vector over the key channels (three heads in
  blocks of two: the last block is ragged)."""
  operands, probes = _walk_operands(vector)

  def through(walk):
    def scalar(*args):
      new, carried = walk(*args)
      return jnp.sum(new * probes[0]) + jnp.sum(carried * probes[1])
    return jax.value_and_grad(scalar, range(5))(*operands)

  with jax.default_matmul_precision("highest"):
    got, got_grads = through(functools.partial(
        delta_rule_walk.walk, block=2, interpret=True))
    want, want_grads = through(gated_delta.scan_walk)
  np.testing.assert_allclose(got, want, rtol=1e-5)
  for a, b in zip(got_grads, want_grads):
    assert a.shape == b.shape
    np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


# --- the two mixers ------------------------------------------------------

def _latent_mixer(impl="reference"):
  return transformer.LatentAttention(
      num_heads=4, q_lora_rank=None, kv_lora_rank=8, qk_nope_head_dim=8,
      qk_rope_head_dim=4, v_head_dim=6, rope_theta=None, eps=1e-5,
      attention_impl=impl, dtype=jnp.float32)


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_latent_attention_without_latent_query_or_positions(monkeypatch,
                                                            impl):
  """`q_lora_rank=None`, `rope_theta=None` against the reference,
  output and gradients; `flash` is the Pallas kernel (interpreted) at
  keys of 12 over values of 6. Nothing is turned: a row shifted by a
  position gives the same outputs a position later."""
  monkeypatch.setattr(ops, "flash_attention", functools.partial(
      ops.flash_attention, block_q=32, block_k=64, interpret=True))
  monkeypatch.setattr(ref, "QUERY_BLOCK", 64)
  params = ref._sub(_params(), "trunk/blocks_3/mixer/")
  assert sorted(params) == [
      "kv_a_norm/weight", "kv_a_proj/kernel", "kv_b_proj/kernel",
      "o_proj/kernel", "q_proj/kernel"]
  x = jax.random.normal(jax.random.PRNGKey(0), (2, 96, 16))
  probe = jax.random.normal(jax.random.PRNGKey(1), (2, 96, 16))

  def program_out(params, x):
    return jnp.sum(_latent_mixer(impl).apply({"params": _tree(params)},
                                             x) * probe)

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
  assert counts == {"mla.attend.kernel_traces" if impl == "flash"
                    else "mla.attend.materialised_traces": 1.0}


@pytest.mark.parametrize("heads", [4, 16])
def test_kimi_delta_attention_equals_the_reference(heads):
  """The layer (its three convolutions, two low-rank gates, the rule,
  the gated head-wise norm) against the reference's, output and
  gradients; at 16 heads the map takes two groups of eight a row."""
  linear = dict(LINEAR, num_heads=heads)
  model = dict(TINY, linear_attn_config=linear)
  params = ref._sub(_params(model=model), "trunk/blocks_1/mixer/")
  mixer = gated_delta.KimiDeltaAttention(
      num_heads=heads, head_dim=8, eps=1e-5, dtype=jnp.float32)
  x = jax.random.normal(jax.random.PRNGKey(0), (2, 150, 16))
  probe = jax.random.normal(jax.random.PRNGKey(1), (2, 150, 16))

  def program_out(params, x):
    return jnp.sum(mixer.apply({"params": _tree(params)}, x) * probe)

  def reference_out(params, x):
    return jnp.sum(jax.vmap(
        lambda row: ref._kimi_delta_attention(row, params, model,
                                              False))(x) * probe)

  with jax.default_matmul_precision("highest"):
    got, got_grads = jax.value_and_grad(program_out, (0, 1))(params, x)
    want, want_grads = jax.value_and_grad(reference_out, (0, 1))(params,
                                                                 x)
  np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
  for name in params:
    err = float(jnp.linalg.norm(got_grads[0][name] - want_grads[0][name])
                / (jnp.linalg.norm(want_grads[0][name]) + 1e-12))
    assert err < 5e-4, (name, err)
  np.testing.assert_allclose(got_grads[1], want_grads[1], atol=1e-4,
                             rtol=1e-3)


def test_kimi_delta_attention_initialises_its_decay_as_published():
  """A job started from the gin file draws the decay's two parameters
  as the benchmark's weights module and the public layer do: A uniform
  in [1, 16] a head, the step log-uniform in [0.001, 0.1] a channel."""
  mixer = gated_delta.KimiDeltaAttention(num_heads=32, head_dim=16)
  params = mixer.init(jax.random.PRNGKey(3),
                      jnp.zeros((1, 8, 16)))["params"]
  rate = np.exp(np.asarray(params["A_log"]))
  step = np.asarray(jax.nn.softplus(params["dt_bias"]))
  assert rate.shape == (32,) and step.shape == (32 * 16,)
  assert 1.0 <= rate.min() < 4.0 and 12.0 < rate.max() <= 16.0
  assert 0.001 <= step.min() < 0.002 and 0.05 < step.max() <= 0.1 + 1e-6
  assert abs(np.median(np.log(step)) - np.log(0.01)) < 0.5


# --- the expert layer's shares -------------------------------------------

def _expert_layer(num=64, held=64, first=0, shared=8):
  return moe.SparseMoE(
      num_experts=num, experts_held=held, first_expert=first, k=8,
      expert_width=8, shared_width=shared, scoring="sigmoid",
      selection_bias=True, routed_scaling_factor=2.446,
      shared_gated=False, dtype=jnp.float32)


def _apply(layer, params, x):
  out, sown = layer.apply({"params": _tree(params)}, x,
                          mutable=["moe_counters"])
  return out, {name: float(value[0])
               for name, value in sown["moe_counters"].items()}


def test_the_32_shares_add_up_to_the_uncut_layer():
  """32 chips of two experts each, the cell's deployment in small
  (model-configs guide, section 4): the routed parts of all 32 shares,
  with the shared expert that every chip computes alike counted once,
  equal the uncut reference layer; the shares of the assignments add
  up to one."""
  model = dict(TINY, num_experts=64, experts_held=64, first_expert=0,
               num_experts_per_token=8)
  params = ref._sub(_params(model=model), "trunk/blocks_1/ffn/")
  params["router_bias"] = params["router_bias"] * 25  # moves choices
  x = jax.random.normal(jax.random.PRNGKey(7), (2, 60, 16))
  no_shared = {k: v for k, v in params.items()
               if not k.startswith("shared")}
  whole, counters = _apply(_expert_layer(), params, x)
  assert counters["bias_moved_choice_share"] > 0.0
  shared_only = whole - _apply(_expert_layer(shared=0), no_shared, x)[0]

  def share(first):
    held = dict(no_shared)
    for name in ("experts_gate", "experts_up", "experts_down"):
      held[name] = no_shared[name][first:first + 2]
    return _apply(_expert_layer(held=2, first=first, shared=0), held, x)

  parts = [share(first) for first in range(0, 64, 2)]
  assert len(parts) == 32
  want = ref._expert_ffn(x.reshape(-1, 16), params,
                         ref._router_model(model), False)
  np.testing.assert_allclose(
      (sum(out for out, _ in parts) + shared_only).reshape(-1, 16),
      want, atol=3e-5, rtol=1e-4)
  np.testing.assert_allclose(whole.reshape(-1, 16), want, atol=3e-5,
                             rtol=1e-4)
  assert abs(sum(c["assignments_here_share"] for _, c in parts)
             - 1.0) < 1e-6


# --- the whole model -----------------------------------------------------

def _model(t, **kwargs):
  return ChannelGatedDeltaLanguageModel(
      sequence_length=t, device_dtype=jnp.float32, loss_block=64,
      attention_impl="reference", **dict(TINY, **kwargs))


def test_the_layer_lists_are_the_published_ones_read_from_their_head():
  model = ChannelGatedDeltaLanguageModel()
  kinds = [type(model._block(i).mixer).__name__ for i in range(27)]
  assert kinds == (["KimiDeltaAttention"] * 3 + ["LatentAttention"]) * 6 \
      + ["KimiDeltaAttention"] * 2 + ["LatentAttention"]
  assert [type(model._block(i).ffn).__name__ for i in range(3)] == [
      "GatedMLP", "SparseMoE", "SparseMoE"]
  latent = model._block(3).mixer
  assert (latent.q_lora_rank, latent.rope_theta) == (None, None)
  assert _model(8, mla_use_nope=False, rope_theta=1e4)._block(
      3).mixer.rope_theta == 1e4
  with pytest.raises(ValueError, match="each of the 5 layers once"):
    _model(8, linear_attn_config=dict(LINEAR, kda_layers=[1, 2, 3]))
  with pytest.raises(ValueError, match="group-limited"):
    _model(8, num_expert_group=2)


def test_loss_and_gradients_equal_the_references(monkeypatch):
  """The loss and every gradient under the shipped checkpoint policy.
  150 positions: two chunks of the rule and 22 positions of a third,
  three blocks of the reference's queries (the third padded), no
  multiple of the loss's block."""
  t = 150
  monkeypatch.setattr(ref, "QUERY_BLOCK", 64)
  model = _model(t, remat_policy="save_attention")
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

  with jax.default_matmul_precision("highest"):
    (got, scalars), got_grads = jax.value_and_grad(
        program_loss, has_aux=True)(tree)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(CONFIG, p, {}, batch, None)[0])(params)
  np.testing.assert_allclose(got, want, rtol=1e-5)
  got_grads = weights_lib.flatten(got_grads)
  worst = 0.0
  for name, grad in want_grads.items():
    if name.endswith("router_bias"):  # no gradient reaches it
      assert not np.any(got_grads[name]) and not np.any(grad)
      continue
    err = float(jnp.linalg.norm(got_grads[name] - grad)
                / (jnp.linalg.norm(grad) + 1e-12))
    worst = max(worst, err)
    assert err < 1e-3, (name, err)
  assert float(scalars["moe.dropped_assignments"]) == 0.0
  assert 0.3 < float(scalars["moe.assignments_here_share"]) < 0.7
  # The control, one precision lower, fails the limit that the program
  # meets by two orders: the same norm of the gradients' difference.
  lowered = jax.grad(lambda p: ref.loss(CONFIG, p, {}, batch, None,
                                        control=True)[0])(params)
  control = check_lib.rel_err(
      {k: np.asarray(v) for k, v in lowered.items()},
      {k: np.asarray(v) for k, v in want_grads.items()})
  sound = check_lib.rel_err(
      {k: np.asarray(v) for k, v in got_grads.items()},
      {k: np.asarray(v) for k, v in want_grads.items()})
  assert sound < 1e-3 < 0.02 < control, (sound, control)
  counts = tmetrics.registry().scalars("")
  assert counts["gated_delta.channel_gate.scan_traces"] >= 4.0
  assert counts["mla.attend.materialised_traces"] >= 1.0


def _cell_config():
  _, _, config, _ = run_lib.load_cell(CELL)
  return config


def test_shipped_gin_file_builds_the_cells_602_450_816_parameters():
  """The shipped gin file under the cell's four bindings builds the
  published widths: four KDA mixers and one of latent attention, the
  dense layer, four expert layers of 8 experts and the vocabulary's
  slice: the count ISSUE 47 reckons, part by part; the benchmark's
  weights have the program's tree; unbound it is the published model."""
  config = _cell_config()
  model = program.build_model(config)
  shapes = jax.eval_shape(lambda: model.create_train_state(
      jax.random.PRNGKey(0), batch_size=1))
  flat = weights_lib.flatten(shapes.params)
  count = lambda prefix: sum(  # noqa: E731
      int(np.prod(leaf.shape)) for name, leaf in flat.items()
      if name.startswith(prefix))
  assert count("trunk/blocks_0/mixer") == 39_518_368   # KDA
  assert count("trunk/blocks_3/mixer") == 29_114_880   # latent attention
  assert count("trunk/blocks_0/ffn") == 63_700_992     # dense at 9216
  assert count("trunk/blocks_1/ffn") == (
      8 * 7_077_888 + 7_077_888 + 589_824 + 256)
  assert count("embed_tokens") == count("lm_head") == 47_185_920
  assert count("") == 602_450_816 == config["parameters"]["total"]
  want = kimi_linear_weights.param_shapes(config["model"])
  assert {k: tuple(v.shape) for k, v in flat.items()} == want
  spec = model.get_feature_specification(Mode.TRAIN)
  assert tuple(spec["token_ids"].shape) == (8193,)
  assert model._remat_policy == "save_attention"
  published = program.build_model(dict(config, gin_bindings=[], model={}))
  assert (published._num_hidden_layers, published._vocab_size,
          published._experts_held) == (27, 163840, 256)


def test_the_configuration_file_holds_the_published_config():
  """Every key of the catalog's row, at the top level and (where the
  model takes it) in the `model` block; only depth and vocabulary
  differ, and `experts_held` counts the chip's share."""
  config = _cell_config()
  with open(os.path.join(run_lib.HERE, "tests", "data", "widths",
                         "kimi_linear_48b_a3b_ep32.json")) as f:
    pin = json.load(f)
  published = {k: v for k, v in pin.items()
               if k not in ("_note", "model")}
  assert len(published) == 32  # the row has 34 keys
  for key, value in published.items():
    assert config[key] == value, key
    if key in config["model"]:
      assert config["model"][key] == value, key
  assert (config["num_hidden_layers"], config["vocab_size"],
          config["experts_held"]) == (5, 20480, 8)
  assert sorted(config["reduced"]) == ["experts_held",
                                       "num_hidden_layers", "vocab_size"]
  assert "32 chips" in config["deployment"]
  for key in ("sequence_length", "kda_gates", "kda_initialisation",
              "router_bias", "mla_use_nope", "norms", "weights",
              "adam_nu0"):
    assert key in config["assumed"], key
  assert config["train"]["batch_size_per_chip"] == 4
  tiny = config["rehearse_cpu"]["model"]
  assert tiny["experts_held"] < tiny["num_experts"]
  assert 0 < tiny["first_k_dense_replace"] < tiny["num_hidden_layers"]
  assert tiny["linear_attn_config"]["kda_layers"] == \
      config["linear_attn_config"]["kda_layers"]


def _rehearse(capsys, monkeypatch, trace="0"):
  monkeypatch.setattr(sys, "argv", [
      "run.py", "--workload", CELL, "--seed", "2147483659",
      "--seconds", "1", "--trace", trace, "--rehearse-cpu"])
  assert run_lib.main() == 0
  lines = capsys.readouterr().out.strip().splitlines()
  return json.loads(lines[-1]), lines


def test_rehearsed_cell_is_correct(capsys, monkeypatch):
  """The program's K steps of Adam through `train_eval_model` against
  `follow`'s on the reference's loss."""
  result, lines = _rehearse(capsys, monkeypatch, trace="1")
  assert result["correct"] is True, lines
  assert result["failed"] == 0 and result["attempted"] > 0
  assert len(result["check"]) >= 5
  # On a CPU the rule's walk is the scan: the share is 0.
  assert result["metric_names"] == ["lm_kda_kernel_share",
                                    "lm_kda_moe_rounds_run"]


def _one_decay_a_head(monkeypatch):
  real = gated_delta.gated_delta_rule
  monkeypatch.setattr(
      gated_delta, "gated_delta_rule",
      lambda q, k, v, g, beta, **kwargs: real(
          q, k, v, jnp.mean(g, axis=-1), beta, **kwargs))


def _turned_keys(monkeypatch):
  real = language_model.LatentAttention
  monkeypatch.setattr(
      language_model, "LatentAttention",
      lambda **kwargs: real(**dict(kwargs, rope_theta=1e4)))


def _no_convolution(monkeypatch):
  monkeypatch.setattr(gated_delta, "causal_depthwise_conv",
                      lambda x, kernel: x * kernel[-1])


@pytest.mark.parametrize("take_out", [
    _one_decay_a_head, _turned_keys, _no_convolution])
def test_rehearsed_cell_with_a_part_taken_out_is_not_correct(
    capsys, monkeypatch, take_out):
  """The timed path broken underneath: a head's channels decaying by
  their mean (the scalar gate's rule), the latent attention's 64 shared
  dims turned by position, the convolutions reduced to their last
  tap."""
  take_out(monkeypatch)
  result, lines = _rehearse(capsys, monkeypatch)
  assert result["correct"] is False
  assert any("FAILED" in line for line in lines)


def test_benchmark_json_has_the_new_entries_and_no_other():
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  config = next(c for c in bench["configs"]
                if c["name"] == "kimi_linear_48b_a3b_ep32")
  assert config["reduced"] == ["num_hidden_layers", "experts_held",
                               "vocab_size"]
  assert config["source"] == _cell_config()["source"]
  cell = next(w for w in bench["workloads"] if w["name"] == CELL)
  assert cell == {"name": CELL, "config": "kimi_linear_48b_a3b_ep32",
                  "traffic": "train_eval", "chips": 1, "why": cell["why"]}
  assert [w["name"] for w in bench["workloads"]
          if w["config"] == "kimi_linear_48b_a3b_ep32"] == [CELL]
  new = [m for m in bench["per_layer"] if m["name"].startswith("lm_kda_")]
  assert [m["name"] for m in new] == [
      "lm_kda_step_mfu", "lm_kda_delta_device_ms",
      "lm_kda_delta_recompute_device_ms", "lm_kda_attention_device_ms",
      "lm_kda_moe_device_ms", "lm_kda_other_device_ms",
      "lm_kda_attention_roofline",
      "lm_kda_kernel_share", "lm_kda_moe_rounds_run"]
  for metric in new:
    assert metric["workloads"] == [CELL]
    assert metric["moves"] == "train_steps_per_s"
    assert metric["better"] == (
        "lower" if metric["name"].endswith(("_device_ms", "_rounds_run"))
        else "higher")
  # No entry accepted before the cell lists it: appending it to the
  # shared lists is a `benchmark` PR's (PERF.md section 7, ROADMAP B9).
  first = bench["per_layer"].index(new[0])
  assert bench["per_layer"][first:first + 9] == new
  for metric in bench["per_layer"][:first]:
    assert CELL not in metric["workloads"], metric["name"]


# --- the walk's vector programs compiled for the chip --------------------

def test_walks_vector_programs_compile_for_a_v5e_at_the_cells_widths():
  """A group of a row of the cell (8 heads, 128 chunks of 64, keys and
  values of 128, bfloat16), forward, state-saving forward and backward
  with `end_decay` a vector: Mosaic takes the transposed state's
  products. Nothing runs."""
  from jax.experimental import topologies
  from jax.sharding import SingleDeviceSharding
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # no libtpu, or another process holds it
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
  chip = SingleDeviceSharding(topo.devices[0])
  n, b, h, c, d = 128, 1, 8, 64, 128
  assert delta_rule_walk.tiles(c, d, d, jnp.bfloat16)

  def aval(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

  avals = (aval((n, b, h, c, d), jnp.float32),
           *(aval((n, b, h, c, d), jnp.bfloat16),) * 3,
           aval((n, b, h, d), jnp.float32))

  def loss(*args):
    new, carried = delta_rule_walk.walk(*args)
    return jnp.sum(new) + 2.0 * jnp.sum(carried)

  from jax.experimental.compilation_cache import compilation_cache
  enabled = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  try:
    forward = jax.jit(delta_rule_walk.walk).lower(*avals).compile()
    backward = jax.jit(jax.grad(loss, range(5))).lower(*avals).compile()
  finally:
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()
  assert "tpu_custom_call" in forward.as_text()
  grads = backward.out_info if hasattr(backward, "out_info") else None
  if grads is not None:
    assert tuple(grads[4].shape) == (n, b, h, d)
