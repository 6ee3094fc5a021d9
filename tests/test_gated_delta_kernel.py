"""The gated delta rule's walk over chunks as a Pallas kernel pair
(ISSUE 35; `tensor2robot_tpu/ops/delta_rule_walk.py`) against the
`lax.scan` it replaces on a TPU (`layers/gated_delta.scan_walk`), in the
Pallas interpreter at tiny sizes: outputs and the five cotangents; the
rule with the kernels forced against the recurrence over positions of
`benchmark/reference/qwen3_next.py`; which path a call takes and what
the two counters and their reader say; and both kernels compiled for a
described v5e at the language model's widths."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from test_sequence_layers import _recurrence, _rule_inputs  # noqa: E402
from tensor2robot_tpu.layers import gated_delta  # noqa: E402
from tensor2robot_tpu.ops import delta_rule_walk  # noqa: E402
from tensor2robot_tpu.telemetry import metrics as tmetrics  # noqa: E402

KERNEL = "gated_delta.walk.kernel_traces"
SCAN = "gated_delta.walk.scan_traces"


@pytest.fixture(autouse=True)
def clean_registry():
  tmetrics.reset_for_tests()
  yield
  tmetrics.reset_for_tests()


def _walk_operands(monkeypatch, t, chunk, g_scale, dtype):
  """The five operands that `gated_delta_rule` hands its walk for
  seeded inputs: [N, 2, 3, chunk, 8] (6 heads to a kernel call)."""
  seen, scan_walk = [], gated_delta.scan_walk

  def record(*operands):
    seen.append(operands)
    return scan_walk(*operands)

  def prepared(*inputs):
    gated_delta.gated_delta_rule(*inputs, chunk=chunk, dtype=dtype)
    (operands,) = seen
    return operands

  with monkeypatch.context() as patch:
    patch.setattr(gated_delta, "scan_walk", record)
    return jax.jit(prepared)(*_rule_inputs(t, t, g_scale=g_scale))


def _probed(walk, probes):
  return lambda *operands: sum(
      jnp.sum(out * probe) for out, probe in zip(walk(*operands), probes))


# t, chunk, g_scale, dtype, heads a grid step (of 6; None: the
# kernel's own choice). 150 is no multiple of the chunk; g of -5 to
# -40 a position underflows float32 inside a chunk: the state is an
# exact 0 from then on.
@pytest.mark.parametrize("t,chunk,g_scale,dtype,block", [
    (150, 64, 1.0, jnp.float32, None),
    (150, 64, 1.0, jnp.bfloat16, None),
    (64, 16, 1.0, jnp.float32, 1),
    (64, 16, 1.0, jnp.bfloat16, 2),
    (64, 16, 1.0, jnp.float32, 4),     # 6 heads in blocks of 4
    (64, 16, 1.0, jnp.bfloat16, 5),
    (64, 16, 1.0, jnp.float32, 16),    # more than there are
    (16, 16, 1.0, jnp.float32, None),  # a single chunk
    (16, 16, 1.0, jnp.bfloat16, 4),
    (40, 16, 10.0, jnp.float32, None),
    (40, 16, 10.0, jnp.bfloat16, 4),
    (33, 32, 0.01, jnp.float32, 3),
])
def test_kernel_pair_equals_the_scan(monkeypatch, t, chunk, g_scale,
                                     dtype, block):
  operands = _walk_operands(monkeypatch, t, chunk, g_scale, dtype)
  assert operands[1].dtype == dtype
  probes = [jax.random.normal(jax.random.PRNGKey(9 + i),
                              operands[0].shape) for i in range(2)]

  def kernel(*x):
    return delta_rule_walk.walk(*x, block=block, interpret=True)

  # The forward pass runs the same products on the same operands.
  for name, got, want in zip(("new", "carried"),
                             jax.jit(kernel)(*operands),
                             jax.jit(gated_delta.scan_walk)(*operands)):
    assert got.dtype == want.dtype and np.all(np.isfinite(got)), name
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5,
                               err_msg=name)
  got, want = (
      jax.jit(jax.grad(_probed(walk, probes), argnums=(0, 1, 2, 3, 4)))(
          *operands) for walk in (kernel, gated_delta.scan_walk))
  # The kernel rounds a cotangent to the operands' dtype where it
  # enters a product (on a TPU XLA's default precision does, inside
  # the product; a CPU multiplies it in float32): 2^-8 a rounding, a
  # few of them in a row, against the cotangent's own size.
  tol = 1e-5 if dtype == jnp.float32 else 3e-2
  for name, a, b in zip(
      "writes k_decayed q_decayed k_to_end end_decay".split(), got, want):
    assert a.dtype == b.dtype and a.shape == b.shape, name
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.all(np.isfinite(a)), name
    np.testing.assert_allclose(a, b, atol=tol * max(np.abs(b).max(), 1.0),
                               rtol=tol, err_msg=name)


@pytest.mark.parametrize("t,chunk,g_scale", [
    (150, 64, 1.0), (64, 16, 1.0), (7, 8, 1.0), (40, 16, 10.0)])
def test_rule_through_the_kernels_equals_the_recurrence(t, chunk,
                                                        g_scale):
  args = _rule_inputs(t, t, g_scale=g_scale)
  probe = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

  def kernels(*a):
    return gated_delta.gated_delta_rule(*a, chunk=chunk, interpret=True)

  got = jax.jit(kernels)(*args)
  np.testing.assert_allclose(got, jax.jit(_recurrence)(*args), atol=2e-5,
                             rtol=2e-4)
  assert tmetrics.registry().scalars("gated_delta.walk.") == {KERNEL: 1.0}

  def through(rule):
    return jax.jit(jax.grad(lambda *a: jnp.sum(rule(*a) * probe),
                            argnums=(0, 1, 2, 3, 4)))(*args)

  for name, a, b in zip("q k v g beta".split(), through(kernels),
                        through(_recurrence)):
    assert np.all(np.isfinite(a)), name
    np.testing.assert_allclose(a, b, atol=3e-5, rtol=1e-3, err_msg=name)


@pytest.mark.parametrize("chunk,dk,dv,dtype,tiled", [
    (64, 128, 128, jnp.bfloat16, True),   # the language model's
    (64, 128, 256, jnp.float32, True),
    (8, 128, 128, jnp.float32, True),
    (8, 128, 128, jnp.bfloat16, False),   # 16 sublanes to a bf16 tile
    (64, 64, 128, jnp.bfloat16, False),
    (64, 128, 8, jnp.bfloat16, False),
    (16, 8, 8, jnp.float32, False),       # `rehearse_cpu`'s widths
])
def test_which_shapes_tile(chunk, dk, dv, dtype, tiled):
  assert delta_rule_walk.tiles(chunk, dk, dv, dtype) is tiled


@pytest.mark.parametrize("heads,block", [(32, 8), (6, 6), (12, 6),
                                         (7, 7), (13, 1), (1, 1)])
def test_head_block_divides_the_heads(heads, block):
  assert delta_rule_walk.head_block(heads) == block


@pytest.mark.parametrize("platform_is_tpu,dk,dtype,counter", [
    (False, 8, jnp.float32, SCAN),      # a CPU, whatever the shapes
    (False, 128, jnp.bfloat16, SCAN),
    (True, 8, jnp.float32, SCAN),       # a TPU, shapes that do not tile
    (True, 128, jnp.bfloat16, KERNEL),
])
def test_path_is_read_off_platform_and_shapes(monkeypatch,
                                              platform_is_tpu, dk, dtype,
                                              counter):
  walked = []

  def stand_in(*operands, interpret):
    # No Mosaic on a CPU: the choice is what is under test.
    walked.append(interpret)
    return gated_delta.scan_walk(*operands)

  monkeypatch.setattr(gated_delta, "_on_tpu", lambda: platform_is_tpu)
  monkeypatch.setattr(delta_rule_walk, "walk", stand_in)
  args = _rule_inputs(1, 32, heads=1, dk=dk, dv=dk)
  out = jax.jit(lambda *a: gated_delta.gated_delta_rule(
      *a, chunk=16, dtype=dtype))(*args)
  assert out.shape == (2, 32, 1, dk)
  assert walked == ([False] if counter == KERNEL else [])
  assert tmetrics.registry().scalars("gated_delta.walk.") == {
      counter: 1.0}


def test_counters_count_traced_calls_not_executions():
  args = _rule_inputs(2, 32)
  rule = jax.jit(lambda *a: gated_delta.gated_delta_rule(*a, chunk=16))
  for _ in range(3):
    rule(*args)
  gated_delta.gated_delta_rule(*args, chunk=16, interpret=True)
  assert tmetrics.registry().scalars("gated_delta.walk.") == {
      SCAN: 1.0, KERNEL: 1.0}


def test_reader_of_the_two_counters():
  from benchmark.layer_metrics import lm_gdn_kernel_share
  assert lm_gdn_kernel_share.read({}) is None  # as on the parent
  tmetrics.counter(SCAN).inc(3)
  assert lm_gdn_kernel_share.read({}) == pytest.approx(0.0)
  tmetrics.reset_for_tests()
  tmetrics.counter(KERNEL).inc(3)
  assert lm_gdn_kernel_share.read({}) == pytest.approx(100.0)
  tmetrics.counter(SCAN).inc(1)
  assert lm_gdn_kernel_share.read({}) == pytest.approx(75.0)


# Both kernels compiled by the TPU's compiler for a chip that is
# described and not attached, at the widths of the language model's
# cell (one row: 128 chunks of 64, 32 heads of 128 x 128, bfloat16):
# what the interpreter cannot refuse (tiling, VMEM) is refused here.
@pytest.fixture(scope="module")
def one_chip():
  from jax.experimental import topologies
  from jax.sharding import SingleDeviceSharding
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # no libtpu, or another process holds it
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
  return SingleDeviceSharding(topo.devices[0])


def _compile_for_the_chip(fn, *avals):
  """`fn` compiled for the described chip of its arguments' shardings.
  Such a compile cannot be read back from the persistent cache: keep
  it out."""
  from jax.experimental.compilation_cache import compilation_cache
  enabled = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  try:
    return jax.jit(fn).lower(*avals).compile()
  finally:
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("dtype,block", [
    (jnp.bfloat16, None), (jnp.float32, None), (jnp.bfloat16, 5)])
def test_kernels_compile_for_a_v5e_at_the_cells_widths(one_chip, dtype,
                                                       block):
  n, b, h, c, d = 128, 1, 32, 64, 128

  def aval(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

  avals = (aval((n, b, h, c, d), jnp.float32),) + 3 * (
      aval((n, b, h, c, d), dtype),) + (aval((n, b, h), jnp.float32),)

  def loss(*operands):
    new, carried = delta_rule_walk.walk(*operands, block=block)
    return jnp.sum(new * new) + jnp.sum(carried)

  compiled = _compile_for_the_chip(
      jax.grad(loss, argnums=(0, 1, 2, 3, 4)), *avals)
  assert compiled.as_text().count("tpu_custom_call") >= 2
  # The states for the backward pass, N x H x Dk x Dv float32, and no
  # second copy of them.
  states = n * h * d * d * 4
  assert states <= compiled.memory_analysis().temp_size_in_bytes < (
      3 * states)


def test_flash_kernels_compile_for_a_v5e_at_the_latent_widths(one_chip):
  """The flash kernel's three programs at keys of 192 over values of
  128 (latent attention; ISSUE 36): 2 rows of 8,192 positions and 32
  heads in bfloat16, the default blocks. In this file because one
  process describes the chip (its fixture)."""
  from tensor2robot_tpu.ops import flash_attention

  def aval(width):
    return jax.ShapeDtypeStruct((2, 8192, 32, width), jnp.bfloat16,
                                sharding=one_chip)

  def loss(q, k, v):
    return jnp.sum(flash_attention(q, k, v, causal=True)
                   .astype(jnp.float32))

  compiled = _compile_for_the_chip(
      jax.grad(loss, argnums=(0, 1, 2)), aval(192), aval(192), aval(128))
  assert compiled.as_text().count("tpu_custom_call") >= 3
  dq, dk, dv = jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)),
                              aval(192), aval(192), aval(128))
  assert (dq.shape[-1], dk.shape[-1], dv.shape[-1]) == (192, 192, 128)


def _compile_block_gradient(one_chip, block, rows, policy):
  """The gradient of one block's trunk under `policy` over `rows` rows
  of 8,192 positions at hidden 2048, compiled for the chip."""
  from tensor2robot_tpu.layers import transformer
  trunk = transformer.SequenceTrunk(blocks=(block,), remat_policy=policy)
  x = jax.ShapeDtypeStruct((rows, 8192, 2048), jnp.float32,
                           sharding=one_chip)
  params = jax.tree_util.tree_map(
      lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                        sharding=one_chip),
      jax.eval_shape(trunk.init, jax.random.PRNGKey(0), x))

  def loss(params, x):
    return jnp.sum(jnp.square(trunk.apply(params, x)))

  return _compile_for_the_chip(jax.grad(loss), params, x)


@pytest.mark.parametrize("policy,forward_calls", [
    ("full", 2), ("save_attention", 1)])
def test_a_checkpointed_latent_block_compiles_for_a_v5e(
    one_chip, policy, forward_calls):
  """One block of the JoyAI cell (latent attention at 192 over 128, the
  dense FFN; 2 rows of 8,192 positions) under a checkpoint, its
  gradient compiled for the chip: the forward kernel once under
  `save_attention`, twice under `full`, beside the backward pair
  (ISSUE 37; the jaxpr's side of it: tests/test_sequence_layers.py)."""
  from tensor2robot_tpu.layers import transformer

  compiled = _compile_block_gradient(one_chip, transformer.TransformerBlock(
      norm="rms", mixer=transformer.LatentAttention(
          num_heads=32, q_lora_rank=1536, kv_lora_rank=512,
          qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
          attention_impl="flash"),
      ffn=transformer.GatedMLP(width=7168)), rows=2, policy=policy)
  assert compiled.as_text().count("tpu_custom_call") == forward_calls + 2


def test_a_checkpointed_delta_net_block_compiles_for_a_v5e(
    one_chip, monkeypatch):
  """One Gated-DeltaNet block at the Qwen3-Next cell's widths (1 row of
  8,192 positions, 16 key heads under 32 value heads of 128) under a
  checkpoint, its gradient compiled for the chip: under `full` the
  walk's forward program three times (the block's forward pass, its
  recomputation, the row's own recomputation, which writes the states)
  beside the backward program, under `save_attention` twice (ISSUE 41;
  the jaxpr's side of it: tests/test_sequence_layers.py). What
  `save_attention` keeps is one array of 2 B x positions x value width:
  the program's temporaries grow by no more than it, so there is no
  second copy. The process that compiles sees a CPU: the test takes
  the walk's choice of path for it."""
  from tensor2robot_tpu.layers import transformer
  monkeypatch.setattr(gated_delta, "_on_tpu", lambda: True)
  full, saved = (_compile_block_gradient(
      one_chip, transformer.TransformerBlock(
          norm="rms", mixer=gated_delta.GatedDeltaNet(
              num_k_heads=16, num_v_heads=32, head_k_dim=128,
              head_v_dim=128),
          ffn=transformer.GatedMLP(width=512)), rows=1, policy=policy)
                 for policy in ("full", "save_attention"))
  assert full.as_text().count("tpu_custom_call") == 3 + 1
  assert saved.as_text().count("tpu_custom_call") == 2 + 1
  kept = 2 * 8192 * 32 * 128
  assert saved.memory_analysis().temp_size_in_bytes <= (
      full.memory_analysis().temp_size_in_bytes + kept)
