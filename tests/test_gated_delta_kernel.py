"""The gated delta rule's walk over chunks as a Pallas kernel pair
(ISSUE 35; `tensor2robot_tpu/ops/delta_rule_walk.py`) against the
`lax.scan` it replaces on a TPU (`layers/gated_delta.scan_walk`), in the
Pallas interpreter at tiny sizes: outputs and the five cotangents; the
rule with the kernels forced against the recurrence over positions of
`benchmark/reference/qwen3_next.py`; which path a call takes and what
the two counters and their reader say; and both kernels compiled for a
described v5e at the language model's widths."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from test_sequence_layers import (  # noqa: E402
    _kernel_calls,
    _recurrence,
    _rule_inputs,
)
from tensor2robot_tpu.layers import gated_delta  # noqa: E402
from tensor2robot_tpu.ops import delta_rule_fused, delta_rule_walk  # noqa: E402
from tensor2robot_tpu.telemetry import metrics as tmetrics  # noqa: E402

KERNEL = "gated_delta.walk.kernel_traces"
SCAN = "gated_delta.walk.scan_traces"
FUSED = "gated_delta.forward.fused_traces"
PREPARED = "gated_delta.forward.prepared_traces"


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
  # Nothing differentiated: the fused program, and no walk was picked.
  assert tmetrics.registry().scalars("gated_delta.") == {FUSED: 1.0}

  def through(rule):
    return jax.jit(jax.grad(lambda *a: jnp.sum(rule(*a) * probe),
                            argnums=(0, 1, 2, 3, 4)))(*args)

  for name, a, b in zip("q k v g beta".split(), through(kernels),
                        through(_recurrence)):
    assert np.all(np.isfinite(a)), name
    np.testing.assert_allclose(a, b, atol=3e-5, rtol=1e-3, err_msg=name)
  assert tmetrics.registry().scalars("gated_delta.") == {
      FUSED: 2.0, KERNEL: 1.0}


# t, chunk, g_scale, dtype, heads a grid step (of 3; None: the
# kernel's own choice), positions with g = 0 and beta = 0. 150 is no
# multiple of the chunk (the rule pads, as it does for today's path);
# g of -5 to -40 a position underflows float32 inside a chunk.
@pytest.mark.parametrize("t,chunk,g_scale,dtype,block,still", [
    (128, 64, 1.0, jnp.float32, None, None),
    (128, 64, 1.0, jnp.bfloat16, None, None),
    (64, 16, 1.0, jnp.float32, 1, None),
    (64, 16, 1.0, jnp.bfloat16, 2, None),    # 3 heads in blocks of 2
    (64, 16, 1.0, jnp.float32, 2, None),
    (64, 16, 1.0, jnp.bfloat16, 16, None),   # more than there are
    (16, 16, 1.0, jnp.float32, None, None),  # a single chunk
    (16, 16, 1.0, jnp.bfloat16, 2, None),
    (150, 64, 1.0, jnp.float32, None, None),
    (150, 64, 1.0, jnp.bfloat16, None, None),
    (40, 16, 10.0, jnp.float32, None, None),
    (48, 16, 10.0, jnp.bfloat16, 2, None),
    (33, 32, 0.01, jnp.float32, None, None),
    (64, 16, 1.0, jnp.float32, None, slice(5, 40)),  # a chunk and more
    (64, 16, 1.0, jnp.bfloat16, 2, slice(0, 64, 3)),
])
def test_fused_forward_equals_the_prepared_rule(t, chunk, g_scale, dtype,
                                                block, still):
  q, k, v, g, beta = _rule_inputs(t, t, g_scale=g_scale)
  if still is not None:
    g, beta = g.at[:, still].set(0.0), beta.at[:, still].set(0.0)
  inputs = (q, k, v, g, beta)

  def fused(*x):
    if block is None:  # through the rule, which pads
      return gated_delta.gated_delta_rule(*x, chunk=chunk, dtype=dtype,
                                          interpret=True)
    return delta_rule_fused.forward(*x, chunk=chunk, dtype=dtype,
                                    block=block, interpret=True)

  got = jax.jit(fused)(*inputs)
  # The rule where no kernel runs (a CPU): the preparation in XLA,
  # `scan_walk`, `within @ new`.
  want = jax.jit(functools.partial(
      gated_delta.gated_delta_rule, chunk=chunk, dtype=dtype))(*inputs)
  assert got.dtype == want.dtype == jnp.float32
  assert got.shape == want.shape and np.all(np.isfinite(got))
  # The same products on the same operands, their terms added in
  # another order; the inverse's products are three bfloat16 passes
  # here and float32 products on a CPU.
  tol = 1e-5 if dtype == jnp.float32 else 2e-3
  np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
  if block is None:
    assert tmetrics.registry().scalars("gated_delta.") == {
        FUSED: 1.0, PREPARED: 1.0, SCAN: 1.0}


def test_three_pass_product_is_precision_high():
  """`_pair_dot_high`, [y_a | y_b] @ blockdiag(p_a, p_b), against the
  two float64 products: 2^-16 of the operands' size a term, where one
  bfloat16 pass gives 2^-8."""
  y, p = (np.asarray(jax.random.normal(jax.random.PRNGKey(i), (64, 128)))
          for i in range(2))
  want = np.concatenate([
      y[:, half].astype(np.float64) @ p[:, half].astype(np.float64)
      for half in (slice(0, 64), slice(64, 128))], axis=1)
  split = delta_rule_fused._split
  got = delta_rule_fused._pair_dot_high(split(jnp.asarray(y)),
                                        split(jnp.asarray(p)))
  one_pass = np.concatenate([
      jnp.dot(jnp.asarray(y[:, half], jnp.bfloat16),
              jnp.asarray(p[:, half], jnp.bfloat16),
              preferred_element_type=jnp.float32)
      for half in (slice(0, 64), slice(64, 128))], axis=1)
  assert np.abs(got - want).max() < 64 * 2.0 ** -15
  assert np.abs(one_pass - want).max() > 20 * np.abs(got - want).max()


def _probed_rule(rule, probe):
  return jax.grad(lambda *x: jnp.sum(rule(*x) * probe),
                  argnums=(0, 1, 2, 3, 4))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("checkpointed", [False, True])
def test_gradient_with_the_fused_primal_is_the_prepared_rules(
    dtype, checkpointed):
  """The differentiated path is the same program as before the fused
  primal: bit for bit, also under a `jax.checkpoint`, whose forward
  pass is the fused program."""
  inputs = _rule_inputs(3, 40)
  probe = jax.random.normal(jax.random.PRNGKey(9), inputs[2].shape)

  def rule(*x):
    return gated_delta.gated_delta_rule(*x, chunk=16, dtype=dtype,
                                        interpret=True)

  def prepared(*x):  # the rule before ISSUE 42, its kernels forced
    x = (jnp.pad(y, ((0, 0), (0, 8)) + ((0, 0),) * (y.ndim - 2))
         for y in x)
    return gated_delta._prepared_rule(*x, 16, dtype, True)[:, :40]

  got = jax.jit(_probed_rule(
      jax.checkpoint(rule) if checkpointed else rule, probe))(*inputs)
  want = jax.jit(_probed_rule(prepared, probe))(*inputs)
  for name, a, b in zip("q k v g beta".split(), got, want):
    assert a.dtype == b.dtype and a.shape == b.shape, name
    np.testing.assert_array_equal(a, b, err_msg=name)


def _products(jaxpr):
  """The `dot_general`s of a jaxpr and of every jaxpr inside it, a
  `pallas_call`'s kernel apart."""
  return sum(
      (eqn.primitive.name == "dot_general")
      + sum(map(_products, jax.core.jaxprs_in_params(eqn.params)))
      for eqn in jaxpr.eqns if eqn.primitive.name != "pallas_call")


def test_checkpointed_rule_runs_fused_forward_and_prepared_backward():
  """Under `jax.checkpoint` and `jax.grad` (a row of `GatedDeltaNet`):
  the forward pass is the fused program and no product of the
  preparation; the backward pass recomputes the prepared rule with
  the state-saving walk and runs the backward walk."""
  inputs = _rule_inputs(3, 32)

  @jax.checkpoint
  def rule(*x):
    return gated_delta.gated_delta_rule(*x, chunk=16, interpret=True)

  jaxpr = jax.make_jaxpr(jax.grad(
      lambda *x: jnp.sum(rule(*x)), argnums=(0, 1, 2, 3, 4)))(
          *inputs).jaxpr
  (backward,) = [e for e in jaxpr.eqns if e.primitive.name == "remat2"]
  backward = backward.params["jaxpr"]
  assert _kernel_calls(backward) == {"_forward_kernel": 1,
                                     "_backward_kernel": 1}
  assert _kernel_calls(jaxpr) - _kernel_calls(backward) == {
      "_fused_kernel": 1}
  assert _products(backward) > 20  # the preparation and its transpose
  assert _products(jaxpr) == _products(backward)  # none forward
  assert tmetrics.registry().scalars("gated_delta.") == {
      FUSED: 1.0, KERNEL: 1.0}


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


@pytest.mark.parametrize("platform_is_tpu,dk,dtype,forward,walk", [
    (False, 8, jnp.float32, PREPARED, SCAN),  # a CPU, whatever the shapes
    (False, 128, jnp.bfloat16, PREPARED, SCAN),
    (True, 8, jnp.float32, PREPARED, SCAN),   # a TPU, shapes do not tile
    (True, 128, jnp.bfloat16, FUSED, KERNEL),
])
def test_path_is_read_off_platform_and_shapes(monkeypatch,
                                              platform_is_tpu, dk, dtype,
                                              forward, walk):
  ran = []

  # No Mosaic on a CPU: the choice is what is under test.
  def walk_stand_in(*operands, interpret):
    ran.append(("walk", interpret))
    return gated_delta.scan_walk(*operands)

  def fused_stand_in(*inputs, chunk, dtype, interpret):
    ran.append(("fused", interpret))
    return gated_delta._prepared_rule(*inputs, chunk, dtype, interpret)

  monkeypatch.setattr(gated_delta, "_on_tpu", lambda: platform_is_tpu)
  monkeypatch.setattr(delta_rule_walk, "walk", walk_stand_in)
  monkeypatch.setattr(delta_rule_fused, "forward", fused_stand_in)
  args = _rule_inputs(1, 32, heads=1, dk=dk, dv=dk)

  def rule(*a):
    return gated_delta.gated_delta_rule(*a, chunk=16, dtype=dtype)

  # Nothing differentiates: the forward pass's program alone.
  out = jax.jit(rule)(*args)
  assert out.shape == (2, 32, 1, dk)
  if forward == FUSED:
    # The stand-in is the prepared rule, which picks a walk itself.
    assert ran == [("fused", False), ("walk", False)]
    assert tmetrics.registry().scalars("gated_delta.") == {
        FUSED: 1.0, KERNEL: 1.0}
  else:
    assert ran == []
    assert tmetrics.registry().scalars("gated_delta.") == {
        forward: 1.0, walk: 1.0}
  # A backward pass follows: the prepared rule, with the walk that the
  # platform and the shapes allow.
  tmetrics.reset_for_tests()
  del ran[:]
  jax.jit(jax.grad(lambda *a: jnp.sum(rule(*a))))(*args)
  assert ("walk", False) in ran if walk == KERNEL else ran == []
  counts = tmetrics.registry().scalars("gated_delta.")
  assert counts[forward] == 1.0 and counts[walk] >= 1.0
  assert set(counts) == {forward, walk}


def test_counters_count_traced_calls_not_executions():
  args = _rule_inputs(2, 32)
  rule = jax.jit(lambda *a: gated_delta.gated_delta_rule(*a, chunk=16))
  for _ in range(3):
    rule(*args)
  assert tmetrics.registry().scalars("gated_delta.") == {
      PREPARED: 1.0, SCAN: 1.0}
  gated_delta.gated_delta_rule(*args, chunk=16, interpret=True)
  assert tmetrics.registry().scalars("gated_delta.") == {
      PREPARED: 1.0, SCAN: 1.0, FUSED: 1.0}
  jax.grad(lambda *a: jnp.sum(gated_delta.gated_delta_rule(
      *a, chunk=16, interpret=True)))(*args)
  assert tmetrics.registry().scalars("gated_delta.") == {
      PREPARED: 1.0, SCAN: 1.0, FUSED: 2.0, KERNEL: 1.0}


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


@pytest.mark.parametrize("dtype,block", [
    (jnp.bfloat16, None), (jnp.float32, None), (jnp.bfloat16, 4)])
def test_fused_forward_compiles_for_a_v5e_at_the_cells_widths(
    one_chip, dtype, block):
  """One row of the cell (8,192 positions, 32 heads of 128 x 128,
  chunk 64) through the fused program: one kernel, and beside q, k, v
  and `out` nothing in HBM but v in float32 where it came in 16 bits
  (rows of a 16-bit array cannot be read at a stride) and the
  per-position scalars, 1 MB each."""
  b, t, h, d = 1, 8192, 32, 128

  def aval(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

  # As the layer has them: q and k normalised in float32, v in `dtype`.
  avals = 2 * (aval((b, t, h, d), jnp.float32),) + (
      aval((b, t, h, d), dtype),) + 2 * (aval((b, t, h), jnp.float32),)
  compiled = _compile_for_the_chip(
      lambda *x: delta_rule_fused.forward(*x, chunk=64, dtype=dtype,
                                          block=block), *avals)
  assert compiled.as_text().count("tpu_custom_call") == 1
  temporaries = compiled.memory_analysis().temp_size_in_bytes
  assert temporaries < (dtype != jnp.float32) * b * t * h * d * 4 + 2 ** 24


def test_flash_kernels_compile_for_a_v5e_at_the_latent_widths(one_chip):
  """The flash kernel's two programs (the forward, and since ISSUE 45
  the one fused backward) at keys of 192 over values of 128 (latent
  attention; ISSUE 36): 2 rows of 8,192 positions and 32 heads in
  bfloat16, the default blocks. In this file because one process
  describes the chip (its fixture)."""
  from tensor2robot_tpu.ops import flash_attention

  def aval(width):
    return jax.ShapeDtypeStruct((2, 8192, 32, width), jnp.bfloat16,
                                sharding=one_chip)

  def loss(q, k, v):
    return jnp.sum(flash_attention(q, k, v, causal=True)
                   .astype(jnp.float32))

  compiled = _compile_for_the_chip(
      jax.grad(loss, argnums=(0, 1, 2)), aval(192), aval(192), aval(128))
  assert compiled.as_text().count("tpu_custom_call") == 2
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
  `save_attention`, twice under `full`, beside the one backward
  program (ISSUE 37, 45; the jaxpr's side of it:
  tests/test_sequence_layers.py)."""
  from tensor2robot_tpu.layers import transformer

  compiled = _compile_block_gradient(one_chip, transformer.TransformerBlock(
      norm="rms", mixer=transformer.LatentAttention(
          num_heads=32, q_lora_rank=1536, kv_lora_rank=512,
          qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
          attention_impl="flash"),
      ffn=transformer.GatedMLP(width=7168)), rows=2, policy=policy)
  assert compiled.as_text().count("tpu_custom_call") == forward_calls + 1


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
