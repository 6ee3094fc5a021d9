"""Unit tests for the sharding-rule contracts.

`expert_sharding` keys on the dedicated ``moe_expert_`` leaf prefix
OWNED by `MoEMLP` — mount-point independent, so experts shard no
matter what module name the trunk instantiates its MoEMLP under. (The
previous contract required the parent module to be literally named
``moe``, which silently replicated experts under any other mount —
the round-5 advisor finding these tests regression-pin.) Indivisible
expert dims raise instead of silently falling back.
`xplane.is_async_window` (the compute-table filter behind that
module's per-op attribution) gets direct unit coverage too.
"""

import numpy as np
import pytest

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tensor2robot_tpu.parallel import (
    DATA_AXIS,
    EXPERT_AXIS,
    create_mesh,
    expert_sharding,
)
from tensor2robot_tpu.utils import xplane


class TestExpertShardingScope:

  @pytest.fixture()
  def mesh(self):
    return create_mesh({DATA_AXIS: 2, EXPERT_AXIS: 4})

  def test_expert_leaf_under_moe_shards_on_expert(self, mesh):
    tree = {"block1": {"moe": {
        "moe_expert_w_in": jnp.zeros((8, 16, 32))}}}
    sh = expert_sharding(mesh, tree, min_size_to_shard=64)
    assert sh["block1"]["moe"]["moe_expert_w_in"].spec == P(EXPERT_AXIS)

  def test_renamed_mount_still_shards(self, mesh):
    """THE regression for the round-5 finding: a MoEMLP mounted under
    a name other than 'moe' (here 'ffn_sparse') must still shard its
    experts — the old parent-name contract silently replicated them."""
    tree = {"block1": {"ffn_sparse": {
        "moe_expert_w_in": jnp.zeros((8, 16, 32)),
        "router": jnp.zeros((16, 8))}}}
    sh = expert_sharding(mesh, tree, min_size_to_shard=64)
    assert sh["block1"]["ffn_sparse"]["moe_expert_w_in"].spec == P(
        EXPERT_AXIS)
    # The router is not an expert weight wherever it lives.
    router_spec = sh["block1"]["ffn_sparse"]["router"].spec
    assert EXPERT_AXIS not in [ax for ax in router_spec if ax]

  def test_root_level_expert_leaf_shards(self, mesh):
    """A bare MoEMLP param tree has expert leaves at the root."""
    sh = expert_sharding(
        mesh, {"moe_expert_w_in": jnp.zeros((8, 16, 32))},
        min_size_to_shard=64)
    assert sh["moe_expert_w_in"].spec == P(EXPERT_AXIS)

  def test_optimizer_mirror_path_shards_too(self, mesh):
    """Adam moments nest the param path under opt-state prefixes; the
    leaf-name rule must still match."""
    tree = {"mu": {"trunk": {"moe": {
        "moe_expert_w_out": jnp.zeros((8, 32, 16))}}}}
    sh = expert_sharding(mesh, tree, min_size_to_shard=64)
    assert sh["mu"]["trunk"]["moe"]["moe_expert_w_out"].spec == P(
        EXPERT_AXIS)

  def test_expert_prefixed_leaf_outside_contract_uses_fsdp(self, mesh):
    """The advisor's collision case: `expert_`-prefixed params that
    are NOT MoEMLP's stacked weights (the prefix is `moe_expert_`,
    which only MoEMLP may use) follow the fsdp rules — with no fsdp
    axis in this mesh, replicate — instead of landing on the expert
    axis."""
    tree = {"policy": {"expert_demo_encoder": jnp.zeros((8, 64, 64))},
            "moe": {"expert_w_in": jnp.zeros((8, 16, 32))}}
    sh = expert_sharding(mesh, tree, min_size_to_shard=64)
    for leaf in (sh["policy"]["expert_demo_encoder"],
                 sh["moe"]["expert_w_in"]):
      assert EXPERT_AXIS not in [ax for ax in leaf.spec if ax], leaf

  def test_indivisible_expert_dim_raises(self, mesh):
    tree = {"moe": {"moe_expert_w_in": jnp.zeros((6, 16, 32))}}
    with pytest.raises(ValueError, match="not divisible"):
      expert_sharding(mesh, tree, min_size_to_shard=64)

  def test_no_expert_axis_falls_back_to_fsdp(self):
    mesh = create_mesh({DATA_AXIS: 8})
    tree = {"moe": {"moe_expert_w_in": jnp.zeros((6, 16, 32))}}
    # No expert axis: the indivisible dim is irrelevant; fsdp rules
    # (here: replicated) apply without raising.
    sh = expert_sharding(mesh, tree, min_size_to_shard=64)
    assert sh["moe"]["moe_expert_w_in"].spec == P()

  def test_moe_mlp_param_names_carry_the_contract_prefix(self):
    """The rule and the module must agree: every stacked expert param
    MoEMLP creates is `moe_expert_`-prefixed (if this breaks, experts
    replicate silently on pods)."""
    import jax as _jax
    from tensor2robot_tpu.parallel.moe import MoEMLP

    module = MoEMLP(num_experts=4, hidden_dim=8, dtype=jnp.float32)
    params = module.init(
        _jax.random.PRNGKey(0), jnp.zeros((2, 4, 8)))["params"]
    stacked = [name for name, leaf in params.items()
               if np.asarray(leaf).ndim and
               np.asarray(leaf).shape[0] == 4]
    assert stacked, params.keys()
    for name in stacked:
      assert name.startswith("moe_expert_"), name


class TestAsyncWindowFilter:
  """The per-op compute filter: -start/-done spans are wall windows
  overlapping compute (round-4's committed tables were 10/10
  copy-starts), so they must be excluded from busy-time attribution
  — and ONLY they."""

  @pytest.mark.parametrize("name", [
      "%copy-start.113 = (f32[64]...) copy-start(...)",
      "%copy-done.77 = f32[64] copy-done(...)",
      "%all-gather-start.3 = ...",
      "%all-reduce-done.9 = ...",
      "%collective-permute-start.1 = ...",
  ])
  def test_async_windows_match(self, name):
    assert xplane.is_async_window(name)

  @pytest.mark.parametrize("name", [
      "%fusion.481 = bf16[256,16,16,64] fusion(...)",
      "%convert_reduce_fusion.27 = f32[16384,64] fusion(...)",
      "%convolution.12 = ...",
      "%all-reduce.4 = ...",          # sync collective: busy time
      "%custom-call.5 = ...",
      "%multiply_add_fusion.153 = ...",
  ])
  def test_compute_ops_pass(self, name):
    assert not xplane.is_async_window(name)

  def test_top_ops_compute_only_drops_windows(self, tmp_path,
                                              monkeypatch):
    monkeypatch.setattr(
        xplane, "op_times_ms",
        lambda trace_dir, plane_filter="TPU": {
            "%copy-start.1": 75.0,
            "%fusion.2": 50.0,
            "%while": 400.0,
            "%convolution.3": 25.0,
        })
    got = xplane.top_ops("unused", k=10, hlo_only=True,
                         compute_only=True)
    assert got == [("%fusion.2", 50.0), ("%convolution.3", 25.0)]


# ---------------------------------------------------------------------------
# The rules seam (ISSUE 12)
# ---------------------------------------------------------------------------


class TestMatchPartitionRules:
  """The regex-rules engine every strategy now selects tables from."""

  def _mesh(self):
    from tensor2robot_tpu.parallel import FSDP_AXIS, create_mesh
    return create_mesh({DATA_AXIS: 2, FSDP_AXIS: 4})

  def test_first_match_wins_and_placements_resolve(self):
    from tensor2robot_tpu.parallel import (
        FSDP_AXIS,
        Replicate,
        ShardLargest,
        match_partition_rules,
    )
    mesh = self._mesh()
    tree = {"torso": {"kernel": jnp.zeros((8, 16)),
                      "bias": jnp.zeros((16,))}}
    specs = match_partition_rules(
        ((r"/bias$", Replicate()),
         (r".*", ShardLargest(FSDP_AXIS))),
        tree, mesh, min_size_to_shard=1)
    assert specs["torso"]["bias"] == P()
    assert specs["torso"]["kernel"] == P(None, FSDP_AXIS)

  def test_literal_partition_spec_used_verbatim(self):
    from tensor2robot_tpu.parallel import match_partition_rules
    specs = match_partition_rules(
        ((r".*", P(DATA_AXIS)),), {"w": jnp.zeros((4, 4))},
        self._mesh())
    assert specs["w"] == P(DATA_AXIS)

  def test_unmatched_leaf_raises(self):
    from tensor2robot_tpu.parallel import (
        Replicate,
        match_partition_rules,
    )
    with pytest.raises(ValueError, match="no partition rule matched"):
      match_partition_rules(((r"/bias$", Replicate()),),
                            {"w": jnp.zeros((4,))}, self._mesh())

  def test_opt_state_tuple_paths_match_leaf_rules(self):
    """Optax chains nest params under tuple indices (SequenceKey);
    the '/'-joined path keeps the leaf name matchable."""
    from tensor2robot_tpu.parallel import (
        FSDP_AXIS,
        ShardLargest,
        match_partition_rules,
    )
    tree = ({"mu": {"conv/kernel": jnp.zeros((8, 8))}},
            {"count": jnp.zeros(())})
    specs = match_partition_rules(
        ((r".*", ShardLargest(FSDP_AXIS)),), tree, self._mesh(),
        min_size_to_shard=1)
    assert specs[0]["mu"]["conv/kernel"] == P(FSDP_AXIS, None)
    assert specs[1]["count"] == P()  # scalars always replicate

  def test_coverage_checker_reports_unmatched_and_dead(self):
    from tensor2robot_tpu.parallel import (
        Replicate,
        ShardLargest,
        check_rules_coverage,
    )
    rules = ((r"/never_matches$", Replicate()),
             (r"/kernel$", ShardLargest()),
             (r".*", Replicate()))
    unmatched, dead = check_rules_coverage(
        ((r"/kernel$", ShardLargest()),),
        [{"a": {"kernel": jnp.zeros((4,)), "bias": jnp.zeros((4,))}}])
    assert unmatched == ["a/bias"] and dead == []
    unmatched, dead = check_rules_coverage(
        rules, [{"a": {"kernel": jnp.zeros((4,))}}])
    assert unmatched == [] and dead == [r"/never_matches$"]

  def test_every_family_table_covers_its_models(self):
    """The in-repo twin of t2rcheck GIN108: each family's table
    matches every param of its canonical models, no dead regexes."""
    from tensor2robot_tpu.parallel import (
        FAMILY_RULES,
        check_rules_coverage,
        family_param_templates,
        family_rules,
    )
    for family in FAMILY_RULES:
      unmatched, dead = check_rules_coverage(
          family_rules(family), family_param_templates(family))
      assert not unmatched, (family, unmatched)
      assert not dead, (family, dead)

  def test_shard_and_gather_fns_roundtrip(self):
    import jax
    from tensor2robot_tpu.parallel import (
        FSDP_AXIS,
        ShardLargest,
        make_shard_and_gather_fns,
        match_partition_rules,
    )
    mesh = self._mesh()
    tree = {"w": np.arange(32, dtype=np.float32).reshape(8, 4),
            "b": np.zeros((4,), np.float32)}
    specs = match_partition_rules(
        ((r".*", ShardLargest(FSDP_AXIS)),), tree, mesh,
        min_size_to_shard=1)
    shard_fns, gather_fns = make_shard_and_gather_fns(mesh, specs)
    on_device = jax.tree_util.tree_map(lambda f, x: f(x), shard_fns,
                                       tree)
    assert on_device["w"].sharding.spec == P(FSDP_AXIS, None)
    back = jax.tree_util.tree_map(lambda f, x: f(x), gather_fns,
                                  on_device)
    np.testing.assert_array_equal(back["w"], tree["w"])
    np.testing.assert_array_equal(back["b"], tree["b"])


class TestStrategySpecRegression:
  """THE refactor pin: all five mesh strategies produce specs
  identical to their pre-refactor tree-walk implementations on the
  8-device MULTICHIP axis — frozen legacy copies below, diffed
  spec-for-spec over a tree with conv/dense kernels, stacked experts,
  stage stacks, optimizer mirrors, odd shapes, and scalars."""

  @staticmethod
  def _legacy_fsdp(mesh, tree, min_size_to_shard=2 ** 10):
    import jax
    from jax.sharding import NamedSharding
    from tensor2robot_tpu.parallel import FSDP_AXIS
    if FSDP_AXIS not in mesh.axis_names:
      repl = NamedSharding(mesh, P())
      return jax.tree_util.tree_map(lambda _: repl, tree)
    size = mesh.shape[FSDP_AXIS]

    def rule(leaf):
      shape = getattr(leaf, "shape", ())
      if not shape or int(np.prod(shape)) < min_size_to_shard:
        return NamedSharding(mesh, P())
      order = sorted(range(len(shape)), key=lambda i: -shape[i])
      for dim in order:
        if shape[dim] % size == 0:
          spec = [None] * len(shape)
          spec[dim] = FSDP_AXIS
          return NamedSharding(mesh, P(*spec))
      return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(rule, tree)

  @staticmethod
  def _legacy_tp(mesh, tree, min_size_to_shard=2 ** 12):
    import jax
    from jax.sharding import NamedSharding
    from tensor2robot_tpu.parallel import FSDP_AXIS, MODEL_AXIS
    legacy_fsdp = TestStrategySpecRegression._legacy_fsdp
    if MODEL_AXIS not in mesh.axis_names:
      return legacy_fsdp(mesh, tree, min_size_to_shard)
    tp = mesh.shape[MODEL_AXIS]
    fsdp = mesh.shape.get(FSDP_AXIS, 1)
    has_fsdp = FSDP_AXIS in mesh.axis_names

    def rule(leaf):
      shape = getattr(leaf, "shape", ())
      if not shape or int(np.prod(shape)) < min_size_to_shard:
        return NamedSharding(mesh, P())
      if len(shape) >= 2 and shape[-1] % tp == 0:
        spec = [None] * len(shape)
        spec[-1] = MODEL_AXIS
        if has_fsdp and shape[-2] % fsdp == 0:
          spec[-2] = FSDP_AXIS
        return NamedSharding(mesh, P(*spec))
      if shape[-1] % tp == 0:
        return NamedSharding(mesh, P(*([None] * (len(shape) - 1)),
                                     MODEL_AXIS))
      return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(rule, tree)

  @staticmethod
  def _legacy_expert(mesh, tree, min_size_to_shard=2 ** 10):
    import jax
    from jax.sharding import NamedSharding
    legacy_fsdp = TestStrategySpecRegression._legacy_fsdp
    if EXPERT_AXIS not in mesh.axis_names:
      return legacy_fsdp(mesh, tree, min_size_to_shard)
    size = mesh.shape[EXPERT_AXIS]

    def name_of(key):
      return str(getattr(key, "key", getattr(key, "name", "")))

    def rule(path, leaf):
      shape = getattr(leaf, "shape", ())
      is_expert = bool(
          path and name_of(path[-1]).startswith("moe_expert_"))
      if is_expert:
        return NamedSharding(mesh, P(EXPERT_AXIS))
      return legacy_fsdp(mesh, leaf, min_size_to_shard)

    return jax.tree_util.tree_map_with_path(rule, tree)

  @staticmethod
  def _legacy_pipeline(mesh, tree, min_size_to_shard=2 ** 10):
    import jax
    from jax.sharding import NamedSharding
    from tensor2robot_tpu.parallel import STAGE_AXIS
    legacy_fsdp = TestStrategySpecRegression._legacy_fsdp
    if STAGE_AXIS not in mesh.axis_names:
      return legacy_fsdp(mesh, tree, min_size_to_shard)

    def name_of(key):
      return str(getattr(key, "key", getattr(key, "name", "")))

    def rule(path, leaf):
      if any(name_of(key) == "stages" for key in path):
        return NamedSharding(mesh, P(STAGE_AXIS))
      return legacy_fsdp(mesh, leaf, min_size_to_shard)

    return jax.tree_util.tree_map_with_path(rule, tree)

  @staticmethod
  def _legacy_replicated(mesh, tree, min_size_to_shard=0):
    import jax
    from jax.sharding import NamedSharding
    del min_size_to_shard
    repl = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda _: repl, tree)

  def _rich_tree(self, experts=8, stages=4):
    """Conv/dense/bn leaves + stacked experts + stage stacks + an Adam
    mirror + odd/scalar leaves — every code path the strategies take."""
    params = {
        "torso_conv_0": {"kernel": jnp.zeros((3, 3, 3, 64))},
        "torso_bn_0": {"scale": jnp.zeros((64,)),
                       "bias": jnp.zeros((64,))},
        "q_head": {"dense_0": {"kernel": jnp.zeros((128, 64)),
                               "bias": jnp.zeros((64,))}},
        "odd": {"kernel": jnp.zeros((37, 41))},
        "tiny": {"kernel": jnp.zeros((4, 4))},
        "moe": {"moe_expert_w_in": jnp.zeros((experts, 64, 128)),
                "router": jnp.zeros((64, experts))},
        "stages": {"attn": {"kernel": jnp.zeros((stages, 64, 64))}},
        "scalar": jnp.zeros(()),
    }
    return {"params": params,
            "opt_state": {"mu": params, "nu": params}}

  MESHES = (
      {DATA_AXIS: 8},
      {DATA_AXIS: 4, "fsdp": 2},
      {DATA_AXIS: 2, "fsdp": 2, "model": 2},
      {DATA_AXIS: 2, EXPERT_AXIS: 4},
      {DATA_AXIS: 2, "stage": 4},
      {"fsdp": 8},
  )

  @pytest.mark.parametrize("strategy,legacy_name", [
      ("fsdp", "_legacy_fsdp"),
      ("tp", "_legacy_tp"),
      ("ep", "_legacy_expert"),
      ("pipeline", "_legacy_pipeline"),
      ("replicated", "_legacy_replicated"),
  ])
  def test_strategy_specs_identical_to_legacy(self, strategy,
                                              legacy_name):
    import jax
    from tensor2robot_tpu.parallel import state_sharding
    legacy = getattr(self, legacy_name)
    tree = self._rich_tree()
    for axes in self.MESHES:
      mesh = create_mesh(dict(axes))
      got = state_sharding(mesh, tree, strategy=strategy)
      # state_sharding forwards its min_size default to every
      # strategy — mirror that in the legacy call.
      want = legacy(mesh, tree, min_size_to_shard=2 ** 10)
      flat_got = jax.tree_util.tree_leaves_with_path(got)
      flat_want = jax.tree_util.tree_leaves(want)
      assert len(flat_got) == len(flat_want)
      for (path, g), w in zip(flat_got, flat_want):
        assert g == w, (strategy, axes,
                        jax.tree_util.keystr(path), g.spec, w.spec)

  def test_update_sharding_axis_parameter(self):
    """`data_update_sharding(axis=...)` / `train_state_update_sharding
    (axis=...)` ride any named axis — the pod-axis ZeRO composition."""
    import jax
    from jax.sharding import Mesh
    from tensor2robot_tpu.parallel.sharding import (
        data_update_sharding,
        train_state_update_sharding,
    )
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("pod",))
    tree = {"opt_state": {"mu": {"kernel": jnp.zeros((64, 64))}},
            "params": {"kernel": jnp.zeros((64, 64))}}
    upd = data_update_sharding(mesh, tree["opt_state"], axis="pod")
    assert upd["mu"]["kernel"].spec == P("pod", None)
    full = train_state_update_sharding(mesh, tree, axis="pod")
    assert full["opt_state"]["mu"]["kernel"].spec == P("pod", None)
    assert full["params"]["kernel"].spec == P()
