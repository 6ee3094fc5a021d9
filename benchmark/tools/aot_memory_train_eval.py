"""`tools/aot_memory.py` for a `train_eval` configuration: compiles the
K-step program of the T2R model that the configuration's gin file binds
to `train_eval_model.model`, for a described v5e chip, here in the
sandbox without one, and prints the compiler's memory analysis: whether
the real size compiles and fits before chip time is spent on it.

  JAX_PLATFORMS=cpu python benchmark/tools/aot_memory_train_eval.py \
      qwen3next_80b_a3b_ep16 [batch,k ...] [<gin binding> ...]

An argument with `=` is one more gin binding: a model that asks the
devices JAX has which attention backend to take (`auto`) finds the
CPU's here, so bind `NextTokenLanguageModel.attention_impl='flash'`.

Nothing runs; a compile that passes is not a chip run.
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(name: str, sizes, bindings) -> None:
  import jax
  import jax.numpy as jnp
  import numpy as np
  from jax.experimental import topologies
  from jax.sharding import SingleDeviceSharding

  from benchmark.harness import program
  from tensor2robot_tpu.data import prefetch as prefetch_lib
  from tensor2robot_tpu.data.abstract_input_generator import Mode

  with open(os.path.join(ROOT, "benchmark", "configs",
                         f"{name}.json")) as f:
    config = json.load(f)
  topo = topologies.get_topology_desc(platform="tpu",
                                      topology_name="v5e:2x2")
  chip = SingleDeviceSharding(topo.devices[0])
  config["gin_bindings"] = config["gin_bindings"] + bindings
  model = program.build_model(config)
  train = config["train"]
  sizes = sizes or [(train["batch_size_per_chip"],
                     train["steps_per_dispatch"])]
  state = jax.eval_shape(
      lambda: model.create_train_state(jax.random.PRNGKey(0),
                                       batch_size=2))

  def k_steps(st, features, labels, rng, step0):
    return prefetch_lib.scan_k_steps(model.train_step, st,
                                     (features, labels), rng, step0)

  def avals(spec, batch, k):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            (k, batch) + tuple(s.shape), np.dtype(s.dtype),
            sharding=chip), spec)

  for batch, k in sizes:
    args = (
        jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=chip), state),
        avals(model.get_feature_specification(Mode.TRAIN), batch, k),
        avals(model.get_label_specification(Mode.TRAIN), batch, k),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=chip))
    t = time.time()
    compiled = jax.jit(k_steps, donate_argnums=(0,)).lower(
        *args).compile()
    mem = compiled.memory_analysis()
    print(f"{name} batch={batch} K={k}: compiled in "
          f"{time.time() - t:.1f} s; temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, aliased "
          f"{mem.alias_size_in_bytes / 1e9:.3f} GB, outputs "
          f"{mem.output_size_in_bytes / 1e9:.3f} GB", flush=True)


if __name__ == "__main__":
  main(sys.argv[1],
       [tuple(int(v) for v in a.split(",")) for a in sys.argv[2:]
        if "=" not in a],
       [a for a in sys.argv[2:] if "=" in a])
