"""Compiles a configuration's K-step train program for a described
v5e chip, here in the sandbox without one, and prints the compiler's
memory analysis: how a configuration's batch and steps per dispatch
were sized against the driver's floor on device memory, and a check
that the real size compiles before chip time is spent on it.

  JAX_PLATFORMS=cpu python benchmark/tools/aot_memory.py qtopt_472 [batch,k ...]

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


def main(name: str, sizes) -> None:
  import jax
  import jax.numpy as jnp
  import numpy as np
  from jax.experimental import topologies
  from jax.sharding import SingleDeviceSharding

  from benchmark.harness import program
  from tensor2robot_tpu.data import prefetch as prefetch_lib
  from tensor2robot_tpu.specs import make_random_tensors

  with open(os.path.join(ROOT, "benchmark", "configs",
                         f"{name}.json")) as f:
    config = json.load(f)
  topo = topologies.get_topology_desc(platform="tpu",
                                      topology_name="v5e:2x2")
  chip = SingleDeviceSharding(topo.devices[0])
  learner = program.build_learner(config)
  train = config["train"]
  sizes = sizes or [(train["batch_size_per_chip"],
                     train["steps_per_dispatch"])]
  state = jax.eval_shape(
      lambda: learner.create_state(jax.random.PRNGKey(0), batch_size=2))
  row = make_random_tensors(learner.transition_specification(),
                            batch_size=1, seed=0)

  def k_steps(st, stacked, rng, step0):
    return prefetch_lib.scan_k_steps(learner.train_step, st,
                                     (stacked,), rng, step0)

  for batch, k in sizes:
    avals = (
        jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=chip), state),
        jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                (k, batch) + np.asarray(x).shape[1:],
                np.asarray(x).dtype, sharding=chip), row),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=chip))
    t = time.time()
    compiled = jax.jit(k_steps, donate_argnums=(0,)).lower(
        *avals).compile()
    mem = compiled.memory_analysis()
    print(f"{name} batch={batch} K={k}: compiled in "
          f"{time.time() - t:.1f} s; temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB", flush=True)


if __name__ == "__main__":
  main(sys.argv[1],
       [tuple(int(v) for v in a.split(",")) for a in sys.argv[2:]])
