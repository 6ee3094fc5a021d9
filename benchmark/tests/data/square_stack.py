"""A synthetic family for `tools/follow_memory.py` and the tests of
`harness/follow.py`: a residual stack of square float32 layers over a
few hundred rows, `x <- x + tanh(x W_i) / 2`, mean squared error
against the batch's target. It stands for no model and is no
configuration of the benchmark: it is the cheapest thing that gives
the reference side of a `train_eval` cell a train state of a chosen
size (`layers * width**2` parameters, next to no activations), with
the three names a family's modules owe the kind (benchmark/README.md,
"A model family"): `make_weights`, `ADAM_NU0`, `loss`. With `control`
the matrix products take bfloat16 operands, the precision below.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ADAM_NU0 = 1e-4


def config_of(layers: int, width: int, rows: int) -> dict:
  """A configuration of the family, as far as `follow` and the two
  functions below read one."""
  name = "tests.data.square_stack"
  return {"name": f"square_stack_{layers}x{width}",
          "model": {"layers": layers, "width": width, "rows": rows},
          "learner": {"optimizer": "adam", "learning_rate": 1e-4},
          "benchmark": {"weights": name, "reference": name}}


def make_weights(seed: int, config: dict):
  """(params, stats): `layer_<i>/kernel` of (width, width), normal over
  the square root of the width, float32 on the device, one jitted
  call; no running statistics."""
  model = config["model"]

  @jax.jit
  def make(key):
    shape = (model["width"], model["width"])
    return {f"layer_{i:03d}/kernel":
                jax.random.normal(jax.random.fold_in(key, i), shape)
                / jnp.sqrt(float(model["width"]))
            for i in range(model["layers"])}

  return make(jax.random.PRNGKey(seed % (2 ** 31 - 1))), {}


def make_batches(seed: int, config: dict, k: int):
  """K batches as a stream would yield them: host arrays, rows that
  all differ."""
  import numpy as np

  model = config["model"]
  rng = np.random.default_rng(seed)
  shape = (model["rows"], model["width"])
  return [{"features": {"x": rng.standard_normal(shape, np.float32)},
           "labels": {"y": rng.standard_normal(shape, np.float32)}}
          for _ in range(k)]


def loss(config, params, stats, batch, rng, control=False):
  del stats, rng  # no running statistics, nothing drawn
  x = batch["features"]["x"]
  for i in range(config["model"]["layers"]):
    kernel = params[f"layer_{i:03d}/kernel"]
    if control:
      product = jnp.dot(x.astype(jnp.bfloat16),
                        kernel.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    else:
      product = jnp.dot(x, kernel, precision=HIGHEST)
    x = x + 0.5 * jnp.tanh(product)
  mse = jnp.mean(jnp.square(x - batch["labels"]["y"]))
  return mse, {"mse": mse}, {}
