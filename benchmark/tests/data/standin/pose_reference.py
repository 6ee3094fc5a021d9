"""Plain float32 reference and seeded weights of the pose-env regression
model, for the stand-in that proves the `train_eval` kind (a test
fixture, not a configuration of the benchmark). Written from the layer
equations in the docstrings of `research/pose_env/pose_env_models.py`
and `layers/vision_layers.py`, not by calling them: the image over 255;
3x3 convolutions of stride 2 without bias, each under batch
normalisation and a ReLU; spatial soft-argmax with a learnt temperature
(expected x, then expected y, per channel, in [-1, 1]); a dense
projection; a ReLU MLP to the pose; mean squared error. With `control`
every convolution's and dense layer's inputs and kernels are rounded to
fp8 (e4m3), the precision below the configuration's bfloat16. On the
chip neither this nor int8 with a scale per tensor reads further from
the reference than the bfloat16 program does (limits/
standin.train_eval.json, PR 26): the fixture has no precision limit.
"""

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BN_EPS, BN_MOMENTUM = 1e-5, 0.9
# Adam's second moment in the start checkpoint, every element (as
# harness/weights.py: a gradient element of 1e-2).
ADAM_NU0 = 1e-4


def param_shapes(model: dict) -> dict:
  shapes, cin = {}, 3
  for i, cout in enumerate(model["filters"]):
    shapes[f"encoder/tower/conv_{i}/kernel"] = (3, 3, cin, cout)
    shapes[f"encoder/tower/bn_{i}/scale"] = (cout,)
    shapes[f"encoder/tower/bn_{i}/bias"] = (cout,)
    cin = cout
  shapes["encoder/ssoftmax/log_temperature"] = ()
  sizes = [model["embedding_size"]] + list(model["hidden_sizes"]) \
      + [model["pose_dim"]]
  names = ["encoder/proj"] + [f"head/dense_{i}"
                              for i in range(len(sizes) - 1)]
  cin = 2 * cin
  for name, size in zip(names, sizes):
    shapes[f"{name}/kernel"], shapes[f"{name}/bias"] = (cin, size), (size,)
    cin = size
  return shapes


def make_weights(seed: int, config: dict):
  """(params, stats) as flat dicts by path, float32 on the device, in
  one jitted call: He-normal kernels, batch-norm scales 1 + 0.1 n,
  everything else 0.1 n; running statistics (0, 1)."""
  model = config["model"]
  items = tuple(sorted(param_shapes(model).items()))

  @jax.jit
  def make(key):
    out = {}
    for index, (name, shape) in enumerate(items):
      noise = jax.random.normal(jax.random.fold_in(key, index), shape)
      if name.endswith("/kernel"):
        out[name] = noise * np.sqrt(2.0 / np.prod(shape[:-1]))
      else:
        out[name] = name.endswith("/scale") + 0.1 * noise
    return out

  stats = {}
  for i, cout in enumerate(model["filters"]):
    stats[f"encoder/tower/bn_{i}/mean"] = jnp.zeros((cout,))
    stats[f"encoder/tower/bn_{i}/var"] = jnp.ones((cout,))
  return make(jax.random.PRNGKey(seed % (2 ** 31 - 1))), stats


def _round(x, control: bool, axis=None):
  """Rounding to fp8 e4m3 under a scale that puts the largest magnitude
  at the format's 448 (straight-through), for the control."""
  if not control:
    return x
  scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis,
                              keepdims=axis is not None) / 448.0, 1e-12)
  rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
  return x + jax.lax.stop_gradient(rounded * scale - x)


def _dense(x, params, name, control):
  return jnp.dot(_round(x, control),
                 _round(params[f"{name}/kernel"], control, axis=(0,)),
                 precision=HIGHEST) + params[f"{name}/bias"]


def loss(config, params, stats, batch, rng, control=False):
  del rng  # the model draws nothing
  model, new_stats = config["model"], {}
  x = batch["features"]["image"].astype(jnp.float32) / 255.0
  for i in range(len(model["filters"])):
    x = jax.lax.conv_general_dilated(
        _round(x, control),
        _round(params[f"encoder/tower/conv_{i}/kernel"], control,
               axis=(0, 1, 2)),
        (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)
    bn = f"encoder/tower/bn_{i}"
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    for key, value in (("mean", mean), ("var", var)):
      new_stats[f"{bn}/{key}"] = (BN_MOMENTUM * stats[f"{bn}/{key}"]
                                  + (1 - BN_MOMENTUM) * value)
    x = (x - mean) * jax.lax.rsqrt(var + BN_EPS)
    x = jax.nn.relu(x * params[f"{bn}/scale"] + params[f"{bn}/bias"])
  b, h, w, c = x.shape
  probs = jax.nn.softmax(
      x.reshape(b, h * w, c)
      / jnp.exp(params["encoder/ssoftmax/log_temperature"]), axis=1)
  grid_y, grid_x = jnp.meshgrid(jnp.linspace(-1.0, 1.0, h),
                                jnp.linspace(-1.0, 1.0, w), indexing="ij")
  x = jnp.concatenate(
      [jnp.einsum("bpc,p->bc", probs, g.reshape(-1), precision=HIGHEST)
       for g in (grid_x, grid_y)], axis=-1)
  x = _dense(x, params, "encoder/proj", control)
  layers = len(model["hidden_sizes"]) + 1
  for i in range(layers):
    x = _dense(x, params, f"head/dense_{i}", control)
    if i < layers - 1:
      x = jax.nn.relu(x)
  error = x - batch["labels"]["target_pose"]
  mse = jnp.mean(jnp.square(error))
  pose_error = jnp.mean(jnp.linalg.norm(error, axis=-1))
  return mse, {"mse": mse, "pose_error": pose_error}, new_stats
