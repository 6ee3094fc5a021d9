"""The benchmark's own weights, made from `--seed` on the device in one
jitted call and handed to the program (as the checkpoint it resumes
from, or as the state a server is built on) and to the reference alike.

Names are the flat paths of the critic's parameters
(`torso_conv_0/kernel`, `q_head/dense_1/bias`, ...): the format in
which the benchmark hands weights over, checked against the program's
own tree when they are placed.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Shapes = Dict[str, Tuple[int, ...]]

# Adam's second moment in the start checkpoint, every element: the
# square of a gradient element of 1e-2, some ten times what this
# critic's gradients measure, so that the resumed run's updates follow
# the gradients' sizes and not only their signs.
ADAM_NU0 = 1e-4


def param_shapes(model: dict) -> Shapes:
  """Shapes of every trainable leaf, from a configuration's `model`."""
  s2d = model["space_to_depth"]
  shapes: Shapes = {}
  cin = 3 * s2d * s2d
  for i, cout in enumerate(model["torso_filters"]):
    shapes[f"torso_conv_{i}/kernel"] = (3, 3, cin, cout)
    shapes[f"torso_bn_{i}/scale"] = (cout,)
    shapes[f"torso_bn_{i}/bias"] = (cout,)
    cin = cout
  merge = cin
  emb = model["action_embedding_size"]
  shapes["action_embed_0/kernel"] = (model["action_dim"], emb)
  shapes["action_embed_0/bias"] = (emb,)
  shapes["action_embed_1/kernel"] = (emb, merge)
  shapes["action_embed_1/bias"] = (merge,)
  for i, cout in enumerate(model["head_filters"]):
    shapes[f"head_conv_{i}/kernel"] = (3, 3, cin, cout)
    shapes[f"head_bn_{i}/scale"] = (cout,)
    shapes[f"head_bn_{i}/bias"] = (cout,)
    cin = cout
  sizes = list(model["dense_sizes"]) + [1]
  for i, size in enumerate(sizes):
    shapes[f"q_head/dense_{i}/kernel"] = (cin, size)
    shapes[f"q_head/dense_{i}/bias"] = (size,)
    cin = size
  return shapes


def stat_shapes(model: dict) -> Shapes:
  """Shapes of the batch-norm running statistics."""
  shapes: Shapes = {}
  for prefix, filters in (("torso", model["torso_filters"]),
                          ("head", model["head_filters"])):
    for i, cout in enumerate(filters):
      shapes[f"{prefix}_bn_{i}/mean"] = (cout,)
      shapes[f"{prefix}_bn_{i}/var"] = (cout,)
  return shapes


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, items):
  out = {}
  for index, (name, shape) in enumerate(items):
    k = jax.random.fold_in(key, index)
    noise = jax.random.normal(k, shape, jnp.float32)
    if name.endswith("/kernel"):
      fan_in = int(np.prod(shape[:-1]))
      out[name] = noise * np.sqrt(2.0 / fan_in)
    elif name.endswith("/scale"):
      out[name] = 1.0 + 0.1 * noise
    else:  # conv-free biases: batch-norm shift and dense bias
      out[name] = 0.1 * noise
  return out


def make_weights(seed: int, model: dict):
  """(params, stats): float32 arrays on the default device. Kernels are
  He-normal, so activations keep their scale through the ReLU stack;
  batch-norm scales and all biases are off their trivial values so
  that every term of the equations is exercised; the running
  statistics start at (0, 1) as a new run's do."""
  key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
  params = _make(key, tuple(sorted(param_shapes(model).items())))
  stats = {name: (jnp.zeros(shape, jnp.float32) if name.endswith("mean")
                  else jnp.ones(shape, jnp.float32))
           for name, shape in stat_shapes(model).items()}
  return params, stats


def _path_name(path) -> str:
  return "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                  for p in path)


def flatten(tree) -> Dict[str, object]:
  """A program tree of parameters (nested dicts) as {flat path: leaf}."""
  return {_path_name(path): leaf for path, leaf in
          jax.tree_util.tree_flatten_with_path(tree)[0]}


def place(tree, values: Dict[str, object]):
  """`tree` (the program's nested parameter tree) with every leaf taken
  from `values` by its flat path; a leaf the benchmark does not make,
  or makes in another shape, is an error."""
  flat = flatten(tree)
  missing = sorted(set(flat) ^ set(values))
  if missing:
    raise ValueError(f"weights and program tree differ at {missing}")

  def pick(path, leaf):
    name = _path_name(path)
    value = values[name]
    if tuple(value.shape) != tuple(leaf.shape):
      raise ValueError(
          f"{name}: benchmark makes {value.shape}, program has "
          f"{leaf.shape}")
    return jnp.asarray(value, leaf.dtype)

  return jax.tree_util.tree_map_with_path(pick, tree)
