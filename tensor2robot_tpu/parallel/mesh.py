"""Device mesh construction and standard shardings.

This is the TPU-native replacement for the reference's TPUEstimator
replication (SURVEY.md §3 parallelism inventory): a named
`jax.sharding.Mesh` over which train steps are jitted. Axis conventions,
used across the framework:

  * ``data``  — batch (data-parallel); gradients all-reduce over it.
  * ``fsdp``  — optional parameter/optimizer sharding axis (zero-style);
                combined with ``data`` for the batch dimension.
  * ``model`` — tensor-parallel axis for wide layers.
  * ``seq``   — sequence/context-parallel axis (ring attention).
  * ``expert`` — expert-parallel axis (MoE layers; tokens all-to-all
                 to the devices holding their routed experts).
  * ``stage`` — pipeline-parallel axis (layer stages; activations
                ppermute stage-to-stage over microbatches).

The reference never goes beyond data parallel; the extra axes exist so
the same step functions scale to pod slices without restructuring.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tensor2robot_tpu import config as gin

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"
STAGE_AXIS = "stage"


@gin.configurable
def create_mesh(
    axis_shapes: Optional[Dict[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
  """Builds a named mesh.

  Args:
    axis_shapes: ordered {axis_name: size}; one axis may be -1 (absorbs
      remaining devices). Default: all devices on the `data` axis.
    devices: defaults to jax.devices().
  """
  devices = list(devices if devices is not None else jax.devices())
  if axis_shapes is None:
    axis_shapes = {DATA_AXIS: len(devices)}
  names = tuple(axis_shapes.keys())
  sizes = list(axis_shapes.values())
  n_devices = len(devices)
  if sizes.count(-1) > 1:
    raise ValueError("At most one mesh axis may be -1.")
  if -1 in sizes:
    known = int(np.prod([s for s in sizes if s != -1]))
    if n_devices % known != 0:
      raise ValueError(
          f"Cannot infer -1 axis: {n_devices} devices not divisible by "
          f"{known}.")
    sizes[sizes.index(-1)] = n_devices // known
  if int(np.prod(sizes)) != n_devices:
    raise ValueError(
        f"Mesh {dict(zip(names, sizes))} needs {int(np.prod(sizes))} "
        f"devices, have {n_devices}.")
  device_array = np.asarray(devices).reshape(sizes)
  return Mesh(device_array, names)


def shard_map_compat(body, mesh: Mesh, *, in_specs, out_specs):
  """`jax.shard_map` with the replication check off: pmean'd scalars
  the framework returns from per-device bodies are legitimately
  replicated, but the checker can't always prove it."""
  return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)


def replicated(mesh: Mesh) -> NamedSharding:
  return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
  """Shards dim 0 over every data-like axis present in the mesh."""
  axes = tuple(a for a in (DATA_AXIS, FSDP_AXIS) if a in mesh.axis_names)
  return NamedSharding(mesh, P(axes if axes else None))


def local_batch_size(mesh: Mesh, global_batch_size: int) -> int:
  axes = [a for a in (DATA_AXIS, FSDP_AXIS) if a in mesh.axis_names]
  shards = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
  if global_batch_size % shards != 0:
    raise ValueError(
        f"Global batch {global_batch_size} not divisible by {shards} "
        f"data shards.")
  return global_batch_size // shards
