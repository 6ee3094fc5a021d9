"""JAX-native pose/grasp-point bandit: the on-device `PoseGraspBandit`.

Mirrors the host adapter's semantics exactly (research/pose_env/
grasp_bandit.py): an episode is a block at a planar pose in the
workspace box, the observation is a rendered RGB image, the action is
a normalized grasp point in [-1, 1]² mapped linearly onto the box
(`action[:2] * WORKSPACE_HIGH`), and the reward is 1 when the grasp
lands within ``success_threshold`` WORLD units of the pose. The
geometry — workspace box, world→pixel mapping, block extent, colors —
is shared with the numpy `PoseEnv` renderer, so at ``noise=0`` the
rendered frames are BITWISE equal on matched poses (pinned by
tests/test_envs.py) and the reward function is the same float math as
`PoseGraspBandit.grade` (the host-vs-device parity pin).

What the host env cannot do: this one is a pure function over PRNG
keys, so `vmap` runs thousands of episodes as one array program and
`lax.scan` rolls them fully on device (envs/rollout.py) — no MuJoCo
process, no RPC, no data plane.

``max_episode_steps > 1`` turns the bandit into a short refinement
episode (the agent may re-grasp until success or the step limit), the
shape auto-reset and multi-step rollouts are exercised against.
"""

from __future__ import annotations

from typing import Dict, Tuple

import flax
import jax
import jax.numpy as jnp

from tensor2robot_tpu import config as gin
from tensor2robot_tpu.envs.core import FunctionalEnv
from tensor2robot_tpu.research.pose_env.pose_env import (
    IMAGE_SIZE,
    WORKSPACE_HIGH,
    WORKSPACE_LOW,
)

# Shared scene palette (the numpy PoseEnv renderer's constants).
BACKGROUND = 96
BLOCK_COLOR = (200, 40, 40)

# Keep the reference np.float32 arrays as-is: a module-level
# `jnp.asarray` is an import-time jax computation that initializes the
# XLA backend, which breaks any later `jax.distributed.initialize` in
# the importing process (the learner-group hazard; see
# preprocessors/image_transformations.py). jnp ops consume them
# identically.
_LOW = WORKSPACE_LOW
_HIGH = WORKSPACE_HIGH


@flax.struct.dataclass
class PoseState:
  """One episode: the settled block pose + the render-noise stream."""

  pose: jax.Array       # [2] world-unit block pose
  noise_key: jax.Array  # per-episode sensor-noise key
  t: jax.Array          # int32 step counter


def world_to_pixel(xy: jax.Array, image_size: int) -> jax.Array:
  """The numpy `PoseEnv._world_to_pixel` mapping, traced: world units
  → integer pixel centers (truncation + clip, identical rounding)."""
  frac = (xy - _LOW) / (_HIGH - _LOW)
  return jnp.clip((frac * image_size).astype(jnp.int32), 0,
                  image_size - 1)


def render_block_scene(pose: jax.Array, noise_key: jax.Array,
                       image_size: int, extent_px: int,
                       noise: float) -> jax.Array:
  """Renders the PoseEnv scene: noisy gray table, red block at `pose`.

  Matches the numpy renderer's compositing order — noise is applied to
  the background only, block pixels are exact BLOCK_COLOR — so at
  ``noise=0`` the frames are bitwise equal to the host env's.
  """
  center = world_to_pixel(pose, image_size)
  cx, cy = center[0], center[1]
  base = jnp.full((image_size, image_size, 3), float(BACKGROUND))
  sensor = 255.0 * noise * jax.random.normal(
      noise_key, (image_size, image_size, 3))
  table = jnp.clip(base + sensor, 0, 255).astype(jnp.uint8)
  # The host writes image[cy-e : cy+e+1, cx-e : cx+e+1] (rows = y,
  # cols = x, inclusive extent): the same box as a mask.
  rows = jnp.arange(image_size)
  in_y = (rows >= cy - extent_px) & (rows <= cy + extent_px)
  in_x = (rows >= cx - extent_px) & (rows <= cx + extent_px)
  mask = (in_y[:, None] & in_x[None, :])[..., None]
  color = jnp.asarray(BLOCK_COLOR, jnp.uint8)
  return jnp.where(mask, color, table)


@gin.configurable
class PoseBanditEnv(FunctionalEnv):
  """Functional pose/grasp bandit over the PoseEnv workspace box."""

  def __init__(self,
               image_size: int = IMAGE_SIZE,
               action_dim: int = 2,
               success_threshold: float = 0.1,
               block_half_extent: float = 0.06,
               noise: float = 0.02,
               max_episode_steps: int = 1):
    """Defaults mirror `PoseGraspBandit` / `PoseEnv`: threshold 0.1
    world units on the ±0.4 box (~5% random baseline), 0.06 block
    half-extent, 2% sensor noise. `action_dim` >= 2; extra dims ride
    along unused, exactly like the host adapter."""
    if action_dim < 2:
      raise ValueError(
          f"action_dim must be >= 2 (grasp point), got {action_dim}")
    if max_episode_steps < 1:
      raise ValueError(
          f"max_episode_steps must be >= 1, got {max_episode_steps}")
    self._size = int(image_size)
    self._action_dim = int(action_dim)
    self._threshold = float(success_threshold)
    self._half = float(block_half_extent)
    self._noise = float(noise)
    self._max_steps = int(max_episode_steps)
    # Static pixel extent — the numpy renderer's exact formula.
    self._extent_px = max(1, int(
        self._half / float(WORKSPACE_HIGH[0] - WORKSPACE_LOW[0])
        * self._size))

  @property
  def action_dim(self) -> int:
    return self._action_dim

  @property
  def image_size(self) -> int:
    return self._size

  def observation_shapes(self) -> Dict[str, tuple]:
    return {"image": (self._size, self._size, 3)}

  def reset(self, key: jax.Array) -> PoseState:
    key_pose, key_noise = jax.random.split(key)
    pose = jax.random.uniform(
        key_pose, (2,), minval=_LOW, maxval=_HIGH).astype(jnp.float32)
    return PoseState(pose=pose, noise_key=key_noise,
                     t=jnp.zeros((), jnp.int32))

  def state_at(self, pose, key: jax.Array) -> PoseState:
    """An episode at a GIVEN pose — the matched-geometry seam the
    host-vs-device parity pin drives (same block, both renderers)."""
    return PoseState(pose=jnp.asarray(pose, jnp.float32),
                     noise_key=key, t=jnp.zeros((), jnp.int32))

  def observe(self, state: PoseState) -> Dict[str, jax.Array]:
    return {"image": render_block_scene(
        state.pose, state.noise_key, self._size, self._extent_px,
        self._noise)}

  def grasp_reward(self, action: jax.Array,
                   pose: jax.Array) -> jax.Array:
    """`PoseGraspBandit.grade` for one episode: normalized grasp point
    → workspace box → proximity success."""
    grasp = action[:2].astype(jnp.float32) * _HIGH
    dist = jnp.linalg.norm(grasp - pose.astype(jnp.float32))
    return (dist < self._threshold).astype(jnp.float32)

  def step(self, state: PoseState, action: jax.Array, key: jax.Array
           ) -> Tuple[PoseState, Dict[str, jax.Array], jax.Array,
                      jax.Array]:
    del key  # the block has settled; transitions are deterministic
    reward = self.grasp_reward(action, state.pose)
    t_next = state.t + 1
    done = (reward > 0.5) | (t_next >= self._max_steps)
    next_state = state.replace(t=t_next)
    return next_state, self.observe(next_state), reward, done


def host_parity_env(bandit) -> PoseBanditEnv:
  """A `PoseBanditEnv` geometry-matched to a host `PoseGraspBandit`
  (same image size, action width, threshold): the construction the
  parity tests use (tests/test_envs.py::TestHostDeviceParity)."""
  return PoseBanditEnv(
      image_size=bandit.env.image_size,
      action_dim=bandit.action_dim,
      success_threshold=bandit.success_threshold)
