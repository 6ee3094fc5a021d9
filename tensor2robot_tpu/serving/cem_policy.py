"""CEM action-selection service: the QT-Opt policy behind the batcher.

The reference's robots each called `predict()` per control tick and ran
the CEM refinement host-side; `QTOptLearner.build_policy` already moved
the whole CEM loop on-device as one XLA program. This module is the
deployment wrapper around that program: bucketed AOT compilation (a
robot fleet's request sizes all hit pre-compiled code), a pinned
device-resident params tree that checkpoint refreshes hot-swap, and a
micro-batcher so N concurrent robots cost ~one CEM program launch
instead of N.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import numpy as np

from tensor2robot_tpu import config as gin
from tensor2robot_tpu.serving.engine import BucketedServingEngine
from tensor2robot_tpu.serving.microbatcher import MicroBatcher
from tensor2robot_tpu.specs import TensorSpecStruct, make_random_tensors


@gin.configurable
class CEMPolicyServer:
  """Serves batched CEM action selection for a QTOptLearner."""

  def __init__(self,
               learner,
               state: Any,
               max_batch: int = 8,
               max_wait_us: int = 200,
               cem_population: Optional[int] = None,
               cem_iterations: Optional[int] = None,
               seed: int = 0,
               warmup: bool = True):
    """Args:
      learner: a `QTOptLearner` (provides the jittable CEM policy).
      state: acting params — a critic `TrainState` (opt_state=None, the
        checkpoint-hook handoff form) or a full `QTOptState`.
      max_batch: largest coalesced dispatch; buckets cover 1..max_batch.
      max_wait_us: micro-batch deadline (0 = never hold a request).
      cem_population / cem_iterations: serving-side CEM overrides
        (robots often run a cheaper CEM than the Bellman backup).
      seed: base PRNG for CEM sampling; folded per dispatch.
      warmup: AOT-compile every bucket now (recommended — first-tick
        compiles inside a control loop are exactly what this exists to
        prevent). `warmup_seconds` records the cost.
    """
    self._learner = learner
    # The serving CEM rides the learner's gin-selected perf levers
    # (int8 tower / fused select — docs/PERF.md); an int8 learner that
    # was never calibrated on real data gets spec-random calibration
    # here, BEFORE the engine AOT-compiles the policy.
    ensure = getattr(learner, "ensure_calibrated", None)
    if ensure is not None:
      ensure(state)
    policy = learner.build_policy(cem_population=cem_population,
                                  cem_iterations=cem_iterations)
    example = make_random_tensors(
        learner.observation_specification(), batch_size=1, seed=0)
    self._engine = BucketedServingEngine(
        policy, state, example, max_batch=max_batch, takes_rng=True)
    self.warmup_seconds = self._engine.warmup() if warmup else 0.0
    self._batcher = MicroBatcher(self._engine,
                                 max_wait_us=max_wait_us,
                                 rng=jax.random.PRNGKey(seed))

  @property
  def engine(self) -> BucketedServingEngine:
    return self._engine

  @property
  def batcher(self) -> MicroBatcher:
    return self._batcher

  @property
  def params_version(self) -> int:
    """Monotonic params-publication counter (engine hot-swap count):
    the policy-version stamp actors log per episode."""
    return self._engine.params_version

  @property
  def params_learner_step(self) -> int:
    """Learner step stamped on the currently-served params — the
    `param_refresh_lag` reference point (docs/FLEET.md)."""
    return self._engine.params_learner_step

  def update_state(self, state: Any,
                   learner_step: Optional[int] = None) -> None:
    """Hot-swaps the acting params (checkpoint-refresh entry point).

    `learner_step` stamps the refresh with the publisher's training
    progress; fleets thread it through so every served action can be
    attributed to the learner step its params came from.
    """
    self._engine.swap_state(state, learner_step=learner_step)

  def select_actions(self,
                     observations: Dict[str, np.ndarray]) -> np.ndarray:
    """Blocking batched action selection — one call per control tick.

    `observations`: flat numpy dict conforming to the learner's
    observation spec, with a leading batch dim (a single robot passes
    batch 1). Thread-safe: concurrent callers coalesce into shared
    dispatches.
    """
    struct = (observations
              if isinstance(observations, TensorSpecStruct)
              else TensorSpecStruct.from_flat_dict(dict(observations)))
    return np.asarray(self._batcher.predict(struct))

  def select_actions_direct(self, observations, rng) -> np.ndarray:
    """Engine-direct selection (no batcher): the device program
    without queueing, for a caller that times one request."""
    struct = (observations
              if isinstance(observations, TensorSpecStruct)
              else TensorSpecStruct.from_flat_dict(dict(observations)))
    return np.asarray(self._engine.predict(struct, rng=rng))

  def close(self) -> None:
    self._batcher.close()

  def __enter__(self):
    return self

  def __exit__(self, *exc):
    self.close()
    return False
