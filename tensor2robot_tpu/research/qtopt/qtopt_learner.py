"""QT-Opt learner: Bellman targets via on-device CEM + critic updates.

The reference open-sourced only the grasping model and the export/
predict handoff — its distributed system (replay buffer service,
Bellman updater fleet, CEM policy server; SURVEY.md §3 parallelism
inventory "Async actor/learner distribution") stayed in Google infra.
This module IS that system, collapsed into a single XLA program per
step, which is what the hardware wants:

  one jitted `train_step(learner_state, transitions)`:
    1. CEM-maximize Q_target(s', ·) for the whole batch (population
       folded into the batch dim — every eval saturates the MXU),
    2. target = r + γ (1-done) max_a' Q_target(s', a'), clipped to
       [0, 1] for the sigmoid grasp-success head (paper's form),
    3. cross-entropy critic update on Q(s, a),
    4. Polyak (or periodic) target-network update.

Data parallel over the mesh: batch sharded on the data axis, params
replicated, GSPMD all-reduces gradients over ICI — the same step scales
from 1 chip to a v5e-64 pod unchanged.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import flax
import jax
import jax.numpy as jnp

from tensor2robot_tpu import config as gin
from tensor2robot_tpu.data.abstract_input_generator import Mode
from tensor2robot_tpu.models.abstract_model import TrainState
from tensor2robot_tpu.models.critic_model import Q_VALUE
from tensor2robot_tpu.research.qtopt import cem
from tensor2robot_tpu.research.qtopt.t2r_models import GraspingQModel
from tensor2robot_tpu.specs import TensorSpecStruct


def _polyak(tau, new, old):
  """Polyak average in the contraction-stable form `old+tau·(new-old)`.

  `optax.incremental_update`'s `tau·new + (1-tau)·old` leaves an
  inexact multiply feeding an add, and XLA backends contract that
  pair into an FMA (or don't) per compiled module — jit- and
  pmap-compiled modules of the SAME jaxpr measurably disagree by
  1 ulp on XLA:CPU, and HLO `optimization_barrier`s don't survive to
  LLVM to stop it. This form has a single multiply on the difference;
  when ``tau`` is a power of two (2^-k) that product is EXACT, so the
  FMA and non-FMA contractions round identically and the update is
  bit-stable across compilation modes regardless of backend ISA. (The
  pod-vs-single-program bitwise pin in tests/test_envs.py removes the
  remaining backward-pass contraction ambiguity by pinning under an
  FMA-less `--xla_cpu_max_isa`; this form keeps the default-ISA drift
  to 1 ulp per step.) For non-pow2 tau the value matches the textbook
  average to 1 ulp.
  """
  return old + tau * (new - old)


@flax.struct.dataclass
class QTOptState:
  """Learner state: critic TrainState + target network params."""

  train_state: TrainState
  target_params: Any

  @property
  def step(self):
    return self.train_state.step


@gin.configurable
class QTOptLearner:
  """Builds the jittable QT-Opt training step for a GraspingQModel."""

  def __init__(self,
               model: GraspingQModel,
               gamma: float = 0.9,
               cem_iterations: int = 2,
               cem_population: int = 64,
               cem_elites: int = 6,
               action_low: float = -1.0,
               action_high: float = 1.0,
               target_update_tau: float = 0.05,
               clip_targets: Optional[Tuple[float, float]] = (0.0, 1.0),
               cem_inference: str = "bf16",
               cem_select: str = "lax"):
    """See class docstring; the two perf levers (docs/PERF.md):

    cem_inference: "bf16" (the network's compute dtype, exact) or
      "int8" — the CEM Q-tower forward runs the quantized tower
      (`networks.quantize_tower`): int8 weights/activations, bf16
      accumulation, activation scales from `calibrate()` (a held-out
      batch) — halves the HBM traffic of the profiled-hottest merged
      population tensor. Bellman targets/acting only; the critic
      gradient path is untouched.
    cem_select: "lax" (top_k + gather, exact reference) or "fused" —
      scoring + running arg-top-k + elite stats run as one Pallas
      kernel (`ops.fused_cem_select`) through `cem_maximize`'s
      select_fn seam; compiled on TPU, interpreted on every other
      backend (decided when the step is traced).
    """
    if cem_inference not in ("bf16", "int8"):
      raise ValueError(f"cem_inference={cem_inference!r} not in "
                       "('bf16', 'int8')")
    if cem_select not in ("lax", "fused"):
      raise ValueError(f"cem_select={cem_select!r} not in "
                       "('lax', 'fused')")
    self._model = model
    self._gamma = gamma
    self._cem_iterations = cem_iterations
    self._cem_population = cem_population
    self._cem_elites = cem_elites
    self._action_low = action_low
    self._action_high = action_high
    self._tau = target_update_tau
    self._clip_targets = clip_targets if model.sigmoid_q else None
    self._cem_inference = cem_inference
    self._cem_select = cem_select
    self._act_scales: Optional[Dict[str, float]] = None

  @property
  def model(self) -> GraspingQModel:
    return self._model

  @property
  def cem_population(self) -> int:
    return self._cem_population

  @property
  def cem_iterations(self) -> int:
    return self._cem_iterations

  def create_state(self, rng: jax.Array,
                   batch_size: int = 2) -> QTOptState:
    train_state = self._model.create_train_state(rng, batch_size)
    # Materialize a distinct copy: aliasing the online params would make
    # donated train_step inputs share buffers (donation error).
    target = jax.tree_util.tree_map(jnp.copy, train_state.params)
    return QTOptState(train_state=train_state, target_params=target)

  # ---- int8 calibration ----

  @property
  def cem_inference(self) -> str:
    return self._cem_inference

  @property
  def needs_calibration(self) -> bool:
    """True when the int8 tower is selected but no activation scales
    exist yet — `calibrate()` (or `ensure_calibrated()`) must run
    before the step/policy is traced."""
    return self._cem_inference == "int8" and self._act_scales is None

  def calibrate(self, state, features) -> Dict[str, float]:
    """Computes the int8 activation scales from a held-out batch.

    Host-level (runs a jitted eval forward); the resulting per-tensor
    scales are plain floats that bake into subsequently traced
    steps/policies as constants. `state` is a QTOptState or TrainState
    (online params — at calibration time target ≈ online); `features`
    is a batch conforming to the model's TRAIN feature spec (the
    transition batch's s-side keys work).
    """
    from tensor2robot_tpu.research.qtopt import networks as net_lib
    ts = state.train_state if isinstance(state, QTOptState) else state
    variables = {"params": ts.params}
    if ts.batch_stats:
      variables["batch_stats"] = ts.batch_stats
    flat = (features.to_flat_dict()
            if hasattr(features, "to_flat_dict") else dict(features))
    flat = {k: v for k, v in flat.items()
            if not k.startswith("next_") and k not in ("reward",
                                                       "done")}
    stats = jax.jit(functools.partial(
        self._model.network.apply, method="calibration_stats"))(
            variables, flat)
    self._act_scales = net_lib.scales_from_stats(
        jax.device_get(stats))
    return self._act_scales

  def set_activation_scales(self, scales: Dict[str, float]) -> None:
    """Adopts scales an earlier `calibrate()` produced (a resumed run
    keeps the constants its first start traced with)."""
    self._act_scales = {k: float(v) for k, v in scales.items()}

  def ensure_calibrated(self, state) -> None:
    """Calibrates from a spec-random batch when nothing better ran —
    serving contexts that never see a replay batch. Random uint8
    images land in the same post-BN activation range class as real
    frames; prefer `calibrate()` on real data when available."""
    if not self.needs_calibration:
      return
    from tensor2robot_tpu.specs import make_random_tensors
    from tensor2robot_tpu.data.abstract_input_generator import Mode
    batch = make_random_tensors(
        self._model.get_feature_specification(Mode.TRAIN),
        batch_size=16, seed=0)
    batch = jax.tree_util.tree_map(jnp.asarray, batch)
    self.calibrate(state, batch)

  # ---- CEM scoring/selection construction ----

  def _cem_fns(self, variables, state_features):
    """(score_fn, select_fn) for `cem_maximize` — exactly one is used.

    The four gin-selectable paths: bf16/int8 tower × lax/fused select.
    All encode-split paths run the torso ONCE per state; int8 swaps
    the tower forward for the quantized twin; "fused" routes the
    scoring tail through `ops.fused_cem_select` via the select seam.
    """
    network = self._model.network
    if not (hasattr(network, "encode") and hasattr(network, "head")):
      return cem.make_q_score_fn(
          functools.partial(network.apply), variables, state_features,
          q_key=Q_VALUE), None
    if self._cem_inference == "bf16" and self._cem_select == "lax":
      return cem.make_encoded_q_score_fn(
          network, variables, state_features, q_key=Q_VALUE), None

    from tensor2robot_tpu.ops import fused_cem_select
    from tensor2robot_tpu.research.qtopt import networks as net_lib
    flat_state = dict(state_features.to_flat_dict()
                      if hasattr(state_features, "to_flat_dict")
                      else state_features)
    image = flat_state.pop("image")
    extras = {k: v for k, v in flat_state.items() if k != "action"}
    if self._cem_inference == "int8":
      if self._act_scales is None:
        raise RuntimeError(
            "cem_inference='int8' needs activation scales: call "
            "learner.calibrate(state, batch) (or ensure_calibrated) "
            "before tracing the step/policy.")
      tower = net_lib.quantize_tower(network, variables,
                                     self._act_scales)
      encoded = net_lib.quantized_encode(network, tower, image)
      score_fn = lambda actions: net_lib.quantized_score_population(  # noqa: E731
          network, tower, variables, encoded, extras, actions)
      pool_fn = lambda actions: net_lib.quantized_pool_population(  # noqa: E731
          network, tower, variables, encoded, extras, actions)
    else:
      encoded = network.apply(variables, image, train=False,
                              method="encode")
      score_fn = lambda actions: network.apply(  # noqa: E731
          variables, encoded, extras, actions,
          method="score_population")
      pool_fn = lambda actions: network.apply(  # noqa: E731
          variables, encoded, extras, actions,
          method="pool_population")
    if self._cem_select != "fused":
      return score_fn, None

    dense = net_lib.q_head_dense_params(variables,
                                        dtype=network.dtype)
    sigmoid = self._model.sigmoid_q

    def select_fn(actions, min_std):
      # Traced, so the backend is already up (a constructor gin calls
      # must not claim the chip). Mosaic compiles on TPU only; every
      # other backend runs the kernel through the interpreter.
      pooled = pool_fn(actions)
      with jax.named_scope("cem_pool"):
        return fused_cem_select(
            pooled, actions, dense,
            num_elites=self._cem_elites, min_std=min_std,
            sigmoid=sigmoid, interpret=jax.default_backend() != "tpu")

    return None, select_fn

  # ---- target computation ----

  def _target_q_values(self, target_params, batch_stats,
                       next_features: TensorSpecStruct,
                       rng: jax.Array) -> jax.Array:
    """max_a' Q_target(s', a') via CEM, one XLA region."""
    variables = {"params": target_params}
    if batch_stats:
      variables["batch_stats"] = batch_stats
    batch = jax.tree_util.tree_leaves(next_features)[0].shape[0]
    score_fn, select_fn = self._cem_fns(variables, next_features)

    sigmoid_score = None
    if score_fn is not None:
      def sigmoid_score(actions):
        q = score_fn(actions)
        return jax.nn.sigmoid(q) if self._model.sigmoid_q else q
    # select_fn case: the sigmoid (monotone — selection unchanged)
    # runs inside the fused kernel, so best_score is already on the
    # sigmoid scale (_cem_fns passes sigmoid=model.sigmoid_q).

    result = cem.cem_maximize(
        sigmoid_score, rng, batch, self._model.action_dim,
        iterations=self._cem_iterations,
        population=self._cem_population,
        num_elites=self._cem_elites,
        low=self._action_low, high=self._action_high,
        select_fn=select_fn)
    return result.best_score

  # ---- the fused train step ----

  def train_step(self, state: QTOptState, transitions: TensorSpecStruct,
                 rng: jax.Array, axis_name: Optional[str] = None
                 ) -> Tuple[QTOptState, Dict[str, jax.Array]]:
    """One Bellman update on a batch of transitions.

    transitions (flat struct): image, action [A], reward [1], done [1],
    next_image (+ any extra state features prefixed next_).

    `axis_name` (trace-time static) is the SPMD pod form: each device
    computes Bellman targets and gradients on its OWN transition
    batch, gradients are `lax.pmean`'d over the axis before the Adam
    update (the model's `train_step` seam), and the Polyak target
    update then runs on identical post-update params everywhere — so
    the replicated learner state stays replicated by construction.
    The q_next/target metrics are pmean'd too (device-0 reports the
    global means).

    Composition of `train_grads` + `apply_gradients` — the split the
    shard_map pod program drives directly (per-device backward under
    shard_map, GSPMD weight update; docs/SHARDING.md).
    """
    grads, new_stats, metrics = self.train_grads(
        state, transitions, rng, axis_name=axis_name)
    return self.apply_gradients(state, grads, new_stats), metrics

  def train_grads(self, state: QTOptState,
                  transitions: TensorSpecStruct, rng: jax.Array,
                  axis_name: Optional[str] = None
                  ) -> Tuple[Any, Any, Dict[str, jax.Array]]:
    """The forward/backward half of `train_step`: CEM Bellman targets
    + critic gradients (pmean'd over `axis_name`), no optimizer
    update. Returns ``(grads, new_batch_stats, metrics)``."""
    flat = transitions.to_flat_dict()
    rng_cem, rng_net = jax.random.split(rng)

    # Every non-next_, non-reward/done key is an online-critic feature:
    # models with state extras beyond {image, action} (gripper status,
    # height, ...) must see them in Q(s, a) just as the target network
    # sees their next_-prefixed twins.
    features = TensorSpecStruct.from_flat_dict({
        k: v for k, v in flat.items()
        if not k.startswith("next_") and k not in ("reward", "done")})
    next_features = TensorSpecStruct.from_flat_dict(
        {k[len("next_"):]: v for k, v in flat.items()
         if k.startswith("next_")})

    ts = state.train_state
    q_next = self._target_q_values(
        state.target_params, ts.batch_stats, next_features, rng_cem)
    # Device-side names (`jax.named_scope`, metadata only) for a
    # profiler trace: the target arithmetic is `bellman_loss`, the
    # critic's forward and backward pass `backward` (the loss sits in
    # the model, inside it), then `optimizer` and `polyak` below.
    with jax.named_scope("bellman_loss"):
      reward = flat["reward"].reshape(-1).astype(jnp.float32)
      done = flat["done"].reshape(-1).astype(jnp.float32)
      target = reward + self._gamma * (1.0 - done) * q_next
      if self._clip_targets is not None:
        target = jnp.clip(target, *self._clip_targets)
      target = jax.lax.stop_gradient(target)

    labels = TensorSpecStruct.from_flat_dict(
        {"target_q": target[:, None]})
    with jax.named_scope("backward"):
      grads, new_stats, metrics = self._model.train_grads(
          ts, features, labels, rng_net, axis_name=axis_name)
    metrics["q_next_mean"] = jnp.mean(q_next)
    metrics["target_mean"] = jnp.mean(target)
    if axis_name is not None:
      metrics["q_next_mean"] = jax.lax.pmean(metrics["q_next_mean"],
                                             axis_name)
      metrics["target_mean"] = jax.lax.pmean(metrics["target_mean"],
                                             axis_name)
    return grads, new_stats, metrics

  def apply_gradients(self, state: QTOptState, grads: Any,
                      new_stats: Any) -> QTOptState:
    """The update half: critic optimizer step + Polyak target sync."""
    with jax.named_scope("optimizer"):
      new_ts = self._model.apply_gradients(state.train_state, grads,
                                           new_stats)
    with jax.named_scope("polyak"):
      new_target = jax.tree_util.tree_map(
          functools.partial(_polyak, self._tau),
          new_ts.params, state.target_params)
    return QTOptState(train_state=new_ts, target_params=new_target)

  # ---- on-robot / actor policy ----

  def build_policy(self, cem_population: Optional[int] = None,
                   cem_iterations: Optional[int] = None):
    """Returns a jittable (state, observation_features, rng) → action.

    The serving-side CEM: the reference's robots looped predict() calls
    host-side; here action selection is one device program.

    `state` may be the full learner `QTOptState` OR just the critic
    `TrainState`: acting reads only the online params (the target net
    exists for Bellman backups, never for action selection), so
    serving contexts that hold a bare TrainState — checkpoint hooks,
    exported policies — pass it directly instead of fabricating a
    learner state with dummy targets.
    """
    population = cem_population or self._cem_population
    iterations = cem_iterations or self._cem_iterations

    def policy(state, observations: TensorSpecStruct,
               rng: jax.Array) -> jax.Array:
      ts = state.train_state if isinstance(state, QTOptState) else state
      variables = {"params": ts.params}
      if ts.batch_stats:
        variables["batch_stats"] = ts.batch_stats
      batch = jax.tree_util.tree_leaves(observations)[0].shape[0]
      score_fn, select_fn = self._cem_fns(variables, observations)
      result = cem.cem_maximize(
          score_fn, rng, batch, self._model.action_dim,
          iterations=iterations, population=population,
          num_elites=self._cem_elites,
          low=self._action_low, high=self._action_high,
          select_fn=select_fn)
      return result.best_action

    return policy

  def observation_specification(self) -> TensorSpecStruct:
    """Serving-side observation spec: every state feature Q(s, ·)
    conditions on — the model's TRAIN feature spec minus the `action`
    CEM optimizes over. This is the wire contract of
    `serving.CEMPolicyServer.select_actions`."""
    feat = self._model.get_feature_specification(Mode.TRAIN).to_flat_dict()
    return TensorSpecStruct.from_flat_dict(
        {k: v for k, v in feat.items() if k != "action"})

  def transition_specification(self) -> TensorSpecStruct:
    """The replay-buffer transition spec, derived from the model specs."""
    import numpy as np
    from tensor2robot_tpu.specs import ExtendedTensorSpec

    model_feat = self._model.get_feature_specification(
        Mode.TRAIN).to_flat_dict()
    out = dict(model_feat)
    for key, spec in model_feat.items():
      if key != "action":
        out[f"next_{key}"] = spec.replace(name=f"next_{spec.name or key}")
    out["reward"] = ExtendedTensorSpec(shape=(1,), dtype=np.float32,
                                       name="reward")
    out["done"] = ExtendedTensorSpec(shape=(1,), dtype=np.float32,
                                     name="done")
    return TensorSpecStruct.from_flat_dict(out)
