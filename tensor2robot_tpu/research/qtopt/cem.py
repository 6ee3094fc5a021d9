"""Cross-entropy-method action optimization, fully on device.

Reference parity: QT-Opt's CEM action selection — the reference ran the
CEM loop host-side, calling the predictor N×M times per action choice
(SURVEY.md §4.4 note [U-med]). TPU-native redesign: the whole optimizer
is one XLA program — `lax.scan` over refinement iterations, the
population batched into the Q-network's batch dimension — so target
computation in the Bellman update AND on-robot action selection both run
without a single host round-trip.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


class CEMResult(NamedTuple):
  best_action: jax.Array   # [B, A]
  best_score: jax.Array    # [B]
  mean: jax.Array          # [B, A] final distribution mean
  std: jax.Array           # [B, A]


def cem_maximize(
    score_fn: Optional[Callable[[jax.Array], jax.Array]],
    rng: jax.Array,
    batch_size: int,
    action_dim: int,
    iterations: int = 3,
    population: int = 64,
    num_elites: int = 6,
    low: float = -1.0,
    high: float = 1.0,
    init_mean: Optional[jax.Array] = None,
    init_std: Optional[jax.Array] = None,
    min_std: float = 1e-2,
    select_fn: Optional[Callable] = None,
) -> CEMResult:
  """Maximizes `score_fn` over actions per batch element.

  Args:
    score_fn: [B, P, A] actions → [B, P] scores. The caller folds the
      population into the network batch dim (reshape), so every Q eval
      rides the MXU at batch B*P. May be None when `select_fn` is
      given.
    rng: PRNG key.
    batch_size, action_dim: static sizes.
    iterations/population/num_elites: CEM hyperparameters (QT-Opt used
      3 rounds, 64 samples, 10% elites).
    low/high: action box bounds (scalar or [A] broadcastable).
    init_mean/init_std: optional [B, A] warm start.
    select_fn: optional fused replacement of the score→top-k→elite-
      stats tail: ([B, P, A] samples, min_std) → (elite_mean [B, A],
      elite_std [B, A] floored at the passed min_std, best_action
      [B, A], best_score [B]), with lax.top_k tie semantics. The
      min_std argument is this function's own `min_std` — forwarded so
      the two paths can never floor differently. The learner wires
      `ops.fused_cem_select` through this seam so scoring, the running
      arg-top-k, and the elite reduction run as ONE kernel without
      materializing the [B, P] score tensor; any callable honoring the
      contract works (tests pin equivalence against the default path).
  """
  if score_fn is None and select_fn is None:
    raise ValueError("one of score_fn / select_fn is required")
  low = jnp.asarray(low, jnp.float32)
  high = jnp.asarray(high, jnp.float32)
  mean = (jnp.zeros((batch_size, action_dim)) + (low + high) / 2.0
          if init_mean is None else init_mean)
  std = (jnp.ones((batch_size, action_dim)) * (high - low) / 2.0
         if init_std is None else init_std)

  def one_iteration(carry, it_rng):
    mean, std, best_action, best_score = carry
    noise = jax.random.normal(
        it_rng, (batch_size, population, action_dim))
    samples = mean[:, None, :] + std[:, None, :] * noise
    samples = jnp.clip(samples, low, high)

    if select_fn is not None:
      new_mean, new_std, it_best, it_best_score = select_fn(samples,
                                                            min_std)
    else:
      scores = score_fn(samples)  # [B, P]
      with jax.named_scope("cem_pool"):  # the selection, in a trace
        elite_scores, elite_idx = jax.lax.top_k(scores, num_elites)
        elites = jnp.take_along_axis(
            samples, elite_idx[..., None], axis=1)  # [B, E, A]
        new_mean = jnp.mean(elites, axis=1)
        new_std = jnp.maximum(jnp.std(elites, axis=1), min_std)
        it_best = elites[:, 0]              # top-1 this iteration
        it_best_score = elite_scores[:, 0]
    improved = it_best_score > best_score
    best_action = jnp.where(improved[:, None], it_best, best_action)
    best_score = jnp.maximum(best_score, it_best_score)
    return (new_mean, new_std, best_action, best_score), ()

  init = (mean, std,
          jnp.zeros((batch_size, action_dim)),
          jnp.full((batch_size,), -jnp.inf))
  # unroll=True: 2-3 iterations, so full unrolling costs nothing in
  # compile time, removes loop overhead, and keeps XLA cost analysis
  # honest (it counts a rolled while-body ONCE regardless of trip
  # count, which silently under-reports FLOPs/MFU in benchmarks).
  (mean, std, best_action, best_score), _ = jax.lax.scan(
      one_iteration, init, jax.random.split(rng, iterations),
      unroll=True)
  return CEMResult(best_action, best_score, mean, std)


def make_q_score_fn(
    apply_fn: Callable,
    variables,
    state_features,
    q_key: str = "q_value",
) -> Callable[[jax.Array], jax.Array]:
  """Builds score_fn: tiles state features over the CEM population.

  `apply_fn(variables, features, train=False)` is the Q-network; state
  features are broadcast to [B*P, ...] and actions folded into the
  batch dim, so one network call scores the whole population.
  """

  def score_fn(actions: jax.Array) -> jax.Array:
    b, p, a = actions.shape
    flat_actions = actions.reshape(b * p, a)

    def tile(x):
      reps = (1, p) + (1,) * (x.ndim - 1)
      return jnp.tile(x[:, None], reps).reshape((b * p,) + x.shape[1:])

    tiled = jax.tree_util.tree_map(tile, state_features)
    flat = dict(tiled.to_flat_dict() if hasattr(tiled, "to_flat_dict")
                else tiled)
    flat["action"] = flat_actions
    from tensor2robot_tpu.specs import TensorSpecStruct
    features = TensorSpecStruct.from_flat_dict(flat)
    outputs = apply_fn(variables, features, train=False)
    q = outputs[q_key] if isinstance(outputs, dict) else outputs
    return q.reshape(b, p)

  return score_fn


def make_encoded_q_score_fn(
    network,
    variables,
    state_features,
    q_key: str = "q_value",
) -> Callable[[jax.Array], jax.Array]:
  """Score fn exploiting an encode/head-split Q-network.

  The action-independent torso (`network.encode`) runs ONCE per state;
  only its (small) output feature map is tiled over the CEM population
  and fed to `network.head` per candidate. The naive path re-convolves
  the full image population × iterations times per action choice — at
  QT-Opt scale (population 64) that is ~64× redundant torso compute.
  """
  flat_state = dict(state_features.to_flat_dict()
                    if hasattr(state_features, "to_flat_dict")
                    else state_features)
  image = flat_state.pop("image")
  encoded = network.apply(variables, image, train=False,
                          method="encode")

  if hasattr(network, "score_population"):
    # Linearity-split population scoring: no tiled torso-map
    # materialization at all (see GraspingQNetwork.score_population).
    # A stale "action" in the state features would become an extra
    # input; the tiled path overrides it with the candidates, so drop
    # it here for the same semantics.
    extras = {k: v for k, v in flat_state.items() if k != "action"}

    def population_score_fn(actions: jax.Array) -> jax.Array:
      return network.apply(variables, encoded, extras, actions,
                           method="score_population")

    return population_score_fn

  def score_fn(actions: jax.Array) -> jax.Array:
    b, p, a = actions.shape
    flat_actions = actions.reshape(b * p, a)

    def tile(x):
      reps = (1, p) + (1,) * (x.ndim - 1)
      return jnp.tile(x[:, None], reps).reshape((b * p,) + x.shape[1:])

    flat = {k: tile(v) for k, v in flat_state.items()}
    flat["action"] = flat_actions
    from tensor2robot_tpu.specs import TensorSpecStruct
    features = TensorSpecStruct.from_flat_dict(flat)
    outputs = network.apply(variables, tile(encoded), features,
                            train=False, method="head")
    q = outputs[q_key] if isinstance(outputs, dict) else outputs
    return q.reshape(b, p)

  return score_fn
