"""Plain float32 reference of the QT-Opt grasping critic.

Written from the layer equations (QT-Opt, arXiv:1806.10293, and the
docstring of the program's `networks.py`), not by calling the program:
conv torso over the image, the action embedded by two dense layers and
broadcast-added onto the torso's feature map, conv head, spatial mean,
dense head to one logit; CEM over actions; the Bellman target
`clip(r + gamma (1 - done) max_a' sigmoid Q_target(s', a'), 0, 1)`;
sigmoid cross-entropy on the logit; Adam; Polyak target update.

Everything is `jax.numpy` in float32 at `Precision.HIGHEST`: no
kernels, no int8 tower, no linearity split of the head's first conv,
no population-major layout. The only thing taken over from the program
is the order of its random draws (which key makes the CEM noise of
which step), because a maximum over other noise is another number.

`Quant` is the control of the outputs check, not part of the
reference: it computes the same equations with weights and
activations rounded to a few bits, the precision below the one the
configuration states.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BN_EPS = 1e-5
BN_MOMENTUM = 0.9
_CONV_DIMS = ("NHWC", "HWIO", "NHWC")

Params = Dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class NetConfig:
  """The sizes of one configuration, as its file under configs/ has
  them (`model`, `cem`, `learner`)."""

  image_size: int
  space_to_depth: int
  torso_filters: Tuple[int, ...]
  head_filters: Tuple[int, ...]
  dense_sizes: Tuple[int, ...]
  action_dim: int
  action_embedding_size: int
  cem_iterations: int
  cem_population: int
  cem_elites: int
  gamma: float = 0.9
  tau: float = 0.05
  learning_rate: float = 1e-4
  adam_b1: float = 0.9
  adam_b2: float = 0.999
  adam_eps: float = 1e-8
  action_low: float = -1.0
  action_high: float = 1.0
  cem_min_std: float = 1e-2

  @classmethod
  def from_config(cls, config: dict) -> "NetConfig":
    model, cem, learner = (config["model"], config["cem"],
                           config["learner"])
    return cls(
        image_size=model["image_size"],
        space_to_depth=model["space_to_depth"],
        torso_filters=tuple(model["torso_filters"]),
        head_filters=tuple(model["head_filters"]),
        dense_sizes=tuple(model["dense_sizes"]),
        action_dim=model["action_dim"],
        action_embedding_size=model["action_embedding_size"],
        cem_iterations=cem["iterations"],
        cem_population=cem["population"],
        cem_elites=cem["elites"],
        gamma=learner["gamma"],
        tau=learner["target_update_tau"],
        learning_rate=learner["learning_rate"])


@dataclasses.dataclass(frozen=True)
class Quant:
  """Bits for the critic's update and for the CEM tower; None is the
  reference itself (float32, nothing rounded)."""

  critic_bits: Optional[int] = None
  tower_bits: Optional[int] = None


REFERENCE = Quant()


def _fake_quant(x, bits: Optional[int], axis=None):
  """Symmetric rounding to `bits` with a straight-through gradient;
  the scale is the largest magnitude (per `axis` slice, else whole)."""
  if bits is None:
    return x
  levels = float(2 ** (bits - 1) - 1)
  scale = jnp.maximum(
      jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
      / levels, 1e-12)
  rounded = jnp.clip(jnp.round(x / scale), -levels, levels) * scale
  return x + jax.lax.stop_gradient(rounded - x)


def _conv(x, kernel, stride: int, bits):
  x = _fake_quant(x, bits)
  kernel = _fake_quant(kernel, bits, axis=(0, 1, 2))
  return jax.lax.conv_general_dilated(
      x, kernel, (stride, stride), "SAME",
      dimension_numbers=_CONV_DIMS, precision=HIGHEST)


def _dense(x, params: Params, name: str, bits):
  x = _fake_quant(x, bits)
  kernel = _fake_quant(params[f"{name}/kernel"], bits, axis=(0,))
  return jnp.dot(x, kernel, precision=HIGHEST) + params[f"{name}/bias"]


def _batch_norm(x, params: Params, stats: Params, name: str,
                train: bool, new_stats: Params):
  if train:
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    for key, batch in (("mean", mean), ("var", var)):
      new_stats[f"{name}/{key}"] = (
          BN_MOMENTUM * stats[f"{name}/{key}"]
          + (1.0 - BN_MOMENTUM) * batch)
  else:
    mean, var = stats[f"{name}/mean"], stats[f"{name}/var"]
  y = (x - mean) * jax.lax.rsqrt(var + BN_EPS)
  return y * params[f"{name}/scale"] + params[f"{name}/bias"]


def encode(cfg: NetConfig, params: Params, stats: Params, image,
           train: bool, new_stats: Params, bits=None):
  """image uint8 [B,H,W,3] -> torso features [B,h,w,C]."""
  x = image.astype(jnp.float32) / 255.0
  s = cfg.space_to_depth
  if s > 1:
    b, h, w, c = x.shape
    x = x.reshape(b, h // s, s, w // s, s, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(
        b, h // s, w // s, s * s * c)
  for i in range(len(cfg.torso_filters)):
    stride = 1 if i == 0 and s > 1 else 2

    def block(x, kernel, bn_params, bn_stats, i=i, stride=stride):
      fresh: Params = {}
      y = _conv(x, kernel, stride, bits)
      y = _batch_norm(y, bn_params, bn_stats, f"torso_bn_{i}", train,
                      fresh)
      return jax.nn.relu(y), fresh

    # Recomputed in the backward pass (same arithmetic): at the paper's
    # image size the float32 activations of a whole batch would not fit
    # beside each other otherwise.
    x, fresh = jax.checkpoint(block)(
        x, params[f"torso_conv_{i}/kernel"], params, stats)
    new_stats.update(fresh)
  return x


def head(cfg: NetConfig, params: Params, stats: Params, encoded,
         action, train: bool, new_stats: Params, bits=None):
  """(torso features [N,h,w,C], action [N,A]) -> logit [N]."""
  a = jax.nn.relu(_dense(action, params, "action_embed_0", bits))
  a = _dense(a, params, "action_embed_1", bits)
  x = encoded + a[:, None, None, :]
  for i in range(len(cfg.head_filters)):
    x = _conv(x, params[f"head_conv_{i}/kernel"], 2, bits)
    x = _batch_norm(x, params, stats, f"head_bn_{i}", train, new_stats)
    x = jax.nn.relu(x)
  x = jnp.mean(x, axis=(1, 2))
  n_dense = len(cfg.dense_sizes) + 1
  for i in range(n_dense):
    x = _dense(x, params, f"q_head/dense_{i}", bits)
    if i < n_dense - 1:
      x = jax.nn.relu(x)
  return x[..., 0]


def population_logits(cfg: NetConfig, params: Params, stats: Params,
                      encoded, actions, bits=None):
  """Eval-mode logits of a population: encoded [B,h,w,C], actions
  [B,P,A] -> [B,P]; the feature map is simply repeated per sample."""
  b, p, a = actions.shape
  tiled = jnp.repeat(encoded, p, axis=0)
  logits = head(cfg, params, stats, tiled, actions.reshape(b * p, a),
                False, {}, bits)
  return logits.reshape(b, p)


def cem_maximize(cfg: NetConfig, score_fn, noise):
  """Cross-entropy method over actions.

  score_fn: [B,P,A] -> [B,P]; noise: [iterations,B,P,A] standard
  normal draws. Returns (best_action [B,A], best_score [B]).
  """
  b = noise.shape[1]
  mean = jnp.full((b, cfg.action_dim),
                  (cfg.action_low + cfg.action_high) / 2.0)
  std = jnp.full((b, cfg.action_dim),
                 (cfg.action_high - cfg.action_low) / 2.0)
  best_action = jnp.zeros((b, cfg.action_dim))
  best_score = jnp.full((b,), -jnp.inf)
  for it in range(cfg.cem_iterations):
    samples = jnp.clip(mean[:, None] + std[:, None] * noise[it],
                       cfg.action_low, cfg.action_high)
    scores = score_fn(samples)
    top_scores, top_idx = jax.lax.top_k(scores, cfg.cem_elites)
    elites = jnp.take_along_axis(samples, top_idx[..., None], axis=1)
    mean = jnp.mean(elites, axis=1)
    std = jnp.maximum(jnp.std(elites, axis=1), cfg.cem_min_std)
    improved = top_scores[:, 0] > best_score
    best_action = jnp.where(improved[:, None], elites[:, 0],
                            best_action)
    best_score = jnp.maximum(best_score, top_scores[:, 0])
  return best_action, best_score


def cem_noise(cfg: NetConfig, rng, batch: int):
  """The draws of one CEM run, keyed as the program keys them: the
  run's key split once per iteration, one normal draw [B,P,A] each."""
  keys = jax.random.split(rng, cfg.cem_iterations)
  return jnp.stack([
      jax.random.normal(k, (batch, cfg.cem_population, cfg.action_dim))
      for k in keys])


def cem_values(cfg: NetConfig, params: Params, stats: Params, image,
               noise, quant: Quant = REFERENCE, rows: int = 64,
               sigmoid: bool = True):
  """max_a score(image, a) by CEM, `rows` images at a time so that the
  repeated feature maps fit; returns (best_action, best_score)."""
  bits = quant.tower_bits
  b = image.shape[0]
  rows = min(rows, b)
  if b % rows:
    raise ValueError(f"batch {b} is not a multiple of {rows} rows")

  def block(args):
    img, nz = args
    encoded = encode(cfg, params, stats, img, False, {}, bits)

    def score(actions):
      logits = population_logits(cfg, params, stats, encoded, actions,
                                 bits)
      return jax.nn.sigmoid(logits) if sigmoid else logits

    return cem_maximize(cfg, score, nz)

  img_blocks = image.reshape((b // rows, rows) + image.shape[1:])
  nz_blocks = noise.reshape(
      (noise.shape[0], b // rows, rows) + noise.shape[2:]
  ).transpose(1, 0, 2, 3, 4)
  actions, scores = jax.lax.map(block, (img_blocks, nz_blocks))
  return actions.reshape(b, -1), scores.reshape(b)


def init_state(params: Params, stats: Params, step: int = 0,
               nu0: float = 0.0) -> dict:
  """Learner state as the start checkpoint holds it: the target
  network a copy of the critic, Adam `step` updates old with first
  moment zero and second moment `nu0` everywhere."""
  return {"params": dict(params), "stats": dict(stats),
          "target": dict(params),
          "mu": {k: jnp.zeros_like(v) for k, v in params.items()},
          "nu": {k: jnp.full_like(v, nu0) for k, v in params.items()},
          "count": jnp.asarray(step, jnp.int32)}


def bellman_step(cfg: NetConfig, state: dict, batch: dict, rng,
                 quant: Quant = REFERENCE, rows: int = 64):
  """One QT-Opt update; returns (new_state, metrics).

  batch: image, next_image uint8 [B,H,W,3]; action [B,A]; reward,
  done [B,1]. `rng` is the step's key: split in two, the first half
  makes the CEM noise (the second is the network's, which has no
  dropout and draws nothing).
  """
  rng_cem, _ = jax.random.split(rng)
  b = batch["image"].shape[0]
  noise = cem_noise(cfg, rng_cem, b)
  # The target tower: target weights under the critic's running
  # statistics, in evaluation mode.
  _, q_next = cem_values(cfg, state["target"], state["stats"],
                         batch["next_image"], noise, quant, rows)
  reward = batch["reward"].reshape(-1).astype(jnp.float32)
  done = batch["done"].reshape(-1).astype(jnp.float32)
  target = jnp.clip(reward + cfg.gamma * (1.0 - done) * q_next,
                    0.0, 1.0)
  target = jax.lax.stop_gradient(target)

  def loss_fn(params):
    new_stats: Params = {}
    bits = quant.critic_bits
    encoded = encode(cfg, params, state["stats"], batch["image"],
                     True, new_stats, bits)
    logit = head(cfg, params, state["stats"], encoded,
                 batch["action"], True, new_stats, bits)
    loss = jnp.mean(jnp.maximum(logit, 0) - logit * target
                    + jnp.log1p(jnp.exp(-jnp.abs(logit))))
    return loss, new_stats

  (loss, new_stats), grads = jax.value_and_grad(
      loss_fn, has_aux=True)(state["params"])
  count = state["count"] + 1
  t = count.astype(jnp.float32)
  mu = {k: cfg.adam_b1 * state["mu"][k] + (1 - cfg.adam_b1) * g
        for k, g in grads.items()}
  nu = {k: cfg.adam_b2 * state["nu"][k]
        + (1 - cfg.adam_b2) * jnp.square(g) for k, g in grads.items()}
  params = {}
  for k, p in state["params"].items():
    mu_hat = mu[k] / (1 - cfg.adam_b1 ** t)
    nu_hat = nu[k] / (1 - cfg.adam_b2 ** t)
    params[k] = p - cfg.learning_rate * mu_hat / (
        jnp.sqrt(nu_hat) + cfg.adam_eps)
  target_params = {k: old + cfg.tau * (params[k] - old)
                   for k, old in state["target"].items()}
  grad_norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                           for g in grads.values()))
  new_state = {"params": params, "stats": {**state["stats"],
                                           **new_stats},
               "target": target_params, "mu": mu, "nu": nu,
               "count": count}
  metrics = {"loss": loss, "grad_norm": grad_norm,
             "q_next_mean": jnp.mean(q_next),
             "target_mean": jnp.mean(target)}
  return new_state, metrics
