"""The reference's side of a `train_eval` cell's first dispatch: K steps
of a plain Adam, written here, on `jax.grad` of the loss that the
configuration's reference module gives, in float32 at `highest`.

A reference module (named by the configuration's `benchmark.reference`,
under `benchmark/`) is a model family's layer equations and nothing of
the program:

  loss(config, params, stats, batch, rng, control=False)
      -> (loss, aux, new_stats)

`params` and `stats` are flat dicts by path, `batch` is `{"features":
{...}, "labels": {...}}` as the stream yielded it, `rng` the step's key
as the loop folds it, `aux` a dict of scalars that the program's step
reports under the same names (each is compared as `<name>_rel_gap`:
`check.numbers_between`), `new_stats` the running statistics after the
step (empty where the model has none). With `control`, the same
equations one precision below the configuration's `precision` block:
the control of the outputs check.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp


def module_of(config: dict, role: str):
  """The module the configuration's `benchmark` block names for `role`
  (`weights` or `reference`), under `benchmark/`."""
  return importlib.import_module(
      f"benchmark.{config['benchmark'][role]}")


def adam(learner: dict, params, mu, nu, count, grads):
  """One step of Adam (Kingma & Ba, with bias correction; `count` is
  the step's own number, from 1) with the numbers of a configuration's
  `learner` block. Returns (params, mu, nu)."""
  lr = learner["learning_rate"]
  b1 = learner.get("beta1", 0.9)
  b2 = learner.get("beta2", 0.999)
  eps = learner.get("epsilon", 1e-8)
  t = jnp.asarray(count, jnp.float32)
  mu = {k: b1 * mu[k] + (1 - b1) * g for k, g in grads.items()}
  nu = {k: b2 * nu[k] + (1 - b2) * jnp.square(g)
        for k, g in grads.items()}
  params = {
      k: p - lr * (mu[k] / (1 - b1 ** t))
      / (jnp.sqrt(nu[k] / (1 - b2 ** t)) + eps)
      for k, p in params.items()}
  return params, mu, nu


def follow(config: dict, inputs: dict, seed32: int,
           control: bool = False):
  """K steps on the K batches the loop's stream yielded first, from
  the benchmark's weights. Returns (state after K steps as flat dicts
  `params`, `mu`, `stats`; last step's metrics)."""
  reference = module_of(config, "reference")
  nu0 = module_of(config, "weights").ADAM_NU0
  learner = config["learner"]

  @jax.jit
  def step(state, batch, rng):
    def loss_fn(params):
      loss, aux, new_stats = reference.loss(
          config, params, state["stats"], batch, rng, control=control)
      return loss, (aux, new_stats)

    (loss, (aux, new_stats)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(state["params"])
    count = state["count"] + 1
    params, mu, nu = adam(learner, state["params"], state["mu"],
                          state["nu"], count, grads)
    grad_norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in grads.values()))
    return ({"params": params, "mu": mu, "nu": nu, "count": count,
             "stats": {**state["stats"], **new_stats}},
            {"loss": loss, "grad_norm": grad_norm, **aux})

  with jax.default_matmul_precision("highest"):
    k = len(inputs["batches"])
    step0 = inputs["first_step"] - k
    params = {name: jnp.asarray(v)
              for name, v in inputs["params"].items()}
    state = {
        "params": params,
        "stats": {name: jnp.asarray(v)
                  for name, v in inputs["stats"].items()},
        "mu": {name: jnp.zeros_like(v) for name, v in params.items()},
        "nu": {name: jnp.full_like(v, nu0)
               for name, v in params.items()},
        "count": jnp.asarray(step0, jnp.int32)}
    # The loop keys step s with fold_in(PRNGKey(seed + 1), s).
    step_rng = jax.random.PRNGKey(seed32 + 1)
    metrics = None
    for i, batch in enumerate(inputs["batches"]):
      state, metrics = step(
          state, jax.tree_util.tree_map(jnp.asarray, batch),
          jax.random.fold_in(step_rng, step0 + i))
    state = jax.device_get(state)
    metrics = {name: float(v) for name, v in metrics.items()}
  return state, metrics
