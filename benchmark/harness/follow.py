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

What this side costs, and what a family's reference module owes it.
The jitted step donates its state, so the K steps update parameters and
both moments in place: on the device stand parameters, first and second
moment and one step's gradients, **16 bytes a parameter**, plus
whatever `loss` and its gradient keep alive in between (the module's
own activations, float32 at `highest`). A family whose activations do
not fit beside that computes its `loss` in blocks (`jax.checkpoint`
around a layer, attention by blocks of queries): the blocks are the
module's, the optimizer state is this file's. To the host come back
`params`, `mu` and `stats`, what `check.numbers_between` reads: 8 bytes
a parameter, beside the start weights (4) and the loop's first
checkpoint (12) that the run's record holds; the check itself goes
through them a leaf at a time (`harness/check.py`).
`tools/follow_memory.py` reads both peaks at a parameter count of
one's choosing.
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


def step_of(config: dict, control: bool = False):
  """One step of the reference side as a plain function of (state,
  batch, rng): `jax.value_and_grad` of the reference module's loss,
  then `adam`. `follow` runs it through `donated_step`; a test jits it
  as it is, as the arithmetic to hold that against."""
  reference = module_of(config, "reference")
  learner = config["learner"]

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

  return step


def donated_step(config: dict, control: bool = False):
  """`step_of` jitted with its state donated: each step writes
  parameters and moments over the ones it read, so the state stands on
  the device once, not in and out (24 bytes a parameter before a
  gradient exists, where 16 hold all of it)."""
  return jax.jit(step_of(config, control), donate_argnums=(0,))


def start_state(config: dict, inputs: dict, step0: int) -> dict:
  """The state the K steps start from, on the device: the benchmark's
  weights (each leaf goes over once), Adam's moments as the start
  checkpoint has them, `step0` steps behind it."""
  nu0 = module_of(config, "weights").ADAM_NU0
  params = {name: jnp.asarray(v)
            for name, v in inputs["params"].items()}
  return {
      "params": params,
      "stats": {name: jnp.asarray(v)
                for name, v in inputs["stats"].items()},
      "mu": {name: jnp.zeros_like(v) for name, v in params.items()},
      "nu": {name: jnp.full_like(v, nu0)
             for name, v in params.items()},
      "count": jnp.asarray(step0, jnp.int32)}


def follow(config: dict, inputs: dict, seed32: int,
           control: bool = False):
  """K steps on the K batches the loop's stream yielded first, from
  the benchmark's weights. Returns (state after K steps as flat dicts
  `params`, `mu`, `stats` on the host; last step's metrics)."""
  step = donated_step(config, control)
  with jax.default_matmul_precision("highest"):
    step0 = inputs["first_step"] - len(inputs["batches"])
    state = start_state(config, inputs, step0)
    # The loop keys step s with fold_in(PRNGKey(seed + 1), s).
    step_rng = jax.random.PRNGKey(seed32 + 1)
    metrics = None
    for i, batch in enumerate(inputs["batches"]):
      state, metrics = step(
          state, jax.tree_util.tree_map(jnp.asarray, batch),
          jax.random.fold_in(step_rng, step0 + i))
    # Only what `check.numbers_between` reads: not `nu`, not `count`.
    state = jax.device_get(
        {part: state[part] for part in ("params", "mu", "stats")})
    metrics = {name: float(v) for name, v in metrics.items()}
  return state, metrics
