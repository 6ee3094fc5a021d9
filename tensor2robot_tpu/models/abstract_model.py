"""AbstractT2RModel: the model_fn template, redesigned as pure JAX steps.

Reference parity: tensor2robot `models/abstract_model.py` —
`AbstractT2RModel.model_fn` with its preprocess → `inference_network_fn`
→ train/eval/predict branches, optimizer creation, and checkpoint
warm-start (`maybe_init_from_checkpoint`); SURVEY.md §4.2.

TPU-native redesign: instead of one `model_fn(features, labels, mode)`
building a TF graph per mode, the model exposes three PURE functions —
`train_step`, `eval_step`, `predict_step` — each of which traces
preprocess + network + loss into a single XLA program. The trainer jits
them over a device mesh (batch sharded on the data axis, params
replicated or sharded by the model's partitioning rules); GSPMD inserts
the gradient all-reduce the reference got from CrossShardOptimizer.
Mutable collections (batch_norm stats) and dropout RNG are threaded
explicitly, as JAX requires.
"""

from __future__ import annotations

import abc
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from tensor2robot_tpu import config as gin
from tensor2robot_tpu import specs as specs_lib
from tensor2robot_tpu.data.abstract_input_generator import Mode
from tensor2robot_tpu.models import optimizers as opt_lib
from tensor2robot_tpu.models.model_interface import ModelInterface
from tensor2robot_tpu.preprocessors.noop_preprocessor import NoOpPreprocessor
from tensor2robot_tpu.specs import TensorSpecStruct


@flax.struct.dataclass
class TrainState:
  """Carried training state: step counter, params, mutable stats, opt."""

  step: jax.Array
  params: Any
  batch_stats: Any  # empty dict when the network has no BN-style stats
  opt_state: Any

  @property
  def variables(self) -> Dict[str, Any]:
    out = {"params": self.params}
    if self.batch_stats:
      out["batch_stats"] = self.batch_stats
    return out


class AbstractT2RModel(ModelInterface):
  """Base class for all models: specs + flax network + loss.

  Subclasses implement:
    * `get_feature_specification(mode)` / `get_label_specification(mode)`
    * `create_network() -> nn.Module` — the module is applied as
      `module(features_struct, train=<bool>)` and returns an output
      structure (dict / TensorSpecStruct / array).
    * `model_train_fn(features, labels, outputs, mode) -> (loss, scalars)`
  Optionally:
    * `model_eval_fn(...) -> scalars` (defaults to train_fn's scalars)
  """

  def __init__(self,
               preprocessor_cls: Optional[Callable] = None,
               create_optimizer_fn: Callable = opt_lib.create_optimizer,
               init_from_checkpoint_path: Optional[str] = None,
               device_dtype=jnp.float32,
               aux_loss_weight: float = 0.01,
               remat_policy: Optional[str] = None):
    """Args:
      preprocessor_cls: class (or factory) called with the two model spec
        getter fns; defaults to NoOpPreprocessor.
      create_optimizer_fn: zero-arg factory returning an
        optax.GradientTransformation (gin binds its parameters).
      init_from_checkpoint_path: warm-start checkpoint directory; params
        present in the checkpoint override fresh initializers
        (reference: maybe_init_from_checkpoint).
      device_dtype: compute dtype networks should favor (bfloat16 on TPU).
      aux_loss_weight: weight on auxiliary losses the network sows into
        the "aux_loss" collection (e.g. the MoE load-balance loss);
        irrelevant for networks that sow none.
      remat_policy: rematerialization of the loss forward under the
        gradient (docs/PERF.md sweep knob): None/"none" keeps XLA's
        default (save everything), "full" = jax.checkpoint saving
        nothing, "dots" = save MXU outputs only
        (checkpoint_dots), "dots_no_batch" = save only batch-free dot
        outputs (dots_with_no_batch_dims_saveable). Remat trades HBM
        residency of forward activations for recompute — at large
        batch that headroom buys bigger fused K-step programs. Bitwise
        identical math (recompute is exact; pinned by tests).
    """
    self._preprocessor_cls = preprocessor_cls
    self._create_optimizer_fn = create_optimizer_fn
    self._init_from_checkpoint_path = init_from_checkpoint_path
    self._device_dtype = device_dtype
    self._aux_loss_weight = aux_loss_weight
    self._remat_policy = remat_policy
    self._preprocessor = None
    self._network = None
    self._tx = None

  # ---- specs ----

  @abc.abstractmethod
  def get_feature_specification(self, mode: Mode) -> TensorSpecStruct:
    ...

  @abc.abstractmethod
  def get_label_specification(
      self, mode: Mode) -> Optional[TensorSpecStruct]:
    ...

  @property
  def device_dtype(self):
    return self._device_dtype

  @property
  def preprocessor(self):
    if self._preprocessor is None:
      cls = self._preprocessor_cls or NoOpPreprocessor
      self._preprocessor = cls(self.get_feature_specification,
                               self.get_label_specification)
    return self._preprocessor

  # ---- network ----

  @abc.abstractmethod
  def create_network(self) -> nn.Module:
    ...

  @property
  def network(self) -> nn.Module:
    if self._network is None:
      self._network = self.create_network()
    return self._network

  @property
  def tx(self):
    if self._tx is None:
      self._tx = self._create_optimizer_fn()
    return self._tx

  def wrap_optimizer(self, wrapper: Callable,
                     key: Optional[str] = None) -> None:
    """Replaces the optimizer with `wrapper(tx)` — the trainer-side
    hook for mesh-dependent transformations (e.g.
    `optimizers.shard_weight_update`, which needs the mesh that only
    the training loop knows). Call before the step is traced.

    ``key`` makes the wrap IDEMPOTENT per key: re-wrapping with the
    same key replaces the previous incarnation instead of stacking on
    top of it. Trainers that may be invoked repeatedly on one model
    (one learner across device counts, successive runs in a process) MUST
    pass a key — a stacked stale wrapper would otherwise pin the tx
    to a dead mesh's devices. Keyless wraps keep the raw composing
    behavior.
    """
    if key is None:
      self._tx = wrapper(self.tx)
      return
    if getattr(self, "_tx_keyed_base", None) is None:
      self._tx_keyed_base = self.tx
      self._tx_keyed_wrappers = {}
    self._tx_keyed_wrappers[key] = wrapper
    tx = self._tx_keyed_base
    for keyed_wrapper in self._tx_keyed_wrappers.values():
      tx = keyed_wrapper(tx)
    self._tx = tx

  AUX_LOSS_OUTPUT = "_aux_loss"

  def inference_network_fn(self,
                           variables: Dict[str, Any],
                           features: TensorSpecStruct,
                           mode: Mode,
                           rng: Optional[jax.Array] = None) -> Any:
    """Applies the network; returns (outputs, new_batch_stats).

    Auxiliary losses the network sows into the "aux_loss" collection
    (MoE load balance) are summed into `outputs[AUX_LOSS_OUTPUT]` for
    `loss_fn` to weight in; `predict_step` strips the key so serving
    signatures never see it.
    """
    train = mode == Mode.TRAIN
    rngs = {"dropout": rng} if (train and rng is not None) else None
    has_stats = "batch_stats" in variables
    mutable = ["aux_loss"]
    if train and has_stats:
      mutable.append("batch_stats")
    outputs, updates = self.network.apply(
        variables, features, train=train, rngs=rngs, mutable=mutable)
    if updates.get("aux_loss"):
      if not isinstance(outputs, dict):
        # Silently dropping a sown regularizer would let experts
        # collapse with no signal; the contract is explicit instead.
        raise TypeError(
            f"{type(self.network).__name__} sowed 'aux_loss' "
            f"variables but returned {type(outputs).__name__} "
            f"outputs; networks with auxiliary losses must return a "
            f"dict so the loss can be threaded through "
            f"(outputs[{self.AUX_LOSS_OUTPUT!r}]).")
      from tensor2robot_tpu.parallel.moe import collect_aux_losses
      outputs[self.AUX_LOSS_OUTPUT] = collect_aux_losses(updates)
    new_stats = (updates.get("batch_stats", {}) if train and has_stats
                 else variables.get("batch_stats", {}))
    return outputs, new_stats

  # ---- losses/metrics ----

  @abc.abstractmethod
  def model_train_fn(self,
                     features: TensorSpecStruct,
                     labels: Optional[TensorSpecStruct],
                     outputs: Any,
                     mode: Mode) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Returns (scalar loss, scalar metrics dict)."""

  def model_eval_fn(self,
                    features: TensorSpecStruct,
                    labels: Optional[TensorSpecStruct],
                    outputs: Any) -> Dict[str, jax.Array]:
    loss, scalars = self.model_train_fn(features, labels, outputs,
                                        Mode.EVAL)
    return {"loss": loss, **scalars}

  # ---- state ----

  def create_inference_state(self, rng: jax.Array,
                             batch_size: int = 1) -> TrainState:
    """Initializes network variables only — no optimizer state.

    The dummy init batch is derived mechanically from the preprocessor's
    OUT specs — the spec system seeding initialization the same way it
    seeds parsers and tests. Predictors use this directly: serving never
    needs (or pays the memory for) optimizer moments.
    """
    out_spec = self.preprocessor.get_out_feature_specification(Mode.TRAIN)
    # include_optional=False: input generators exclude optional specs
    # from real batches, so init must see the same tree structure or the
    # first jitted step diverges from the initialized params.
    dummy = specs_lib.make_random_tensors(
        out_spec, batch_size=batch_size, seed=0, include_optional=False,
        sequence_length=self.init_sequence_length)
    dummy = jax.tree_util.tree_map(jnp.asarray, dummy)
    init_rng, dropout_rng = jax.random.split(rng)
    variables = self.network.init(
        {"params": init_rng, "dropout": dropout_rng}, dummy, train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    if self._init_from_checkpoint_path:
      params, batch_stats = self.maybe_init_from_checkpoint(
          params, batch_stats)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=None,
    )

  @property
  def init_sequence_length(self):
    """Time-axis length of the dummy init batch for sequence specs.

    None → the random-data default. Models whose networks constrain T
    (e.g. sequence-parallel attention needs T divisible by the mesh's
    `seq` axis) override this so initialization traces a valid shape.
    """
    return None

  def create_train_state(self, rng: jax.Array,
                         batch_size: int = 1) -> TrainState:
    """Initializes params + batch stats + optimizer state from specs."""
    state = self.create_inference_state(rng, batch_size=batch_size)
    return state.replace(opt_state=self.tx.init(state.params))

  def maybe_init_from_checkpoint(self, params, batch_stats=None):
    """Warm-starts params (and BN stats) from `init_from_checkpoint_path`.

    BN moving averages ride along when the model carries batch_stats —
    warm-starting params alone would pair trained weights with
    fresh-init statistics, the same silent degradation the predictor
    path guards against.
    """
    from tensor2robot_tpu.utils import checkpoints as ckpt_lib
    if batch_stats:
      variables = ckpt_lib.restore_variables(
          self._init_from_checkpoint_path,
          like={"params": params, "batch_stats": batch_stats})
      return variables["params"], variables["batch_stats"]
    restored = ckpt_lib.restore_params(
        self._init_from_checkpoint_path, like=params)
    return restored, batch_stats

  # ---- steps (pure; the trainer jits these) ----

  def network_inputs_from_labels(self,
                                 features: TensorSpecStruct,
                                 labels: Optional[TensorSpecStruct],
                                 mode: Mode) -> TensorSpecStruct:
    """Hook: lift label-derived conditioning INPUTS into the features.

    Models whose networks consume parts of the labels as inputs —
    demonstration actions conditioning WTL/SNAIL policies — override
    this instead of re-implementing loss_fn. Runs after preprocessing
    in train/eval; at predict time the same inputs must arrive inside
    the feature struct directly (the condition_labels serving
    convention), so this hook is NOT called then. Default: unchanged.
    """
    del labels, mode
    return features

  def loss_fn(self, params, batch_stats, features, labels, rng,
              mode: Mode):
    variables = {"params": params}
    if batch_stats:
      variables["batch_stats"] = batch_stats
    rng_pre, rng_net = (jax.random.split(rng) if rng is not None
                        else (None, None))
    features, labels = self.preprocessor.preprocess(
        features, labels, mode, rng_pre)
    features = self.network_inputs_from_labels(features, labels, mode)
    outputs, new_stats = self.inference_network_fn(
        variables, features, mode, rng_net)
    # Pop BEFORE model_train_fn: subclass losses/metrics never see the
    # private key (predict_step shields its consumers the same way).
    aux = (outputs.pop(self.AUX_LOSS_OUTPUT, None)
           if isinstance(outputs, dict) else None)
    loss, scalars = self.model_train_fn(features, labels, outputs, mode)
    if aux is not None:
      loss = loss + self._aux_loss_weight * aux
      if "aux_loss" in scalars:
        raise ValueError(
            "model_train_fn reported a scalar named 'aux_loss'; that "
            "key is reserved for the network-sown auxiliary loss "
            f"({self.AUX_LOSS_OUTPUT}) — rename the subclass scalar.")
      scalars = {**scalars, "aux_loss": aux}
    return loss, (scalars, new_stats)

  def _loss_for_grad(self) -> Callable:
    """`loss_fn`, optionally under jax.checkpoint per `remat_policy`.

    `mode` (arg 5) is static — an enum, not a tracer. Recompute is
    exact arithmetic, so every policy is bitwise-equal to "none"; the
    choice only moves the HBM-vs-recompute trade (docs/PERF.md).
    """
    policy_name = self._remat_policy
    if policy_name in (None, "none"):
      return self.loss_fn
    policies = {
        "full": None,
        "dots": "checkpoint_dots",
        "dots_no_batch": "dots_with_no_batch_dims_saveable",
    }
    if policy_name not in policies:
      raise ValueError(
          f"remat_policy={policy_name!r} not in "
          f"{['none'] + sorted(policies)}")
    attr = policies[policy_name]
    policy = getattr(jax.checkpoint_policies, attr) if attr else None
    return jax.checkpoint(self.loss_fn, policy=policy,
                          static_argnums=(5,))

  def train_step(self, state: TrainState, features, labels,
                 rng: jax.Array, axis_name: Optional[str] = None
                 ) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """One optimizer step on `features`/`labels`.

    `axis_name` (trace-time static) selects the SPMD data-parallel
    form: inside a `pmap`/`shard_map` over that axis, per-device
    gradients are `lax.pmean`'d before the optimizer — every replica
    then applies the identical update, so replicated params STAY
    replicated (the Podracer/Anakin pod contract, docs/ENVS.md).
    Batch-norm statistics and the reported metrics are pmean'd the
    same way (cross-replica batch stats; device-0 metrics are global
    means). `axis_name=None` (the default) is the unchanged
    single-program step.

    Composition of the two halves below — `train_grads` (forward/
    backward, collective-synchronized) and `apply_gradients` (the
    elementwise weight-sized update). The shard_map pod program calls
    the halves SEPARATELY so the backward runs per-device under
    `shard_map` while the update runs as jit+mesh GSPMD — the seam
    the ZeRO weight-update sharding composes through
    (docs/SHARDING.md).
    """
    grads, new_stats, metrics = self.train_grads(
        state, features, labels, rng, axis_name=axis_name)
    return self.apply_gradients(state, grads, new_stats), metrics

  def train_grads(self, state: TrainState, features, labels,
                  rng: jax.Array, axis_name: Optional[str] = None
                  ) -> Tuple[Any, Any, Dict[str, jax.Array]]:
    """The forward/backward half of `train_step`.

    Returns ``(grads, new_batch_stats, metrics)`` — gradients, batch
    stats, and loss metrics, already `lax.pmean`'d over `axis_name`
    when given. Everything collective lives here; no optimizer state
    is touched.
    """
    grad_fn = jax.value_and_grad(self._loss_for_grad(), has_aux=True)
    (loss, (scalars, new_stats)), grads = grad_fn(
        state.params, state.batch_stats, features, labels, rng, Mode.TRAIN)
    if axis_name is not None:
      grads = jax.lax.pmean(grads, axis_name)
      loss = jax.lax.pmean(loss, axis_name)
      scalars = jax.lax.pmean(scalars, axis_name)
      if new_stats:
        new_stats = jax.lax.pmean(new_stats, axis_name)
    metrics = {"loss": loss,
               "grad_norm": optax.global_norm(grads),
               **scalars}
    return grads, new_stats, metrics

  def apply_gradients(self, state: TrainState, grads: Any,
                      new_stats: Any) -> TrainState:
    """The optimizer half of `train_step`: tx.update + apply.

    Elementwise weight-sized math (plus whatever the configured optax
    chain adds), so under a mesh whose tx is wrapped with
    `optimizers.shard_weight_update` the GSPMD constraints shard it
    cross-replica — each device updates 1/N of every weight's
    moments.
    """
    updates, new_opt_state = self.tx.update(grads, state.opt_state,
                                            state.params)
    new_params = optax.apply_updates(state.params, updates)
    return state.replace(
        step=state.step + 1,
        params=new_params,
        batch_stats=new_stats,
        opt_state=new_opt_state,
    )

  def eval_step(self, state: TrainState, features,
                labels) -> Dict[str, jax.Array]:
    variables = state.variables
    features, labels = self.preprocessor.preprocess(
        features, labels, Mode.EVAL, None)
    features = self.network_inputs_from_labels(features, labels,
                                               Mode.EVAL)
    outputs, _ = self.inference_network_fn(variables, features, Mode.EVAL)
    # Same aux treatment as loss_fn, so the eval "loss" tracks the
    # optimized objective and expert collapse is visible in eval too.
    aux = (outputs.pop(self.AUX_LOSS_OUTPUT, None)
           if isinstance(outputs, dict) else None)
    metrics = self.model_eval_fn(features, labels, outputs)
    if aux is not None:
      if "aux_loss" in metrics:
        raise ValueError(
            "model_eval_fn reported a metric named 'aux_loss'; that "
            "key is reserved for the network-sown auxiliary loss "
            f"({self.AUX_LOSS_OUTPUT}) — rename the subclass metric.")
      metrics = {**metrics, "aux_loss": aux}
      # model_eval_fn's contract promises only "scalars" — a custom
      # override may not report a "loss" key at all.
      if "loss" in metrics:
        metrics["loss"] = (metrics["loss"]
                           + self._aux_loss_weight * aux)
    return metrics

  def predict_step(self, state: TrainState, features) -> Any:
    variables = state.variables
    features, _ = self.preprocessor.preprocess(
        features, None, Mode.PREDICT, None)
    outputs, _ = self.inference_network_fn(variables, features,
                                           Mode.PREDICT)
    if isinstance(outputs, dict):
      outputs.pop(self.AUX_LOSS_OUTPUT, None)
    return outputs
