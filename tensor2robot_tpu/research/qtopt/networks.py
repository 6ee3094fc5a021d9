"""QT-Opt grasping Q-network.

Reference parity: tensor2robot `research/qtopt/t2r_models.py` +
`networks.py` — the grasping Q-network: camera image + proposed action
(+ gripper/height state) → grasp-success Q logit (SURVEY.md §3 "QT-Opt
models"; exact class names tagged [U-low] there; file:line unavailable —
empty reference mount). Architecture follows the QT-Opt paper
(arXiv:1806.10293): conv torso over the image, the action/state vector
embedded and broadcast-added into mid-level conv features, conv head,
then a dense head to a scalar logit.

TPU-first: NHWC bf16 convs sized in MXU-friendly multiples, uint8
images cast+scaled on device, the action merge is a 1×1-conv-equivalent
dense broadcast (fuses into the surrounding convs), no dynamic shapes.

The network is split at the action merge into two callable halves:
`encode(image)` — everything action-independent — and
`head(encoded, features)` — action embed + conv head + dense. CEM
exploits the split: the torso runs ONCE per state and only the (much
cheaper) head runs per population candidate, instead of re-convolving
the full image population × iterations times per Bellman target.

Device-side names (`jax.named_scope`: operation metadata only, shown
by a profiler trace on every operation): `torso` (encode, either
tower), `q_head` (the online critic's action merge, head convs and
MLP; in the population path the MLP alone), `cem_tower` (the
population path's action embed, merge and head convs), `cem_pool`
(its spatial pooling; `cem.py` names the selection the same).
"""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu.layers import MLP
from tensor2robot_tpu.models.critic_model import Q_VALUE


def _gather_action_extras(features, dtype):
  """Flattens action + every non-image float feature, sorted by key."""
  flat = (features.to_flat_dict() if hasattr(features, "to_flat_dict")
          else dict(features))
  action = flat["action"]
  extras = [action.reshape(action.shape[0], -1).astype(dtype)]
  for key in sorted(flat):
    if key in ("image", "action"):
      continue
    value = flat[key]
    if jnp.issubdtype(value.dtype, jnp.floating):
      extras.append(value.reshape(value.shape[0], -1).astype(dtype))
  return jnp.concatenate(extras, axis=-1)


class GraspingQNetwork(nn.Module):
  """Image + action → Q logit, QT-Opt-paper style."""

  torso_filters: Sequence[int] = (32, 64)
  head_filters: Sequence[int] = (64, 64)
  action_embedding_size: int = 64
  dense_sizes: Sequence[int] = (64, 64)
  use_batch_norm: bool = True
  # TPU stem: rearrange s×s spatial blocks into channels before the
  # first conv (1 = off). A 3-channel image leaves the MXU's reduce
  # dimension ~90% padding in the stem conv (3×3×3 = 27 taps);
  # space_to_depth=4 turns [H, W, 3] into [H/4, W/4, 48] so the first
  # conv contracts 432 taps instead — the standard TPU trick for
  # large-image stems. The first torso conv then runs stride 1 (the
  # rearrange already downsampled 4×); remaining convs are unchanged.
  space_to_depth: int = 1
  dtype: Any = jnp.bfloat16

  def setup(self):
    conv = lambda f, name, s=(2, 2): nn.Conv(  # noqa: E731
        f, (3, 3), strides=s, padding="SAME",
        use_bias=not self.use_batch_norm, dtype=self.dtype, name=name)
    norm = lambda name: nn.BatchNorm(  # noqa: E731
        momentum=0.9, dtype=self.dtype, name=name)
    self._torso_convs = [
        conv(f, f"torso_conv_{i}",
             s=(1, 1) if i == 0 and self.space_to_depth > 1 else (2, 2))
        for i, f in enumerate(self.torso_filters)]
    self._torso_bns = ([norm(f"torso_bn_{i}")
                        for i in range(len(self.torso_filters))]
                       if self.use_batch_norm else [])
    self._head_convs = [conv(f, f"head_conv_{i}")
                        for i, f in enumerate(self.head_filters)]
    self._head_bns = ([norm(f"head_bn_{i}")
                       for i in range(len(self.head_filters))]
                      if self.use_batch_norm else [])
    self._action_embed_0 = nn.Dense(
        self.action_embedding_size, dtype=self.dtype,
        name="action_embed_0")
    # The merge adds the embedded action onto the torso's output
    # channels (3 = raw RGB when the torso is empty).
    merge_channels = (self.torso_filters[-1] if self.torso_filters
                      else 3)
    self._action_embed_1 = nn.Dense(
        merge_channels, dtype=self.dtype, name="action_embed_1")
    self._q_head = MLP(hidden_sizes=tuple(self.dense_sizes),
                       output_size=1, dtype=self.dtype, name="q_head")

  @jax.named_scope("torso")
  def encode(self, image, train: bool = False, taps=None):
    """Action-independent half: image → torso feature map [B,h,w,C].

    CEM callers run this once per state and tile the (small) result
    over the candidate population instead of the full image. `taps`
    (optional dict) records each conv's INPUT tensor under
    ``torso_in_<i>`` — the int8 calibration points
    (`calibration_stats`); passing it changes nothing else.
    """
    x = image.astype(self.dtype) / jnp.asarray(255.0, self.dtype)
    if self.space_to_depth > 1:
      s = self.space_to_depth
      b, h, w, c = x.shape
      if h % s or w % s:
        raise ValueError(
            f"Image {h}x{w} must divide space_to_depth={s}.")
      # [B, H, W, C] -> [B, H/s, W/s, s*s*C]: each s×s block's pixels
      # become channels of one coarse position.
      x = x.reshape(b, h // s, s, w // s, s, c)
      x = x.transpose(0, 1, 3, 2, 4, 5).reshape(
          b, h // s, w // s, s * s * c)
    for i, conv in enumerate(self._torso_convs):
      if taps is not None:
        taps[f"torso_in_{i}"] = x
      x = conv(x)
      if self.use_batch_norm:
        x = self._torso_bns[i](x, use_running_average=not train)
      x = nn.relu(x)
    return x

  @jax.named_scope("q_head")
  def head(self, encoded, features, train: bool = False):
    """Action-dependent half: (torso features, action+extras) → Q."""
    a = _gather_action_extras(features, self.dtype)
    a = nn.relu(self._action_embed_0(a))
    a = self._action_embed_1(a)
    x = encoded + a[:, None, None, :]
    for i, conv in enumerate(self._head_convs):
      x = conv(x)
      if self.use_batch_norm:
        x = self._head_bns[i](x, use_running_average=not train)
      x = nn.relu(x)
    x = jnp.mean(x, axis=(1, 2))
    logit = self._q_head(x, train=train)
    return {Q_VALUE: logit[..., 0].astype(jnp.float32)}

  def score_population(self, encoded, extras, actions):
    """Scores a CEM population without materializing tiled torso maps.

    The naive population path tiles `encoded` to [B*P, h, w, C] — at
    the 64-wide cell a ~0.5 GB materialization per CEM iteration that
    profiles as the single most expensive op in the Bellman step. The
    first head conv is linear, so conv(encoded + broadcast(a)) splits
    exactly into conv(encoded) — once per STATE — plus the action
    contribution conv(broadcast(a)), which for a spatially-constant
    input reduces to an einsum with the kernel's per-position tap sums
    V[c, h', w', o] (border positions see fewer taps; V is computed
    border-exactly by pushing a one-hot channel basis through the conv).
    Only the post-merge [B, P, h', w', C'] activation is ever
    materialized, after most of the head FLOPs are already spent.

    Args:
      encoded: [B, h, w, C] torso features from `encode`.
      extras: dict of non-image state features keyed like the feature
        struct (values [B, ...] floats); may be empty.
      actions: [B, P, A] candidate actions.
    Eval-mode only (CEM target/policy scoring): BN uses running stats.
    Returns [B, P] Q values.
    """
    b, p, a_dim = actions.shape
    a = self._population_action_embed(extras, actions)
    if self._head_convs:
      pooled = self._population_tail(
          self._population_merge(encoded, a))
      with jax.named_scope("q_head"):
        logit = self._q_head(pooled, train=False)
      return logit[..., 0].astype(jnp.float32).reshape(p, b).T
    x = encoded[:, None] + a[:, :, None, None, :]
    x = x.reshape((b * p,) + x.shape[2:])
    x = jnp.mean(x, axis=(1, 2))
    logit = self._q_head(x, train=False)
    return logit[..., 0].astype(jnp.float32).reshape(b, p)

  @jax.named_scope("cem_tower")
  def _population_action_embed(self, extras, actions):
    """Action + extras → merge-channel embedding a [B, P, C]."""
    b, p, a_dim = actions.shape
    parts = [actions.astype(self.dtype)]
    for key in sorted(extras):
      value = extras[key]
      if jnp.issubdtype(value.dtype, jnp.floating):
        tiled = jnp.broadcast_to(
            value.reshape(b, 1, -1).astype(self.dtype),
            (b, p, int(np.prod(value.shape[1:]))))
        parts.append(tiled)
    a = jnp.concatenate(parts, axis=-1)
    a = nn.relu(self._action_embed_0(a))
    return self._action_embed_1(a)  # [B, P, C]

  @jax.named_scope("cem_tower")
  def _population_merge(self, encoded, a):
    """The linearity-split merge: [P·B, h', w', C'] relu'd tensor.

    P-MAJOR row order throughout (see the GEMM/concatenate notes
    inline) — the single hottest tensor of the Bellman step.
    """
    p = a.shape[1]
    conv0 = self._head_convs[0]
    c = encoded.shape[-1]
    enc0 = conv0(encoded)  # [B, h', w', C'] — bias (if any) included.
    # Tap-sum tensor: push the one-hot channel basis (constant over
    # space) through the conv; subtract the zero-input response so a
    # conv bias isn't double-counted into every channel's row.
    basis = jnp.broadcast_to(
        jnp.eye(c, dtype=self.dtype)[:, None, None, :],
        (c,) + encoded.shape[1:])
    v = conv0(basis)  # [C, h', w', C']
    if not self.use_batch_norm:  # bias active ⇒ remove from basis rows
      v = v - conv0(jnp.zeros((1,) + encoded.shape[1:], self.dtype))
    if self.use_batch_norm:
      # Eval-mode BN is per-channel affine: BN(enc0 + act) =
      # BN(enc0) + s·act. Fold s into the tap-sum tensor so the big
      # population tensor never enters flax BN (whose float32
      # internals force a layout-changing f32 copy of the whole
      # tensor — profiled as the top op of the Bellman step).
      bn0 = self._head_bns[0]
      out_c = v.shape[-1]
      shift = bn0(jnp.zeros((1, 1, 1, out_c), self.dtype),
                  use_running_average=True)
      scale = bn0(jnp.ones((1, 1, 1, out_c), self.dtype),
                  use_running_average=True) - shift
      enc0 = bn0(enc0, use_running_average=True)
      v = v * scale.astype(self.dtype)
    # The action contribution as a flat 2-D GEMM in P-MAJOR row
    # order: a bphwo einsum (and a B-major GEMM) both leave XLA
    # layout assignment inserting a transpose copy of the whole
    # population tensor before the next conv (profiled at up to 60%
    # of the Bellman step). With rows ordered (p, b), the enc0
    # addend is a CONTIGUOUS axis-0 replication (see the
    # concatenate note below) — no transpose anywhere, and the GEMM
    # output is already NHWC for the conv. Measured end to end:
    # 225 (einsum) -> 362 (B-major GEMM) -> 441 (P-major, round 3).
    h2, w2, oc = v.shape[1:]
    b = encoded.shape[0]
    a_pm = a.transpose(1, 0, 2).reshape(p * b, c)
    act = (a_pm @ v.reshape(c, -1)).reshape(p * b, h2, w2, oc)
    # Population-replicating enc0, three measured variants (a v5e,
    # round 4, pre-ledger): jnp.tile = 487 steps/s (lowers as broadcast
    # + layout-changing reshape — two full copies, profiled at ~36%
    # of device time); 5-D broadcast-add then reshape = 414 (layout
    # assignment re-transposes the population tensor before the
    # add's consumer); axis-0 concatenate of p views = 620 — ONE
    # contiguous write, no relayout. Don't "simplify" back to tile.
    enc_rep = jnp.concatenate([enc0.astype(self.dtype)] * p, axis=0)
    return nn.relu(act + enc_rep)

  def _population_tail(self, x, taps=None):
    """Remaining head convs + spatial pool: [P·B, h', w', C'] →
    pooled [P·B, C'']. `taps` records each conv's input under
    ``head_in_<i>`` (int8 calibration points)."""
    with jax.named_scope("cem_tower"):
      for i, conv in enumerate(self._head_convs[1:], start=1):
        if taps is not None:
          taps[f"head_in_{i}"] = x
        x = conv(x)
        if self.use_batch_norm:
          x = self._head_bns[i](x, use_running_average=True)
        x = nn.relu(x)
    with jax.named_scope("cem_pool"):
      return jnp.mean(x, axis=(1, 2))

  def pool_population(self, encoded, extras, actions):
    """`score_population` minus the q-head MLP: pooled population
    features in P-major [P, B, C''] (a free reshape of the P-major
    tail output — no transpose touches the hot path). The fused CEM
    select kernel (`ops.fused_cem_select`) consumes this and runs
    scoring + running top-k + elite stats in one kernel.
    """
    b, p, _ = actions.shape
    a = self._population_action_embed(extras, actions)
    if self._head_convs:
      pooled = self._population_tail(
          self._population_merge(encoded, a))
      return pooled.reshape(p, b, -1)
    x = encoded[:, None] + a[:, :, None, None, :]
    x = x.reshape((b * p,) + x.shape[2:])
    pooled = jnp.mean(x, axis=(1, 2))
    return pooled.reshape(b, p, -1).transpose(1, 0, 2)

  def calibration_stats(self, features):
    """Eval-mode forward recording max-abs at every int8 quantization
    point — the held-out-batch calibration `quantize_tower` consumes.

    `features` is a flat feature struct/dict with ``image``,
    ``action`` and any extra state floats; the batch's own actions
    stand in as a population of 1 (activation ranges are state-, not
    population-, dominated). Returns {point_name: f32 scalar}.
    """
    taps = {}
    flat = (features.to_flat_dict()
            if hasattr(features, "to_flat_dict") else dict(features))
    encoded = self.encode(flat["image"], train=False, taps=taps)
    action = flat["action"]
    actions = action.reshape(action.shape[0], 1, -1)
    extras = {k: v for k, v in flat.items()
              if k not in ("image", "action")}
    a = self._population_action_embed(extras, actions)
    if self._head_convs:
      self._population_tail(self._population_merge(encoded, a),
                            taps=taps)
    return {k: jnp.max(jnp.abs(v)).astype(jnp.float32)
            for k, v in taps.items()}

  def __call__(self, features, train: bool = False):
    encoded = self.encode(features["image"], train=train)
    return self.head(encoded, features, train=train)


# ---------------------------------------------------------------------------
# int8 CEM inference tower
#
# The CEM Q-tower forward is inference-only (Bellman targets + acting),
# and the profiled Bellman step is HBM-bound: the [B·P, h', w', C']
# merged population tensor's read dominates device time. Storing the
# tower's activations (and weights) as int8 halves that traffic; the
# arithmetic stays on the MXU in the network's compute dtype (bf16 in
# production — int8 values up to ±127 are exact in bf16, and the MXU
# accumulates partial products in f32 before the one bf16 rounding at
# output, the "bf16 accumulation" contract). Per-output-channel weight
# scales are computed from the CURRENT params inside the traced step
# (cheap elementwise work, so Polyak-drifting target params requantize
# every step); per-tensor activation scales come from a one-time
# held-out-batch calibration (`GraspingQNetwork.calibration_stats`).
# Selected by gin (`QTOptLearner.cem_inference = "int8"`), gated by the
# end-metric parity tests in tests/test_qtopt.py against bf16.
# ---------------------------------------------------------------------------

_BN_EPS = 1e-5  # flax nn.BatchNorm default; the eval-affine fold assumes it
_CONV_DIMS = ("NHWC", "HWIO", "NHWC")


def _eval_bn_affine(bn_params, bn_stats):
  """Eval-mode BN as per-channel (scale, shift) f32."""
  scale = (bn_params["scale"].astype(jnp.float32)
           / jnp.sqrt(bn_stats["var"].astype(jnp.float32) + _BN_EPS))
  shift = (bn_params["bias"].astype(jnp.float32)
           - bn_stats["mean"].astype(jnp.float32) * scale)
  return scale, shift


def _quantize_weight(w):
  """Per-output-channel symmetric int8: w ≈ w_q · scale[c_out]."""
  w = w.astype(jnp.float32)
  red = tuple(range(w.ndim - 1))
  scale = jnp.maximum(jnp.max(jnp.abs(w), axis=red) / 127.0, 1e-12)
  w_q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
  return w_q, scale


def _quantize_act(x, scale):
  """Per-tensor symmetric int8 with a calibrated scale."""
  return jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                  -127, 127).astype(jnp.int8)


def scales_from_stats(stats) -> dict:
  """max-abs calibration stats → per-tensor int8 scales (host floats,
  so they bake into the traced step as constants)."""
  return {k: max(float(v) / 127.0, 1e-8) for k, v in stats.items()}


def quantize_tower(network: GraspingQNetwork, variables,
                   act_scales: dict) -> dict:
  """Builds the int8 tower pytree from params + calibrated act scales.

  Pure and traceable — call INSIDE the step so drifting (target)
  params requantize each step. Each layer entry: ``w_q`` int8 HWIO
  kernel, ``eff_scale`` f32 [c_out] (activation · weight · BN scales
  folded into one multiplier), ``shift`` f32 [c_out] (BN shift or conv
  bias), ``act_scale`` f32 scalar for the layer's input quantizer.
  """
  params = variables["params"]
  stats = variables.get("batch_stats", {})

  def layer(conv_name, bn_name, act_key):
    w_q, w_scale = _quantize_weight(params[conv_name]["kernel"])
    a_scale = jnp.asarray(act_scales[act_key], jnp.float32)
    if network.use_batch_norm:
      bn_scale, shift = _eval_bn_affine(params[bn_name],
                                        stats[bn_name])
      eff = a_scale * w_scale * bn_scale
    else:
      eff = a_scale * w_scale
      shift = params[conv_name]["bias"].astype(jnp.float32)
    return {"w_q": w_q, "eff_scale": eff, "shift": shift,
            "act_scale": a_scale}

  return {
      "torso": [layer(f"torso_conv_{i}", f"torso_bn_{i}",
                      f"torso_in_{i}")
                for i in range(len(network.torso_filters))],
      "head": [layer(f"head_conv_{i}", f"head_bn_{i}",
                     f"head_in_{i}")
               for i in range(1, len(network.head_filters))],
  }


def _int8_conv(x, layer, stride, dtype):
  """quantize → int8-valued conv in `dtype` → fold scales → relu."""
  x_q = _quantize_act(x, layer["act_scale"])
  y = jax.lax.conv_general_dilated(
      x_q.astype(dtype), layer["w_q"].astype(dtype), stride, "SAME",
      dimension_numbers=_CONV_DIMS)
  y = (y.astype(jnp.float32) * layer["eff_scale"] + layer["shift"])
  return jnp.maximum(y, 0.0).astype(dtype)


@jax.named_scope("torso")
def quantized_encode(network: GraspingQNetwork, tower: dict, image):
  """int8 twin of `GraspingQNetwork.encode` (eval mode)."""
  dt = network.dtype
  x = image.astype(dt) / jnp.asarray(255.0, dt)
  s = network.space_to_depth
  if s > 1:
    b, h, w, c = x.shape
    x = x.reshape(b, h // s, s, w // s, s, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(
        b, h // s, w // s, s * s * c)
  for i, layer in enumerate(tower["torso"]):
    stride = (1, 1) if i == 0 and s > 1 else (2, 2)
    x = _int8_conv(x, layer, stride, dt)
  return x


def _dense(params, name, x, dtype, relu=False):
  w = params[name]["kernel"].astype(dtype)
  b = params[name]["bias"].astype(dtype)
  y = x.astype(dtype) @ w + b
  return nn.relu(y) if relu else y


def _quantized_population_pooled(network: GraspingQNetwork,
                                 tower: dict, variables, encoded,
                                 extras, actions):
  """int8 twin of the population path up to the pooled features.

  Mirrors `_population_merge` + `_population_tail` with the SAME
  P-major layout tricks; the merged population tensor — the hot
  tensor — is stored int8 between the merge and the next conv.
  Returns pooled [P·B, C''] in the compute dtype.
  """
  params = variables["params"]
  dt = network.dtype
  b, p, _ = actions.shape

  with jax.named_scope("cem_tower"):
    parts = [actions.astype(dt)]
    for key in sorted(extras):
      value = extras[key]
      if jnp.issubdtype(value.dtype, jnp.floating):
        parts.append(jnp.broadcast_to(
            value.reshape(b, 1, -1).astype(dt),
            (b, p, int(np.prod(value.shape[1:])))))
    a = _dense(params, "action_embed_0", jnp.concatenate(parts, -1),
               dt, relu=True)
    a = _dense(params, "action_embed_1", a, dt)  # [B, P, C]

  if not network.head_filters:
    x = encoded[:, None] + a[:, :, None, None, :]
    x = x.reshape((b * p,) + x.shape[2:])
    return jnp.mean(x, axis=(1, 2)).reshape(b, p, -1) \
        .transpose(1, 0, 2).reshape(p * b, -1)

  with jax.named_scope("cem_tower"):
    # conv0 linearity split, on the raw kernel (bias only without BN).
    k0 = params["head_conv_0"]["kernel"].astype(dt)
    c = encoded.shape[-1]
    enc0 = jax.lax.conv_general_dilated(
        encoded.astype(dt), k0, (2, 2), "SAME",
        dimension_numbers=_CONV_DIMS)
    basis = jnp.broadcast_to(
        jnp.eye(c, dtype=dt)[:, None, None, :], (c,) + encoded.shape[1:])
    v = jax.lax.conv_general_dilated(
        basis, k0, (2, 2), "SAME", dimension_numbers=_CONV_DIMS)
    if network.use_batch_norm:
      bn_scale, bn_shift = _eval_bn_affine(params["head_bn_0"],
                                           variables["batch_stats"]
                                           ["head_bn_0"])
      enc0 = (enc0.astype(jnp.float32) * bn_scale
              + bn_shift).astype(dt)
      v = (v.astype(jnp.float32) * bn_scale).astype(dt)
    else:
      enc0 = enc0 + params["head_conv_0"]["bias"].astype(dt)
    h2, w2, oc = v.shape[1:]
    a_pm = a.transpose(1, 0, 2).reshape(p * b, c)
    act = (a_pm @ v.reshape(c, -1)).reshape(p * b, h2, w2, oc)
    enc_rep = jnp.concatenate([enc0] * p, axis=0)
    x = nn.relu(act + enc_rep)  # the hot tensor; int8 from here on
    for i, layer in enumerate(tower["head"]):
      x = _int8_conv(x, layer, (2, 2), dt)
  with jax.named_scope("cem_pool"):
    return jnp.mean(x, axis=(1, 2))


@jax.named_scope("q_head")
def _q_head_mlp(params, pooled, dtype):
  """The q-head MLP from raw params (bf16 — tiny, not quantized)."""
  q_head = params["q_head"]
  names = sorted(q_head, key=lambda n: int(n.split("_")[-1]))
  h = pooled
  for i, name in enumerate(names):
    h = _dense(q_head, name, h, dtype, relu=i < len(names) - 1)
  return h.astype(jnp.float32)


def q_head_dense_params(variables, dtype=None):
  """((w, b), ...) of the q-head MLP — the fused select kernel's
  scoring parameters, in MLP layer order."""
  q_head = variables["params"]["q_head"]
  names = sorted(q_head, key=lambda n: int(n.split("_")[-1]))
  out = []
  for name in names:
    w, b = q_head[name]["kernel"], q_head[name]["bias"]
    if dtype is not None:
      w, b = w.astype(dtype), b.astype(dtype)
    out.append((w, b))
  return tuple(out)


def quantized_score_population(network: GraspingQNetwork, tower: dict,
                               variables, encoded, extras, actions):
  """int8 twin of `GraspingQNetwork.score_population`: [B, P] Q."""
  b, p, _ = actions.shape
  pooled = _quantized_population_pooled(
      network, tower, variables, encoded, extras, actions)
  logit = _q_head_mlp(variables["params"], pooled, network.dtype)
  return logit[..., 0].reshape(p, b).T


def quantized_pool_population(network: GraspingQNetwork, tower: dict,
                              variables, encoded, extras, actions):
  """int8 twin of `GraspingQNetwork.pool_population`: [P, B, C'']."""
  b, p, _ = actions.shape
  pooled = _quantized_population_pooled(
      network, tower, variables, encoded, extras, actions)
  return pooled.reshape(p, b, -1)
