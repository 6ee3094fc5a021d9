"""FLOPs/MFU estimation for the trainers' live gauges.

Reference parity: the reference had no in-repo profiling — TPU traces
were captured with the external `capture_tpu_profile` tool and viewed
in TensorBoard (SURVEY.md §6 "Tracing/profiling"). Here the device
trace is the benchmark's (`benchmark/harness/window.py` records it,
`harness/trace_reduce.py` reduces it); this module keeps what the
running trainers need: the device's peak, XLA's FLOP count of a
compiled program, and a model-FLOPs count from shapes.

This module owns the analytic-FLOPs denominator of the live MFU
gauges (`analytic_flops`): `train_qtopt` and `train_anakin` publish
`perf.mfu` (`telemetry/perf.py`) against this one model-flops count
(docs/PERF.md). The benchmark's `step_flops_share` has its own copy
of the model (`benchmark/harness/flops.py`). The MFU *arithmetic*
itself lives in jax-free `telemetry.perf.mfu_value`; `mfu()` here
delegates to it.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from tensor2robot_tpu.telemetry import perf as perf_lib

log = logging.getLogger(__name__)

# Peak dense-matmul throughput per chip, bf16, FLOP/s. Keyed by
# substrings of jax device_kind. Sources: public TPU spec sheets
# (v5e: 197 TFLOPs bf16; v4: 275; v5p: 459; v6e/Trillium: 918).
PEAK_BF16_FLOPS = {
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 46e12,
}


def device_peak_flops(device: Optional[jax.Device] = None
                      ) -> Optional[float]:
  """bf16 peak FLOP/s of a device.

  On platform ``tpu`` the `PEAK_BF16_FLOPS` table is the only source
  and a `device_kind` it does not list raises: an MFU against a
  guessed or substituted peak is worse than none. Off-TPU there is no
  peak (None, MFU unpublished) unless ``T2R_PEAK_FLOPS_OVERRIDE``
  (env) supplies one — how the perf-plane tests exercise the live-MFU
  path on a CPU host.
  """
  device = device or jax.devices()[0]
  if device.platform == "tpu":
    kind = device.device_kind.lower()
    for key, peak in PEAK_BF16_FLOPS.items():
      if key in kind:
        return peak
    raise ValueError(
        f"No peak FLOP/s entry for TPU device_kind "
        f"{device.device_kind!r}; add it to "
        "utils.profiling.PEAK_BF16_FLOPS with its source.")
  override = os.environ.get("T2R_PEAK_FLOPS_OVERRIDE")
  return float(override) if override else None


def compiled_flops_per_call(compiled: Any) -> Optional[float]:
  """Reads XLA's FLOP estimate for one call of a compiled function.

  Takes the object returned by `jit(f).lower(*args).compile()`. XLA's
  cost analysis counts matmul/conv FLOPs exactly and elementwise ops
  approximately — good enough for MFU. Returns None when the backend
  does not expose cost analysis (some CPU builds).
  """
  try:
    analysis = compiled.cost_analysis()
  except Exception:  # noqa: BLE001 — backend-dependent surface
    return None
  if isinstance(analysis, (list, tuple)):
    analysis = analysis[0] if analysis else None
  if not analysis:
    return None
  flops = analysis.get("flops")
  return float(flops) if flops and flops > 0 else None


def mfu(steps_per_sec: float, flops_per_step: Optional[float],
        device: Optional[jax.Device] = None) -> Optional[float]:
  """Model FLOPs utilization: achieved / peak. None when unknowable.

  Delegates the arithmetic to `telemetry.perf.mfu_value` — the SAME
  code path the trainers' live ``perf.mfu`` gauges use (the ISSUE-15
  shared-path pin, `tests/test_perf_plane.py`).
  """
  return perf_lib.mfu_value(steps_per_sec, flops_per_step,
                            device_peak_flops(device))


def _same_conv_taps(h: int, k: int, s: int):
  """(out_size, valid_taps) of one spatial dim of a SAME conv.

  XLA cost analysis counts only VALID multiply-adds — border output
  positions whose window overlaps SAME padding contribute fewer taps
  (probed: a lone 8×8 stride-2 3×3 conv costs 11²/12² of the naive
  k² count). Mirroring that here keeps analytic/XLA ratios ≈ 1.
  """
  pad_total = max(k - (s if h % s == 0 else h % s), 0)
  pad_low = pad_total // 2
  out = -(-h // s)
  taps = sum(min(i * s - pad_low + k, h) - max(i * s - pad_low, 0)
             for i in range(out))
  return out, taps


def analytic_flops(kind: str, **kw):
  """The analytic-FLOPs model behind the trainers' live MFU gauges.

  MFU's denominator is MODEL flops from shapes — NOT XLA's count of
  the compiled program — so the figure stays comparable across
  dtype/remat/kernel levers: an int8 tower or a remat recompute does
  not change the model, only the schedule, and must not move the
  denominator (docs/PERF.md). XLA's cost analysis of the unlevered
  program is the cross-check: `tests/test_perf_plane.py` holds the
  ratio near 1 on a tiny model.

  kinds:
    "qtopt_step": one fused Bellman step — kw: learner, batch_size,
      optionally params (for the optimizer/Polyak elementwise tail).
      CEM target (encode once + I scored populations through the
      linearity-split head) + critic fwd/bwd (bwd = 2× fwd) + the
      elementwise optimizer/Polyak tail.
  """
  if kind != "qtopt_step":
    raise ValueError(f"unknown analytic_flops kind {kind!r}")
  learner = kw["learner"]
  batch = kw["batch_size"]
  model = learner.model
  net = model.network
  s2d = net.space_to_depth
  h = model.image_size // max(s2d, 1)
  cin = 3 * max(s2d, 1) ** 2

  def conv_flops(n, h_in, k, s, ci, co):
    out, taps = _same_conv_taps(h_in, k, s)
    return out, 2 * n * taps * taps * ci * co

  def seq_convs(n, h_in, ci, filters, first_stride):
    """Conv stack flops + BN/relu elementwise; returns (flops, h, c)."""
    total = 0.0
    for i, co in enumerate(filters):
      s = first_stride if i == 0 else 2
      h_in, f = conv_flops(n, h_in, 3, s, ci, co)
      total += f + 3 * n * h_in * h_in * co  # BN affine + relu
      ci = co
    return total, h_in, ci

  torso_first_stride = 1 if s2d > 1 else 2
  encode_n1, he, ce = seq_convs(1, h, cin, net.torso_filters,
                                torso_first_stride)

  from tensor2robot_tpu.data.abstract_input_generator import Mode
  extras_dim = sum(
      int(np.prod(spec.shape))
      for key, spec in model.get_feature_specification(
          Mode.TRAIN).to_flat_dict().items()
      if key not in ("image", "action"))
  emb_in = model.action_dim + extras_dim
  emb = net.action_embedding_size
  merge_c = net.torso_filters[-1] if net.torso_filters else 3
  embed_row = 2 * (emb_in * emb + emb * merge_c)

  qhead_dims = [net.head_filters[-1] if net.head_filters else merge_c]
  qhead_dims += list(net.dense_sizes) + [1]
  qhead_row = 2 * sum(a * b for a, b in zip(qhead_dims[:-1],
                                            qhead_dims[1:]))

  p = learner.cem_population
  iters = learner.cem_iterations
  rows = batch * p
  per_iter = rows * (embed_row + qhead_row)
  if net.head_filters:
    h2, conv0_row = conv_flops(1, he, 3, 2, ce, net.head_filters[0])
    c1 = net.head_filters[0]
    # The linearity split: per-sample action contribution is a GEMM
    # against the [C, h2·w2·C'] tap-sum tensor, then merge + tail.
    per_iter += rows * 2 * ce * h2 * h2 * c1        # act GEMM
    per_iter += rows * 2 * h2 * h2 * c1             # merge add + relu
    tail, ht, ct = seq_convs(rows, h2, c1, net.head_filters[1:], 2)
    per_iter += tail + rows * ht * ht * ct          # + mean pool
    base = (batch * encode_n1
            + batch * conv0_row                      # enc0, CSE'd
            + ce * conv0_row)                        # basis tap-sums
  else:
    per_iter += rows * he * he * ce                  # pool fallback
    base = batch * encode_n1
  cem = base + iters * per_iter

  # Critic fwd: full encode + head at batch rows; bwd = 2× fwd.
  head_f, hh, hc = ((seq_convs(1, he, ce, net.head_filters, 2))
                    if net.head_filters else (0.0, he, ce))
  critic_fwd = batch * (encode_n1 + head_f + hh * hh * hc
                        + embed_row + qhead_row)
  # Optimizer/Polyak/grad-norm elementwise tail over the param count.
  n_params = sum(
      int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
          kw["params"])) if "params" in kw else 0
  return cem + 3 * critic_fwd + 14 * n_params


def qtopt_step_flops(learner: Any, batch_size: int,
                     params: Any = None) -> Optional[float]:
  """`analytic_flops("qtopt_step", ...)` with a graceful None for
  learners whose network does not expose the GraspingQNetwork shape
  surface — the trainers' live-gauge entry point (a non-qtopt model
  publishes no MFU rather than crashing the train loop)."""
  try:
    kw: Dict[str, Any] = dict(learner=learner, batch_size=batch_size)
    if params is not None:
      kw["params"] = params
    return float(analytic_flops("qtopt_step", **kw))
  except Exception:  # noqa: BLE001 — model surface is duck-typed
    log.warning("analytic FLOPs unavailable for %r; live MFU gauges "
                "will not be published", type(learner).__name__,
                exc_info=True)
    return None


def device_memory_source() -> Callable[[], Dict[str, float]]:
  """A `telemetry.perf.ResourceSampler` source reading per-device
  memory stats where the backend provides them (`memory_stats()` —
  TPU/GPU; XLA:CPU returns None ⇒ the source yields nothing there,
  gracefully). Lives here, not in the jax-free telemetry package."""

  def sample() -> Dict[str, float]:
    out: Dict[str, float] = {}
    try:
      for index, device in enumerate(jax.local_devices()):
        stats = getattr(device, "memory_stats", None)
        stats = stats() if callable(stats) else None
        if not stats:
          continue
        in_use = stats.get("bytes_in_use")
        if in_use is not None:
          out[f"device{index}_mem_bytes"] = float(in_use)
        limit = stats.get("bytes_limit")
        if limit:
          out[f"device{index}_mem_fraction"] = (
              float(stats.get("bytes_in_use", 0.0)) / float(limit))
    except Exception:  # noqa: BLE001 — sampling must never raise
      log.debug("device memory sampling failed", exc_info=True)
    return out

  return sample
