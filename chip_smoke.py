"""Chip smoke: the flagship path, once, on whatever accelerator JAX finds.

    python chip_smoke.py                 # on the TPU machine
    python chip_smoke.py --rehearse-cpu  # same code, tiny, 4 virtual CPUs

A chip belongs to one process at a time, so this parent never touches
JAX: it runs three children one after another and reads what they
print and write.

  1. train   `run_t2r_trainer --trainer=qtopt` on the shipped
             `qtopt_int8.gin` (model, batch and CEM sizes untouched;
             only model_dir, step counts, cadences and K are bound):
             a few K-step dispatches, int8 calibration, one checkpoint.
  2. resume  the same command line for one more dispatch — every
             program must come out of the persistent compile cache.
  3. serve + kernels (one process)  a `CEMPolicyServer` on the restored
             checkpoint answering batch-1 and batch-8 requests with no
             compile after warmup, then three kernel families compiled
             (never interpreted) at their production shapes, each against
             its reference: flash attention at its sizes, `cem_select`,
             the delta rule's pair.

Every child prints the device it ran on; anything but the expected
platform fails the run. The last line of stdout is the verdict,
`{"ok": true, "device": {...}}`, printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

# JAX-free imports (the package's config and record readers); a
# directory that holds this file without the package fails right here.
from tensor2robot_tpu import config as gin
from tensor2robot_tpu.telemetry.records import read_records

ROOT = os.path.dirname(os.path.abspath(__file__))
GIN_CONFIG = "tensor2robot_tpu/research/qtopt/configs/qtopt_int8.gin"
BUDGET_SECS = 1100  # the contract allows 1200, compilation included
_MARK = "SMOKE_JSON "

# The kernels' hardware bars: sized to the MXU's f32-emulation
# epsilon, not to CPU float32; a lowering bug is orders above them.
KERNEL_BAR = 5e-2
FLASH_BARS = {"out": 2e-2, "lse": KERNEL_BAR, "dq": KERNEL_BAR,
              "dk": KERNEL_BAR, "dv": KERNEL_BAR}


class Sizes:
  """What differs between the chip run and its CPU rehearsal."""

  def __init__(self, rehearse: bool):
    self.rehearse = rehearse
    self.platform = "cpu" if rehearse else "tpu"
    self.interpret = rehearse  # Pallas: compiled on the chip, always
    if rehearse:
      self.k, self.dispatches, self.batch = 2, 2, 16
      self.model_bindings = [
          "train_qtopt.batch_size=16",
          "GraspingQModel.image_size=16",
          "GraspingQModel.torso_filters=(8,)",
          "GraspingQModel.head_filters=(16, 16)",
          "GraspingQModel.dense_sizes=(16,)",
          "QTOptLearner.cem_population=8",
          "QTOptLearner.cem_elites=2",
      ]
      # (b, t, heads, d, dtype, chunk[, kv heads, window]) per flash
      # shape; select dims.
      self.flash = [(1, 64, 2, 16, "float32", 32),
                    (2, 32, 2, 16, "bfloat16", 32),
                    (1, 64, 6, 16, "float32", 32, 2, 24)]
      self.select = dict(p=16, b=8, c=16, a=4, e=3, hidden=16)
      # (b, t, heads, d, dtype, chunk) of the delta rule's kernels.
      self.delta_rule = (1, 64, 3, 8, "float32", 16)
    else:
      self.k, self.dispatches, self.batch = 25, 4, 256
      self.model_bindings = []
      self.flash = [(2, 1024, 2, 64, "float32", 1024),    # one tile
                    (16, 32, 4, 32, "bfloat16", 32),       # BC episode
                    (1, 32768, 4, 64, "bfloat16", 1024),   # long context
                    # A row of the windowed-attention cell (PERF.md
                    # section 4): a sliding layer's band and groups,
                    # a full layer's groups.
                    (1, 8192, 64, 128, "bfloat16", 256, 8, 512),
                    (1, 8192, 48, 128, "bfloat16", 256, 8, None)]
      self.select = dict(p=64, b=256, c=64, a=4, e=6, hidden=64)
      # A row of the Qwen3-Next cell (PERF.md section 4).
      self.delta_rule = (1, 8192, 32, 128, "bfloat16", 64)


def _emit(record: str, **payload) -> None:
  print(_MARK + json.dumps({"record": record, **payload}), flush=True)


def _report_device(expected_platform: str) -> None:
  """Prints this process's device; raises unless it is the expected one."""
  import jax
  devices = jax.devices()
  device = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
  _emit("device", **device)
  if device["platform"] != expected_platform:
    raise RuntimeError(
        f"expected platform {expected_platform!r}, JAX found {device}")


# ---------------------------------------------------------------------
# Trainer side: loaded by the train/resume children through
# `--import_modules=chip_smoke` and bound as `train_qtopt.hooks`.
# ---------------------------------------------------------------------


@gin.configurable
class PlacementCheckHook:
  """What only the trainer process can see: its device, and that the
  batch and the ZeRO-sharded optimizer moments live on EVERY device."""

  drives_online_collection = False

  def __init__(self, platform: str = "tpu", batch_size: int = 256):
    self._platform = platform
    self._batch_size = batch_size
    self._batch_checked = False

  def begin(self, model, model_dir):
    del model, model_dir
    from tensor2robot_tpu.utils.native import native_available
    _report_device(self._platform)
    _emit("native", available=native_available())

  def after_step(self, step, metrics):
    del step, metrics
    if self._batch_checked:
      return
    self._batch_checked = True
    import jax
    import numpy as np
    # The prefetcher keeps the NEXT stacked batches resident: uint8
    # [K, B, H, W, 3] image leaves, batch dim split over the mesh.
    batches = [a for a in jax.live_arrays()
               if a.dtype == np.uint8 and a.ndim == 5
               and a.shape[1] == self._batch_size]
    if not batches:
      raise RuntimeError("no prefetched image batch is live on device")
    everyone = set(jax.devices())
    for array in batches:
      holders = {s.device for s in array.addressable_shards}
      rows = {s.data.shape[1] for s in array.addressable_shards}
      if holders != everyone or rows != {
          self._batch_size // len(everyone)}:
        raise RuntimeError(
            f"batch {array.shape} is on {len(holders)} of "
            f"{len(everyone)} devices with per-shard rows {rows}")
    _emit("batch_placement", arrays=len(batches),
          devices=len(everyone),
          rows_per_device=self._batch_size // len(everyone))

  def after_checkpoint(self, step, state, model_dir):
    del model_dir
    import jax
    everyone = set(jax.devices())
    split = 0
    moments = [leaf for leaf in jax.tree_util.tree_leaves(state.opt_state)
               if isinstance(leaf, jax.Array)]
    for leaf in moments:
      holders = {s.device for s in leaf.addressable_shards}
      if holders != everyone:
        raise RuntimeError(
            f"optimizer leaf {leaf.shape} is on {len(holders)} of "
            f"{len(everyone)} devices")
      if not leaf.sharding.is_fully_replicated:
        split += 1
        shard_sizes = {s.data.size for s in leaf.addressable_shards}
        if shard_sizes != {leaf.size // len(everyone)}:
          raise RuntimeError(
              f"optimizer leaf {leaf.shape} shards unevenly: "
              f"{shard_sizes}")
    if len(everyone) > 1 and not split:
      raise RuntimeError("shard_weight_update left every optimizer "
                         "moment replicated")
    _emit("optimizer_placement", step=step, leaves=len(moments),
          split_leaves=split, devices=len(everyone),
          bytes_in_use=[(d.memory_stats() or {}).get("bytes_in_use")
                        for d in jax.devices()])

  def end(self, step, state, model_dir):
    del step, state, model_dir


# ---------------------------------------------------------------------
# Child phases that drive the library directly (one process).
# ---------------------------------------------------------------------


def _build_learner(sizes: Sizes):
  """The learner exactly as the trainer children configured it."""
  import importlib
  from tensor2robot_tpu.bin import run_t2r_trainer
  for module in run_t2r_trainer._DEFAULT_MODULES:
    importlib.import_module(module)
  gin.parse_config_files_and_bindings(
      [os.path.join(ROOT, GIN_CONFIG)], sizes.model_bindings)
  from tensor2robot_tpu.research.qtopt.qtopt_learner import QTOptLearner
  return QTOptLearner()


def phase_serve(sizes: Sizes, model_dir: str) -> None:
  """CEMPolicyServer on the checkpoint the trainer wrote."""
  import jax
  import numpy as np
  from tensor2robot_tpu.serving import engine as engine_lib
  from tensor2robot_tpu.serving.cem_policy import CEMPolicyServer
  from tensor2robot_tpu.specs import make_random_tensors
  from tensor2robot_tpu.utils import checkpoints as ckpt_lib

  learner = _build_learner(sizes)
  init = learner.create_state(jax.random.PRNGKey(0),
                              batch_size=2).train_state
  restored = ckpt_lib.restore_variables(
      model_dir, {"params": init.params,
                  "batch_stats": init.batch_stats})
  moved = max(
      float(np.max(np.abs(np.asarray(a, np.float32)
                          - np.asarray(b, np.float32))))
      for a, b in zip(jax.tree_util.tree_leaves(init.params),
                      jax.tree_util.tree_leaves(restored["params"])))
  if not moved > 0:
    raise RuntimeError("restored params equal the seed-0 init: the "
                       "checkpoint holds no training")
  # The acting form the fleet host and the checkpoint hooks hand over.
  acting = init.replace(params=restored["params"],
                        batch_stats=restored["batch_stats"],
                        opt_state=None)
  action_dim = learner.model.action_dim
  policy = jax.jit(learner.build_policy())

  with CEMPolicyServer(learner, acting, max_batch=8) as server:
    compiles_after_warmup = engine_lib.compile_count()
    checked = []
    for request, batch in enumerate((1, 8, 1, 8, 8, 1)):
      observations = make_random_tensors(
          learner.observation_specification(), batch_size=batch,
          seed=100 + request)
      actions = server.select_actions(observations)
      if (actions.shape != (batch, action_dim)
          or not np.all(np.isfinite(actions))
          or np.max(np.abs(actions)) > 1.0 + 1e-6):
        raise RuntimeError(
            f"request {request}: bad actions {actions.shape}, "
            f"range [{actions.min()}, {actions.max()}]")
      checked.append(batch)
    # Reference on a small input: the engine's bucket program against
    # the same policy under a plain jit, same params, same key.
    observations = make_random_tensors(
        learner.observation_specification(), batch_size=8, seed=7)
    key = jax.random.PRNGKey(11)
    served = server.select_actions_direct(observations, key)
    direct = np.asarray(policy(acting, observations, key))
    policy_err = float(np.max(np.abs(served - direct)))
    if not policy_err <= 1e-5:
      raise RuntimeError(f"served actions differ from the jitted "
                         f"policy by {policy_err}")
    compiled = engine_lib.compile_count() - compiles_after_warmup
    if compiled:
      raise RuntimeError(f"{compiled} engine compile(s) after warmup")
    _emit("serve", requests=checked, buckets=server.engine.bucket_sizes,
          warmup_seconds=round(server.warmup_seconds, 2),
          compiles_after_warmup=compiled,
          served_vs_jit_max_err=policy_err, params_moved_by=moved)


def _max_err(got, want) -> float:
  """max|got − want| on the scale of the reference (absolute below 1)."""
  import numpy as np
  got = np.asarray(got, np.float32)
  want = np.asarray(want, np.float32)
  if not np.all(np.isfinite(got)):
    return math.inf
  return float(np.max(np.abs(got - want))
               / max(1.0, float(np.max(np.abs(want)))))


def _attention_reference(q, k, v, chunk: int, window=None):
  """Causal attention in plain f32 jnp (under a band where `window`:
  a row sees itself and the `window - 1` before it; key-value heads
  repeated to their groups of query heads), materializing one
  [chunk, T] score slab per step so T=32k fits; returns (out,
  lse [B, H, T])."""
  import jax
  import jax.numpy as jnp
  b, t, h, d = q.shape
  q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
  k, v = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (k, v))
  hi = jax.lax.Precision.HIGHEST

  @jax.checkpoint
  def rows(args):
    q_rows, row0 = args                                   # [B, c, H, D]
    s = jnp.einsum("bthd,bshd->bhts", q_rows, k,
                   precision=hi) / math.sqrt(d)
    behind = (row0 + jnp.arange(chunk))[:, None] - jnp.arange(t)[None]
    visible = (behind >= 0) & (behind < (window or t))
    s = jnp.where(visible[None, None], s, -1e30)
    out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v,
                     precision=hi)
    return out, jax.scipy.special.logsumexp(s, axis=-1)   # [B, H, c]

  q_chunks = q.reshape(b, t // chunk, chunk, h, d).swapaxes(0, 1)
  out, lse = jax.lax.map(
      rows, (q_chunks, jnp.arange(t // chunk) * chunk))
  return (out.swapaxes(0, 1).reshape(b, t, h, d),
          lse.transpose(1, 2, 0, 3).reshape(b, h, t))


def _check_flash(sizes: Sizes, rng) -> None:
  import jax
  import jax.numpy as jnp
  from tensor2robot_tpu.ops.flash_attention import (
      flash_attention_with_lse,
  )
  for b, t, h, d, dtype, chunk, *grouped in sizes.flash:
    kv, window = grouped or (h, None)
    q, k, v, do = (jnp.asarray(rng.standard_normal((b, t, n, d)), dtype)
                   for n in (h, kv, kv, h))
    dlse = jnp.asarray(rng.standard_normal((b, h, t)) * 0.1,
                       jnp.float32)

    def scalar(attend, q, k, v):
      out, lse = attend(q, k, v)
      return (jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32))
              + jnp.sum(lse * dlse))

    flash = lambda q, k, v: flash_attention_with_lse(  # noqa: E731
        q, k, v, causal=True, interpret=sizes.interpret, window=window)
    reference = lambda q, k, v: _attention_reference(  # noqa: E731
        q, k, v, chunk, window)
    got = flash(q, k, v) + jax.jit(jax.grad(
        lambda *x: scalar(flash, *x), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(reference)(q, k, v) + jax.jit(jax.grad(
        lambda *x: scalar(reference, *x), argnums=(0, 1, 2)))(q, k, v)
    errs = {name: _max_err(g, w)
            for name, g, w in zip(FLASH_BARS, got, want)}
    _emit("kernel", name="flash_attention fwd+bwd",
          shape=dict(b=b, t=t, heads=h, kv_heads=kv, window=window, d=d,
                     dtype=dtype), errs=errs)
    bad = {n: e for n, e in errs.items() if not e < FLASH_BARS[n]}
    if bad:
      raise RuntimeError(f"flash T={t} {dtype}: over the bar: {bad}")


def _check_cem_select(sizes: Sizes, rng) -> None:
  import jax.numpy as jnp
  from tensor2robot_tpu.ops import cem_select_lax, fused_cem_select
  s = sizes.select
  f = lambda *shape: jnp.asarray(  # noqa: E731
      rng.standard_normal(shape) * 0.3, jnp.bfloat16)
  pooled = f(s["p"], s["b"], s["c"])
  samples = jnp.asarray(
      rng.standard_normal((s["b"], s["p"], s["a"])), jnp.float32)
  dense = ((f(s["c"], s["hidden"]), f(s["hidden"])),
           (f(s["hidden"], s["hidden"]), f(s["hidden"])),
           (f(s["hidden"], 1), f(1)))
  want = cem_select_lax(pooled, samples, dense, num_elites=s["e"],
                        sigmoid=True)
  got = fused_cem_select(pooled, samples, dense, num_elites=s["e"],
                         sigmoid=True, interpret=sizes.interpret)
  errs = {name: _max_err(g, w) for name, g, w in zip(
      ("mean", "std", "best_action", "best_score"), got, want)}
  _emit("kernel", name="fused_cem_select", shape=s, errs=errs)
  if not max(errs.values()) < KERNEL_BAR:
    raise RuntimeError(f"fused_cem_select over the bar: {errs}")


def _check_delta_rule(sizes: Sizes, rng) -> None:
  """The gated delta rule's three programs against what they replace:
  the walk's kernel pair (`ops/delta_rule_walk.py`) against
  `scan_walk`, forward and the five cotangents, with `end_decay` a
  head's scalar (the Qwen3-Next cell's) and a vector over the key
  channels (the Kimi-Linear cell's: the state's rows scaled), and the
  fused forward program (`ops/delta_rule_fused.py`) against the
  prepared rule."""
  import jax
  import jax.numpy as jnp
  from tensor2robot_tpu.layers import gated_delta
  from tensor2robot_tpu.ops import delta_rule_fused, delta_rule_walk
  b, t, h, d, dtype, chunk = sizes.delta_rule
  n = t // chunk
  shape = dict(b=b, t=t, heads=h, d=d, dtype=dtype, chunk=chunk)
  normal = lambda *s: jnp.asarray(  # noqa: E731
      rng.standard_normal(s), jnp.float32)

  # Keys a tenth of unit length: the walk's state stays bounded over
  # 128 chunks of operands that no preparation has matched.
  keys = [(0.1 * gated_delta.l2_normalize(normal(n, b, h, chunk, d))
           ).astype(dtype) for _ in range(3)]
  probes = [normal(n, b, h, chunk, d) for _ in range(2)]
  kernels = lambda *x: delta_rule_walk.walk(  # noqa: E731
      *x, interpret=sizes.interpret)
  names = ("new", "carried", "d_writes", "d_k_decayed", "d_q_decayed",
           "d_k_to_end", "d_end_decay")
  for end_decay, channels in (("scalar", ()), ("vector", (d,))):
    operands = (normal(n, b, h, chunk, d), *keys, jnp.asarray(
        rng.uniform(0.5, 0.95, (n, b, h) + channels), jnp.float32))

    def both(walk):
      scalar = lambda *x: sum(  # noqa: E731
          jnp.sum(out * probe) for out, probe in zip(walk(*x), probes))
      return jax.jit(walk)(*operands) + jax.jit(jax.grad(
          scalar, argnums=(0, 1, 2, 3, 4)))(*operands)

    errs = {name: _max_err(got, want) for name, got, want in zip(
        names, both(kernels), both(gated_delta.scan_walk))}
    _emit("kernel", name="delta_rule_walk fwd+bwd",
          shape=dict(shape, end_decay=end_decay), errs=errs)
    if not max(errs.values()) < KERNEL_BAR:
      raise RuntimeError(
          f"delta_rule_walk ({end_decay} end_decay) over the bar: {errs}")

  q = gated_delta.l2_normalize(normal(b, t, h, d)) * d ** -0.5
  k = gated_delta.l2_normalize(normal(b, t, h, d))
  v = normal(b, t, h, d).astype(dtype)
  g = -jnp.asarray(rng.uniform(0.001, 0.3, (b, t, h)), jnp.float32)
  beta = jax.nn.sigmoid(normal(b, t, h))
  got = jax.jit(lambda *x: delta_rule_fused.forward(
      *x, chunk=chunk, dtype=jnp.dtype(dtype),
      interpret=sizes.interpret))(q, k, v, g, beta)
  want = jax.jit(lambda *x: gated_delta._prepared_rule(
      *x, chunk, jnp.dtype(dtype), sizes.interpret))(q, k, v, g, beta)
  errs = {"out": _max_err(got, want)}
  _emit("kernel", name="delta_rule_fused fwd", shape=shape, errs=errs)
  if not errs["out"] < KERNEL_BAR:
    raise RuntimeError(f"delta_rule_fused over the bar: {errs}")


def phase_kernels(sizes: Sizes, model_dir: str) -> None:
  """No default config runs a Pallas kernel, so the trainer path
  cannot find a Mosaic refusal; this compiles and runs each once."""
  del model_dir
  import numpy as np
  rng = np.random.default_rng(0)
  _check_cem_select(sizes, rng)
  _check_flash(sizes, rng)
  _check_delta_rule(sizes, rng)


PHASES = {"serve": phase_serve, "kernels": phase_kernels}


def run_child_phases(names, sizes: Sizes, model_dir: str) -> None:
  _report_device(sizes.platform)
  for name in names:
    t0 = time.time()
    PHASES[name](sizes, model_dir)
    _emit("phase_done", phase=name, seconds=round(time.time() - t0, 1))


# ---------------------------------------------------------------------
# The parent: stays off JAX.
# ---------------------------------------------------------------------


class SmokeFailure(Exception):
  pass


def _child_env(sizes: Sizes) -> dict:
  env = dict(os.environ)
  env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
  if sizes.rehearse:
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    # perf.mfu needs a peak; off-TPU only this CPU-test device has one.
    env["T2R_PEAK_FLOPS_OVERRIDE"] = "1e12"
  else:
    # Named explicitly, JAX raises at start-up when the TPU cannot be
    # had — it never falls back to the CPU behind the run's back.
    env["JAX_PLATFORMS"] = "tpu,cpu"
  return env


def _run_child(label: str, argv, sizes: Sizes, deadline: float) -> list:
  """Runs one child to its end, echoing its stdout; returns the
  records it emitted. The child gets its own process group so that a
  timeout takes everything it started down with it."""
  remaining = deadline - time.time()
  if remaining <= 0:
    raise SmokeFailure(f"{label}: no time left in the budget")
  print(f"--- {label}: {' '.join(argv)}", flush=True)
  t0 = time.time()
  records = []
  child = subprocess.Popen(
      argv, cwd=ROOT, env=_child_env(sizes), stdout=subprocess.PIPE,
      text=True, start_new_session=True)
  timer = threading.Timer(
      remaining, lambda: os.killpg(child.pid, signal.SIGKILL))
  timer.start()
  try:
    for line in child.stdout:
      print(f"[{label}] {line}", end="", flush=True)
      if line.startswith(_MARK):
        records.append(json.loads(line[len(_MARK):]))
    code = child.wait()
  finally:
    timer.cancel()
    if child.poll() is None:
      os.killpg(child.pid, signal.SIGKILL)
      child.wait()
  print(f"--- {label}: exit {code} after {time.time() - t0:.1f}s",
        flush=True)
  if code != 0:
    raise SmokeFailure(f"{label}: exit code {code}")
  return records


def _trainer_argv(sizes: Sizes, model_dir: str, max_steps: int) -> list:
  bindings = [
      f"train_qtopt.model_dir='{model_dir}'",
      f"train_qtopt.max_train_steps={max_steps}",
      f"train_qtopt.steps_per_dispatch={sizes.k}",
      f"train_qtopt.log_every_steps={sizes.k}",
      f"train_qtopt.save_checkpoints_steps={max_steps}",
      "train_qtopt.hooks=[@PlacementCheckHook()]",
      f"PlacementCheckHook.platform='{sizes.platform}'",
      f"PlacementCheckHook.batch_size={sizes.batch}",
  ] + sizes.model_bindings
  argv = [sys.executable, "-m", "tensor2robot_tpu.bin.run_t2r_trainer",
          "--trainer=qtopt", "--gin_configs", GIN_CONFIG,
          "--import_modules=chip_smoke"]
  for binding in bindings:
    argv += ["--gin_bindings", binding]
  return argv


def _one(records: list, record: str, label: str) -> dict:
  found = [r for r in records if r["record"] == record]
  if not found:
    raise SmokeFailure(f"{label}: printed no {record!r} record")
  return found[-1]


def _check_train_records(label, rows, first_step, last_step, k):
  steps = [int(r["step"]) for r in rows]
  want = list(range(first_step, last_step + 1, k))
  if steps != want:
    raise SmokeFailure(f"{label}: logged steps {steps}, wanted {want}")
  for row in rows:
    if not math.isfinite(row["loss"]):
      raise SmokeFailure(f"{label}: loss {row['loss']} at {row['step']}")
    if not row.get("perf.mfu", 0) > 0:
      raise SmokeFailure(
          f"{label}: no perf.mfu in the step-{row['step']} record")


def run_smoke(sizes: Sizes, model_dir: str) -> dict:
  deadline = time.time() + BUDGET_SECS
  devices = []
  steps = sizes.k * sizes.dispatches
  metrics_path = os.path.join(model_dir, "metrics_train.jsonl")

  records = _run_child(
      "train", _trainer_argv(sizes, model_dir, steps), sizes, deadline)
  devices.append(_one(records, "device", "train"))
  _one(records, "batch_placement", "train")
  _one(records, "optimizer_placement", "train")
  rows = read_records(metrics_path)
  _check_train_records("train", rows, sizes.k, steps, sizes.k)
  if not os.path.isdir(os.path.join(model_dir, "ckpt", str(steps),
                                    "state")):
    raise SmokeFailure(f"train: no checkpoint for step {steps}")
  # The first interval holds the compile; the last is steady state.
  first, last = rows[0], rows[-1]
  print(f"train: first dispatch {sizes.k / first['grad_steps_per_sec']:.1f}s"
        f" (compile included), last {sizes.k / last['grad_steps_per_sec']:.3f}s;"
        f" loss {last['loss']:.4f}, perf.mfu {last['perf.mfu']:.4f},"
        f" compile_cache hits/misses {last['compile_cache.hits']:.0f}/"
        f"{last['compile_cache.misses']:.0f}", flush=True)

  records = _run_child(
      "resume", _trainer_argv(sizes, model_dir, steps + sizes.k), sizes,
      deadline)
  devices.append(_one(records, "device", "resume"))
  resumed = read_records(metrics_path)[len(rows):]
  _check_train_records("resume", resumed, steps + sizes.k,
                       steps + sizes.k, sizes.k)
  hits = resumed[-1]["compile_cache.hits"]
  misses = resumed[-1]["compile_cache.misses"]
  print(f"resume: first dispatch "
        f"{sizes.k / resumed[-1]['grad_steps_per_sec']:.1f}s, "
        f"compile_cache hits/misses {hits:.0f}/{misses:.0f}", flush=True)
  if not hits > 0 or misses:
    raise SmokeFailure(
        f"resume: compile_cache hits={hits} misses={misses}; every "
        "program of a resume must come from the persistent cache")

  records = _run_child(
      "serve+kernels",
      [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
       "--child=serve,kernels", f"--model-dir={model_dir}"]
      + (["--rehearse-cpu"] if sizes.rehearse else []), sizes, deadline)
  devices.append(_one(records, "device", "serve+kernels"))
  _one(records, "serve", "serve+kernels")
  kernels = [r["name"] for r in records if r["record"] == "kernel"]
  if len(kernels) != 3 + len(sizes.flash):
    raise SmokeFailure(f"serve+kernels: kernels checked: {kernels}")

  devices = [{key: d[key] for key in ("platform", "kind", "count")}
             for d in devices]
  if any(d != devices[0] for d in devices):
    raise SmokeFailure(f"children disagree on the device: {devices}")
  return devices[0]


def main(argv=None) -> int:
  parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
  parser.add_argument(
      "--rehearse-cpu", action="store_true",
      help="run the same code at tiny size on 4 virtual CPU devices "
           "(Pallas interpreted); without it a run that finds no TPU "
           "fails")
  parser.add_argument("--model-dir", default=None,
                      help="keep the run here instead of a temp dir")
  parser.add_argument("--child", default=None, help=argparse.SUPPRESS)
  args = parser.parse_args(argv)
  sizes = Sizes(args.rehearse_cpu)

  if args.child:
    run_child_phases(args.child.split(","), sizes, args.model_dir)
    return 0

  model_dir = args.model_dir or tempfile.mkdtemp(prefix="chip_smoke_")
  try:
    device = run_smoke(sizes, model_dir)
  except SmokeFailure as failure:
    print(f"chip_smoke FAILED: {failure}", file=sys.stderr, flush=True)
    return 1
  finally:
    if not args.model_dir:
      shutil.rmtree(model_dir, ignore_errors=True)
  print(json.dumps({"ok": True, "device": device}), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
