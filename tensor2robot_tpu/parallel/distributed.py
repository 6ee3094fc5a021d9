"""Multi-host (multi-process) JAX runtime initialization.

Reference parity: the reference scaled across hosts with TPUEstimator's
cluster config (SURVEY.md §3 parallelism table "multi-slice via jax
distributed init" [U]); the JAX-native equivalent is
`jax.distributed.initialize`, after which `jax.devices()` spans every
host's chips and one `Mesh` + GSPMD program covers the whole slice —
collectives ride ICI within a slice and DCN across slices.

Call `maybe_initialize_distributed()` ONCE at binary startup, before
any jax device use. On TPU pods the runtime discovers coordinator /
process_id / process_count from the TPU metadata, so an argless
initialize is correct; off-pod multi-process runs (CPU/GPU fleets,
tests) pass the coordination triple explicitly. Single-process runs
no-op, so the same binary works from a laptop to a v5e-64.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

log = logging.getLogger(__name__)

_INITIALIZED = False


def ephemeral_coordinator_address(host: str = "127.0.0.1") -> str:
  """Picks a collision-safe coordinator address for same-host launches.

  The launch contract for same-host multi-process runs (fleets, the
  two-process distributed test, CPU rehearsals): the COORDINATOR —
  the one process that spawns the others — calls this ONCE before
  spawning and hands the result to every child via
  `JAX_COORDINATOR_ADDRESS` (or the explicit flag). The OS assigns a
  port from the ephemeral range (`bind(0)`), so two concurrent fleets
  (or two test runs) on one machine never race on a fixed port the
  way a hard-coded constant guarantees they eventually would.

  The port is released before jax binds it, so a theoretical window
  exists; ephemeral-range assignment makes a collision in that window
  vanishingly unlikely (the kernel cycles the range rather than
  re-issuing the port it just handed out), which is the practical
  difference vs. a fixed port's CERTAIN collision under concurrency.
  """
  import socket

  with socket.socket() as s:
    s.bind((host, 0))
    return f"{host}:{s.getsockname()[1]}"


def maybe_initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    force: bool = False,
) -> bool:
  """Initializes jax.distributed when a multi-process launch is detected.

  Triggers when any of:
    * explicit args (coordinator_address or force=True),
    * `JAX_COORDINATOR_ADDRESS` env (+`JAX_NUM_PROCESSES`/
      `JAX_PROCESS_ID`) — the framework's own launch contract,
    * a TPU pod environment (`TPU_WORKER_HOSTNAMES` with >1 worker),
      where the argless auto-discovery path is used.

  Idempotent; returns True when jax.distributed is (now) initialized.
  """
  global _INITIALIZED
  if _INITIALIZED:
    return True

  coordinator_address = coordinator_address or os.environ.get(
      "JAX_COORDINATOR_ADDRESS")
  if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
    num_processes = int(os.environ["JAX_NUM_PROCESSES"])
  if process_id is None and "JAX_PROCESS_ID" in os.environ:
    process_id = int(os.environ["JAX_PROCESS_ID"])

  pod_workers = [w for w in os.environ.get(
      "TPU_WORKER_HOSTNAMES", "").split(",") if w]
  on_pod = len(pod_workers) > 1

  if not (coordinator_address or on_pod or force):
    return False

  import jax

  _maybe_enable_cpu_collectives()
  kwargs = {}
  if coordinator_address:
    kwargs["coordinator_address"] = coordinator_address
  if num_processes is not None:
    kwargs["num_processes"] = num_processes
  if process_id is not None:
    kwargs["process_id"] = process_id
  jax.distributed.initialize(**kwargs)
  _INITIALIZED = True
  log.info(
      "jax.distributed initialized: process %d/%d, %d local / %d global "
      "devices.", jax.process_index(), jax.process_count(),
      jax.local_device_count(), jax.device_count())
  return True


def _maybe_enable_cpu_collectives() -> None:
  """Selects the gloo CPU collectives backend for multi-process CPU.

  XLA:CPU's default collectives cannot span processes at all
  ("Multiprocess computations aren't implemented on the CPU backend")
  — every off-accelerator multi-process run (CI, the two-process
  distributed test, a laptop fleet rehearsal) needs jax's gloo-based
  cross-process CPU collectives, selected via
  `jax_cpu_collectives_implementation` BEFORE
  `jax.distributed.initialize`. The option only governs the CPU
  backend's cross-process collectives, so it is selected whenever the
  CPU backend could end up primary: platforms unset (auto-detect on a
  CPU-only host) or explicitly naming cpu. Only an explicit
  accelerator-only selection (e.g. `JAX_PLATFORMS=tpu`) skips it.
  """
  import jax

  platforms = (os.environ.get("JAX_PLATFORMS", "")
               or str(getattr(jax.config, "jax_platforms", None) or ""))
  if platforms and "cpu" not in platforms.lower():
    return  # accelerator-only selection: CPU backend never primary
  jax.config.update("jax_cpu_collectives_implementation", "gloo")
