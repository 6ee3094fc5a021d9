"""Shared helpers for fleet child processes (host / actors / learner).

Every fleet child runs this module's `scrub_inherited_distributed_env`
FIRST: a fleet is often launched from a process that itself sits
inside a multi-host training context (`JAX_COORDINATOR_ADDRESS` and
friends in the environment), and `multiprocessing`'s spawn children
inherit the parent's environ wholesale. A fleet child that kept those
variables would call `jax.distributed.initialize` against a
coordinator it is not part of and block forever waiting for peers —
the exact class of same-host collision the collision-safe coordinator
contract exists to prevent (see
`parallel.distributed.ephemeral_coordinator_address`). Children that
DO want a distributed runtime (the learner with
`FleetConfig.distributed_learner=True`) get a fresh ephemeral
coordinator address handed to them explicitly by the orchestrator.

Kept jax-free at import so actor processes can import it without
paying the XLA runtime (pinned by tests/test_fleet.py); only
`claim_device`, which the JAX-using children call, imports jax.
"""

from __future__ import annotations

import os
import sys
import threading
import time

# The launch-contract variables `maybe_initialize_distributed` reads.
_DISTRIBUTED_ENV_VARS = (
    "JAX_COORDINATOR_ADDRESS",
    "JAX_NUM_PROCESSES",
    "JAX_PROCESS_ID",
)


def scrub_inherited_distributed_env() -> None:
  """Drops inherited multi-host launch variables from this process."""
  for var in _DISTRIBUTED_ENV_VARS:
    os.environ.pop(var, None)


def pin_single_host_device() -> None:
  """Forces ONE host-platform device in this process's XLA runtime.

  Learner-group ranks (ISSUE 19, `learner_hosts > 1`) must present a
  symmetric single-device topology to gloo: the CPU backend's
  cross-process collectives desync when each rank carries a forced
  multi-device host platform (a parent that set
  `--xla_force_host_platform_device_count=8` — the test suite does —
  hands every spawned rank 8 fake devices, and the group's first
  collective tears with a gloo preamble-size mismatch). Strip any
  inherited count and pin 1; the flag only affects the host platform,
  so this is a no-op on real accelerators. Must run before the
  process's first jax import.
  """
  flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
           if not f.startswith("--xla_force_host_platform_device_count")]
  flags.append("--xla_force_host_platform_device_count=1")
  os.environ["XLA_FLAGS"] = " ".join(flags)


def adopt_coordinator(address: str, num_processes: int = 1,
                      process_id: int = 0) -> None:
  """Installs an orchestrator-issued coordinator triple into env.

  The orchestrator (not the child) picked `address` with
  `ephemeral_coordinator_address()`, so two fleets on one machine can
  never race on a fixed port; the child just adopts it before its
  first jax import.
  """
  os.environ["JAX_COORDINATOR_ADDRESS"] = address
  os.environ["JAX_NUM_PROCESSES"] = str(num_processes)
  os.environ["JAX_PROCESS_ID"] = str(process_id)


def claim_device(role: str, timeout_secs: float = 60.0) -> None:
  """Initialises this child's JAX backend, or ends the process saying
  why it could not.

  A chip belongs to one process at a time, and every fleet host,
  learner, front and pod is a JAX process: on a one-chip machine only
  the first of them gets the chip, and the rest either raise inside
  backend initialisation or block there. Either way the child exits
  at once with the reason, instead of sitting silent until the
  orchestrator's heartbeat timer (300 s) calls it hung. The fleet is
  not brought up on a chip machine yet; it runs with
  `JAX_PLATFORMS=cpu`.
  """
  outcome = {}

  def initialise():
    try:
      import jax
      outcome["devices"] = jax.devices()
    except Exception as e:  # noqa: BLE001 — reported below, then fatal
      outcome["error"] = e

  thread = threading.Thread(target=initialise, daemon=True,
                            name="claim-device")
  thread.start()
  thread.join(timeout_secs)
  if "devices" in outcome:
    return
  reason = (repr(outcome["error"]) if "error" in outcome else
            f"backend initialisation still blocked after "
            f"{timeout_secs:.0f} s")
  message = (
      f"fleet {role}: no JAX device for this process ({reason}). A chip "
      "belongs to one process at a time and every fleet host, learner, "
      "front and pod is a JAX process; the fleet is not brought up on "
      "a chip machine yet — run it with JAX_PLATFORMS=cpu.")
  if "error" in outcome:
    raise SystemExit(message)
  # Blocked inside the runtime: no exception will ever unwind this
  # process, and interpreter shutdown would wait on the same lock.
  sys.stderr.write(message + "\n")
  sys.stderr.flush()
  os._exit(1)


def beat(heartbeat) -> None:
  """Stamps a shared heartbeat slot with the current monotonic time.

  `heartbeat` is a `multiprocessing.Value('d')`; CLOCK_MONOTONIC is
  system-wide on Linux, so the orchestrator compares stamps from any
  process against its own clock.
  """
  if heartbeat is not None:
    heartbeat.value = time.monotonic()


def hang(duration_secs: float) -> None:
  """Deterministic hang injection: sleep WITHOUT beating.

  The `actor_hang` fault class (`fleet/faults.py`): the process stays
  alive but its heartbeat goes stale, which is exactly what a wedged
  env binding or a deadlocked native call looks like from the
  orchestrator — detected by the heartbeat timer, recovered by
  kill-and-respawn under the restart policy. A real hang would not
  check a stop event either, so this one doesn't.
  """
  time.sleep(duration_secs)
