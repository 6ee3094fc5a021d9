"""Multi-process jax.distributed: the initialize path EXECUTES.

Round-3 verdict #28: `maybe_initialize_distributed`'s real path had
never run anywhere — only the single-process no-op was tested. Here
two OS processes (2 virtual CPU devices each) form a 4-device cluster
through the framework's env launch contract, run a cross-process psum
and one sharded QT-Opt train step, and must agree on the loss. This is
the same code path a v5e pod binary takes, with DCN standing in for
the loopback coordinator.
"""

import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

import pytest

from tensor2robot_tpu.parallel.distributed import (
    ephemeral_coordinator_address,
)


@pytest.mark.slow
def test_two_process_cluster_runs_sharded_train_step(tmp_path):
  repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  worker = os.path.join(repo, "tests", "distributed_worker.py")
  # The coordinator-side port pick the fleet orchestrator uses too:
  # two runs on one machine must never race on a fixed port.
  coordinator = ephemeral_coordinator_address()

  # Scrub jax/tpu config the parent test session forced (cpu platform,
  # 8 fake devices): each worker sets its own.
  env = {k: v for k, v in os.environ.items()
         if not k.startswith(("JAX_", "XLA_", "TPU"))}
  env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
  env["JAX_COORDINATOR_ADDRESS"] = coordinator
  env["JAX_NUM_PROCESSES"] = "2"
  env["TF_CPP_MIN_LOG_LEVEL"] = "2"
  # Shared dir for the cross-process sharded-checkpoint round trip.
  env["T2R_TEST_CKPT_DIR"] = str(tmp_path / "ckpt")

  procs = []
  try:
    for i in range(2):
      worker_env = dict(env)
      worker_env["JAX_PROCESS_ID"] = str(i)
      procs.append(subprocess.Popen(
          [sys.executable, worker],
          env=worker_env, stdout=subprocess.PIPE,
          stderr=subprocess.STDOUT, text=True))

    # Drain both pipes CONCURRENTLY: a worker blocking on a full
    # stdout pipe would stall its SPMD collective and hang its peer.
    with ThreadPoolExecutor(max_workers=2) as pool:
      futures = [pool.submit(p.communicate, None, 520) for p in procs]
      outputs = [f.result(timeout=540)[0] for f in futures]
  finally:
    for p in procs:
      if p.poll() is None:
        p.kill()

  for i, (proc, out) in enumerate(zip(procs, outputs)):
    assert proc.returncode == 0, (
        f"worker {i} failed (rc={proc.returncode}):\n{out[-3000:]}")

  losses = []
  for i, out in enumerate(outputs):
    marker = [line for line in out.splitlines()
              if line.startswith("DISTRIBUTED_OK")]
    assert marker, f"worker {i} printed no marker:\n{out[-2000:]}"
    pid, loss = marker[0].split()[1:]
    assert int(pid) == i
    losses.append(float(loss))
    # The sharded checkpoint round-trip (each process saving only its
    # addressable shards, restore + cross-process checksum) ran too.
    assert any(line.startswith("CKPT_OK") for line in
               out.splitlines()), f"worker {i}: no CKPT_OK:\n{out[-2000:]}"
  # Replicated metrics: both processes must see the SAME global loss —
  # the signature of one SPMD program spanning both, not two
  # independent runs.
  assert losses[0] == pytest.approx(losses[1], abs=1e-6), losses
