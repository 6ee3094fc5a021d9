"""Podracer-style learner/actor fleet (Sebulba topology, one host).

The composition layer over every organ PRs 1–6 built: N jax-free actor
PROCESSES (each a `GraspActor` driving `MuJoCoPoseEnv` through the
`PoseGraspBandit` adapter) pull actions from, and commit atomic
episodes into, ONE replay/serving host process (`CEMPolicyServer` +
`ReplayWriteService`/`ReplayStore`), which feeds a learner process
running the unmodified `train_qtopt` loop; fresh checkpoints flow back
as param publications hot-swapped into the serving engine, stamped
with the learner step so `param_refresh_lag` is measured next to
replay staleness. See docs/FLEET.md; no chip measurement (ROADMAP W4).

  * `orchestrator` — `FleetConfig` / `Fleet` / `run_fleet`: the
    launch gate, heartbeat + exit-code supervision, actor-crash
    policy, and the zero-leak shutdown barrier.
  * `host` — the replay/serving host process.
  * `actor` — the jax-free actor process + the RPC-backed
    policy-server and replay-session seams for `GraspActor`.
  * `learner` — `RemoteReplay` + `ParamPublishHook` around
    `train_qtopt`.
  * `rpc` — the loopback request/response transport.

This package init stays light (no jax): `run_t2r_trainer` imports it
for gin registration in every mode, including `--validate_only`.
"""

from tensor2robot_tpu.fleet.orchestrator import (
    Fleet,
    FleetConfig,
    FleetError,
    FleetResult,
    run_fleet,
)
from tensor2robot_tpu.fleet.rpc import RpcClient, RpcError, RpcServer

__all__ = [
    "Fleet",
    "FleetConfig",
    "FleetError",
    "FleetResult",
    "RpcClient",
    "RpcError",
    "RpcServer",
    "run_fleet",
]
