"""Fleet replay/serving host: one process owning the store + the engine.

The Sebulba topology (PAPERS.md Podracer): actors do not touch the
device or the replay memory — they speak RPC to host processes that
own the `ReplayWriteService`→`ReplayStore` ingestion plane and the
`CEMPolicyServer` (bucketed AOT engine + micro-batcher). On a single
host both live in ONE process (the default, `replay_hosts=0`), which
is deliberate:

  * every actor's `act` request lands in the SAME micro-batcher, so N
    actors coalesce into ~one CEM program dispatch (the serving stack's
    whole point, now fed by a process fleet instead of threads);
  * the learner's `publish` hot-swaps the engine's params in the same
    address space the actors' requests resolve against — one swap
    serves the entire actor fleet atomically;
  * `param_refresh_lag` and replay staleness are measured at the one
    choke point every transition passes through.

Past one host (ISSUE 16) the same process splits along its two
planes, each behind `fleet.transport`:

  * SHARDED REPLAY — `replay_shard_main` processes each own ONE store
    shard behind a `replay.service.ReplayFront`; actors commit
    episodes to their rendezvous-hash home shard
    (`fleet.actor.home_shard`) and the learner fans sample requests
    across shards, concatenating shard-major (the PR-3 gather
    contract). Staleness and lag are accounted where each shard
    lives. Serving hosts then own NO store (`replay_hosts > 0`).
  * BROADCAST TREE — `serving_hosts` engine replicas arranged in a
    `broadcast_degree`-ary tree (heap layout: children of host i are
    i·d+1 … i·d+d). The learner publishes to the root only; each host
    swaps locally and forwards to its children, so the learner's
    uplink carries d copies instead of N — with per-hop
    `param_refresh_lag` attribution (commits stamp the acting host's
    tree depth) and `fleet.broadcast.*` wall-clock hop metrics.

Metric definitions (docs/FLEET.md):

  * `param_refresh_lag` — at each committed episode, the learner's
    CURRENT step (the store's `learner_step` tag) minus the learner
    step stamped on the params the actor acted with. This is the
    end-to-end publication latency actors actually experience:
    checkpoint cadence + publish transfer (+ broadcast hops) +
    however long the episode took to collect.
  * replay staleness — the plane's existing definition (learner step
    at SAMPLE minus at ADD), accounted by the store-side
    `ReplayBatchSampler` every learner `sample` rides through.

Crash contract: each connection's replay sessions are aborted on
disconnect (`rpc.DISCONNECT_METHOD`), so an actor that dies mid-episode
never lands partial rows — same session-abort semantics as the
in-process service, proven across the process boundary by
tests/test_fleet.py (and across the TCP transport by
tests/test_fleet_transport.py).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from tensor2robot_tpu import telemetry
from tensor2robot_tpu.fleet import faults as faults_lib
from tensor2robot_tpu.fleet import proc
from tensor2robot_tpu.fleet import rpc as rpc_lib
from tensor2robot_tpu.telemetry import flightrec
from tensor2robot_tpu.telemetry import metrics as tmetrics

# The replay plane (`replay.service.LagStats`/`ReplayFront`) is
# imported INSIDE the state constructors, never at module top: its
# import chain reaches `specs` → jax, and this module must stay in the
# jax-free actor import closure (fleet/__init__ pulls it in;
# tests/test_fleet.py pins the closure).

log = logging.getLogger(__name__)


def _server_kwargs(config) -> Dict[str, Any]:
  """The transport-seam kwargs every fleet RpcServer shares."""
  return dict(
      authkey=config.authkey,
      transport=getattr(config, "transport", "loopback"),
      sndbuf=getattr(config, "tcp_sndbuf", 0),
      rcvbuf=getattr(config, "tcp_rcvbuf", 0))


def _client_kwargs(config) -> Dict[str, Any]:
  """The transport-seam kwargs every fleet RpcClient shares."""
  return dict(
      authkey=config.authkey,
      transport=getattr(config, "transport", "loopback"),
      sndbuf=getattr(config, "tcp_sndbuf", 0),
      rcvbuf=getattr(config, "tcp_rcvbuf", 0))


def _handshake_clock(config, root_address) -> None:
  """Offsets this process's trace clock to the root host's.

  Every fleet process merges onto ONE timeline — the root serving
  host's CLOCK_MONOTONIC. Actors and the learner handshake over their
  long-lived clients; replica/shard hosts (which otherwise only
  answer) dial this transient hello at startup.
  """
  if root_address is None:
    return
  try:
    client = rpc_lib.RpcClient(
        tuple(root_address),
        call_timeout_secs=getattr(config, "rpc_call_timeout_secs",
                                  rpc_lib.DEFAULT_CALL_TIMEOUT_SECS),
        max_retries=getattr(config, "rpc_max_retries",
                            rpc_lib.DEFAULT_MAX_RETRIES),
        **_client_kwargs(config))
  except Exception:  # noqa: BLE001 — trace alignment is best-effort
    log.warning("clock handshake connect failed", exc_info=True)
    return
  try:
    t_before = time.monotonic()
    hello = client.call("hello")
    t_after = time.monotonic()
    if "monotonic" in hello:
      telemetry.get_tracer().set_clock_offset(
          telemetry.clock_offset_from_handshake(
              hello["monotonic"], t_before, t_after))
  except Exception:  # noqa: BLE001
    log.warning("clock handshake failed", exc_info=True)
  finally:
    client.close()


class _HostState:
  """Everything a serving host serves, plus the RPC method table.

  `host_index` 0 is the ROOT: the reference clock, the learner's
  control endpoint, and — when `replay_hosts == 0` — the owner of the
  whole replay plane (the original single-host fleet, unchanged).
  Indices > 0 are broadcast-tree engine replicas: same engine, same
  `act` surface, no store (actors commit to shard services).
  """

  def __init__(self, config, host_index: int = 0):
    # jax and the model stack load HERE, in the host process — never
    # at module import (actor processes import this package jax-free).
    import jax

    from tensor2robot_tpu.replay.service import (
        ReplayFront,
        ReplayWriteService,
    )
    from tensor2robot_tpu.replay.store import ReplayStore
    from tensor2robot_tpu.serving.cem_policy import CEMPolicyServer

    self._config = config
    self.host_index = int(host_index)
    role = "host" if host_index == 0 else f"host{host_index}"
    # The host's telemetry identity: spans from the RPC layer and the
    # serving/replay planes flush to trace_<role>.jsonl; the ROOT
    # host's clock is the REFERENCE clock every handshaking client
    # offsets against.
    telemetry.configure(
        role, trace_dir=getattr(config, "telemetry_dir", "") or None)
    # Resource watermarks (ISSUE 15): device memory + host RSS +
    # replay/queue fill peaks as rsrc.* gauges. They live in the
    # ordinary registry, so the orchestrator's `telemetry` poll
    # aggregates them fleet-wide for free.
    from tensor2robot_tpu.telemetry import perf as perf_lib
    from tensor2robot_tpu.utils import profiling
    perf_lib.start_resource_sampler(
        sources=[profiling.device_memory_source()])
    self._learner = _build_learner(config)
    state0 = self._learner.create_state(
        jax.random.PRNGKey(config.seed), batch_size=2)
    acting0 = state0.train_state.replace(opt_state=None)
    self.policy_server = CEMPolicyServer(
        self._learner, acting0,
        max_batch=config.serve_max_batch,
        max_wait_us=config.serve_max_wait_us,
        seed=config.seed + 7)
    # The replay plane lives here ONLY on the single-host topology;
    # with shard services (`replay_hosts > 0`) every serving host —
    # root included — is engine-only and commit/sample are shard RPCs.
    if host_index == 0 and getattr(config, "replay_hosts", 0) == 0:
      store = ReplayStore(
          self._learner.transition_specification(),
          capacity=config.replay_capacity,
          num_shards=config.replay_shards,
          seed=config.seed + 11)
      service = ReplayWriteService(
          store,
          queue_batches=config.queue_batches,
          overflow=config.overflow)
      self.replay: Optional[ReplayFront] = ReplayFront(store, service)
    else:
      self.replay = None
    # Per-role registry snapshots pushed by actors/learner over the
    # `telemetry_push` RPC; the orchestrator's `telemetry` poll
    # returns them next to the host's own registry — one aggregated
    # fleet-wide view from one call.
    self._pushed_telemetry: Dict[str, Any] = {}
    self._lock = threading.Lock()
    self.publishes = 0
    self._publish_t0: Optional[float] = None
    self._learner_window: Optional[Tuple[float, int, float, int]] = None
    self._resumes: list = []  # observed backward learner steps
    # Broadcast-tree placement, set by the orchestrator's
    # `configure_broadcast` after every serving host is up. Forward
    # CLIENTS are per-connection (`ctx`) — owned by the publishing
    # connection's handler thread, rebuilt free on reconnect — only
    # the address list is shared state.
    self._children: List[Tuple[str, int]] = []
    self._tree_depth = 0
    self._broadcast_forwards = 0
    self._tm_depth = tmetrics.gauge("fleet.broadcast.depth")
    self._tm_forwards = tmetrics.counter("fleet.broadcast.forwards")
    self._tm_publish_ms = tmetrics.histogram(
        "fleet.broadcast.publish_ms", faults_lib.RECOVERY_MS_BOUNDS)
    self.shutdown_requested = threading.Event()

  # ---- broadcast fan-out ----

  def _forward_publish(self, payload: Dict[str, Any],
                       ctx: dict) -> None:
    """Forwards a publication to this host's tree children.

    Runs on the publishing connection's handler thread with its own
    per-child clients (in `ctx` — lock-free by ownership). A child
    that cannot be reached raises out of the handler: the learner's
    publish call sees the error, exactly as if its own direct publish
    had failed — broadcast does not silently narrow the fleet.
    """
    with self._lock:
      children = list(self._children)
    if not children:
      return
    forwarded = dict(payload)
    forwarded["hop"] = int(payload.get("hop", 0)) + 1
    clients = ctx.setdefault("broadcast_clients", {})
    for child in children:
      client = clients.get(child)
      if client is None:
        client = rpc_lib.RpcClient(
            child,
            call_timeout_secs=getattr(
                self._config, "rpc_call_timeout_secs",
                rpc_lib.DEFAULT_CALL_TIMEOUT_SECS),
            max_retries=getattr(self._config, "rpc_max_retries",
                                rpc_lib.DEFAULT_MAX_RETRIES),
            **_client_kwargs(self._config))
        clients[child] = client
      client.call("publish", forwarded)
      self._tm_forwards.inc()
      with self._lock:
        self._broadcast_forwards += 1

  # ---- the RPC method table ----

  def handle(self, method: str, payload: Any, ctx: dict) -> Any:
    if method == "act":
      # One atomic publication read: version and learner_step must be
      # a consistent pair (a swap between two property reads would
      # tear them). A swap landing between this read and the engine's
      # own dispatch can still attribute a single episode to the
      # adjacent publication — off by at most one refresh, which the
      # lag histogram tolerates (documented in docs/FLEET.md).
      publication = self.policy_server.engine.publication
      actions = self.policy_server.select_actions(payload)
      return {"actions": np.asarray(actions),
              "params_version": publication.version,
              "params_learner_step": publication.learner_step,
              # The acting host's broadcast-tree depth: actors stamp
              # it into commits so lag is attributable PER HOP.
              "params_hop": self._tree_depth}
    if method == "acting_state":
      # Whole-params refresh for Anakin pods (ISSUE 19): a pod acts
      # ON ITS OWN DEVICES (the env and the Q-network are one pmapped
      # program), so instead of per-step `act` RPCs it pulls the
      # published acting state and runs with it until the version
      # moves. `have_version` makes the poll cheap: an unchanged
      # version returns the stamp alone, no state payload.
      publication = self.policy_server.engine.publication
      have = (int(payload.get("have_version", -1))
              if isinstance(payload, dict) else -1)
      reply: Dict[str, Any] = {
          "params_version": publication.version,
          "params_learner_step": publication.learner_step,
          "params_hop": self._tree_depth,
          "state": None,
      }
      if publication.version != have and publication.state is not None:
        import jax
        reply["state"] = jax.device_get(publication.state)
      return reply
    if method in ("commit", "begin_episode", "append", "end_episode",
                  "sample", "size"):
      if self.replay is None:
        raise ValueError(
            f"host {self.host_index} serves no replay "
            "(replay_hosts > 0 — commits and samples go to the shard "
            "services)")
      if method == "commit":
        return self.replay.commit(payload, ctx)
      if method == "begin_episode":
        return self.replay.begin_episode(payload, ctx)
      if method == "append":
        return self.replay.append(payload, ctx)
      if method == "end_episode":
        return self.replay.end_episode(payload, ctx)
      if method == "sample":
        return self.replay.sample(int(payload))
      return self.replay.size()
    if method == "set_learner_step":
      step = int(payload)
      if self.replay is not None:
        self.replay.set_learner_step(step)
      now = time.monotonic()
      with self._lock:
        if self._learner_window is None:
          self._learner_window = (now, step, now, step)
        else:
          t0, s0, _, last = self._learner_window
          if step < last:
            # The learner's step went BACKWARD: a crash-resume
            # restored from a checkpoint. The host is the one witness
            # with continuous state across learner incarnations, so
            # the MEASURED restore point is recorded here — the chaos
            # tests' loss-bounded-by-cadence gate reads it instead
            # of trusting config arithmetic.
            self._resumes.append({"from_step": last, "to_step": step})
          self._learner_window = (t0, s0, now, step)
      return True
    if method == "publish":
      self.policy_server.update_state(
          payload["state"], learner_step=int(payload["step"]))
      with self._lock:
        self.publishes += 1
        if self._publish_t0 is None:
          self._publish_t0 = time.monotonic()
      tmetrics.counter("fleet.param_publishes").inc()
      # Broadcast hop accounting: the learner stamps its wall clock at
      # origin; every host in the tree records origin→local-swap
      # latency (same machine, same wall clock), so hop cost is
      # visible per depth in the merged registry.
      if payload.get("origin_wall") is not None:
        self._tm_publish_ms.observe(
            max(0.0, (time.time() - float(payload["origin_wall"]))
                * 1e3))
      self._forward_publish(payload, ctx)
      return self.policy_server.params_version
    if method == "configure_broadcast":
      with self._lock:
        self._children = [tuple(c) for c in payload.get("children", ())]
        self._tree_depth = int(payload.get("depth", 0))
      self._tm_depth.set(self._tree_depth)
      return True
    if method == "metrics_scalars":
      out = (self.replay.metrics_scalars()
             if self.replay is not None else {})
      out["fleet_param_publishes"] = float(self.publishes)
      return out
    if method == "metrics":
      return self.metrics()
    if method == "hello":
      engine = self.policy_server.engine
      capacity = (self.replay.store.capacity
                  if self.replay is not None
                  else int(self._config.replay_capacity))
      # `monotonic` is the telemetry clock handshake: the client reads
      # its own clock around the call and derives its offset to this
      # host's CLOCK_MONOTONIC (telemetry.clock_offset_from_handshake)
      # — how the merge tool puts every process on one timeline.
      return {"max_batch": engine.max_batch,
              "capacity": capacity,
              "params_version": engine.params_version,
              "params_learner_step": engine.params_learner_step,
              "monotonic": time.monotonic()}
    if method == "telemetry":
      # The fleet-wide aggregated view (one poll): the host's own
      # registry — serving/lag live HERE, at the choke point — plus
      # whatever snapshots the other roles pushed.
      with self._lock:
        pushed = dict(self._pushed_telemetry)
      return {"host": tmetrics.registry().snapshot(),
              "pushed": pushed,
              "monotonic": time.monotonic()}
    if method == "telemetry_push":
      with self._lock:
        self._pushed_telemetry[str(payload["role"])] = {
            "snapshot": payload["snapshot"],
            "wall": time.time(),
        }
      return True
    if method == "flight_record":
      # The orchestrator's latched-error hook: a still-live host dumps
      # its span ring + registry before teardown.
      return flightrec.dump(payload["out_dir"],
                            payload.get("reason", "requested"))
    if method == "shutdown":
      self.shutdown_requested.set()
      return True
    if method == rpc_lib.DISCONNECT_METHOD:
      # A dropped connection aborts every session IT opened: whatever
      # its actor staged mid-episode is discarded, never committed
      # (identity-checked in the front — a late-detected death never
      # touches a restarted incarnation's fresh session). Broadcast
      # forward clients opened by this connection close with it.
      if self.replay is not None:
        self.replay.abort_sessions(ctx)
      for client in ctx.get("broadcast_clients", {}).values():
        client.close()
      return None
    raise ValueError(f"unknown fleet rpc method {method!r}")

  def metrics(self) -> Dict[str, Any]:
    with self._lock:
      learner_window = self._learner_window
      resumes = list(self._resumes)
      publishes = self.publishes
      broadcast = {
          "depth": self._tree_depth,
          "children": len(self._children),
          "forwards": self._broadcast_forwards,
      }
    if self.replay is not None:
      front = self.replay.metrics()
    else:
      front = {"store": None, "service": None, "staleness": {},
               "param_refresh_lag": None, "commit_window": None}
    engine = self.policy_server.engine
    front.update({
        "publishes": publishes,
        "params_version": engine.params_version,
        "params_learner_step": engine.params_learner_step,
        "learner_window": (None if learner_window is None else {
            "first_time": learner_window[0],
            "first_step": learner_window[1],
            "last_time": learner_window[2],
            "last_step": learner_window[3],
        }),
        "learner_resumes": resumes,
        "commit_window": front.get("commit_window"),
        "serving_dispatches": engine.dispatch_count,
        "host_index": self.host_index,
        "broadcast": broadcast,
    })
    return front

  def close(self) -> None:
    # Intake is already stopped (the RPC server closes first); flush
    # what the writer still holds, then tear the batcher down.
    try:
      if self.replay is not None:
        self.replay.close()
    finally:
      self.policy_server.close()


class _ShardState:
  """One replay shard service: a 1-shard store behind a `ReplayFront`.

  The `ReplayShardService` of ISSUE 16: each shard host owns
  `replay_capacity / replay_hosts` rows with the SAME session/commit/
  sample/lag semantics as the single-host plane (shared via
  `ReplayFront` — one implementation, two deployments), so staleness
  and `param_refresh_lag` are accounted where the shard lives.
  """

  def __init__(self, config, shard_index: int):
    from tensor2robot_tpu.replay.service import (
        ReplayFront,
        ReplayWriteService,
    )
    from tensor2robot_tpu.replay.store import ReplayStore

    self._config = config
    self.shard_index = int(shard_index)
    telemetry.configure(
        f"shard{shard_index}",
        trace_dir=getattr(config, "telemetry_dir", "") or None)
    from tensor2robot_tpu.telemetry import perf as perf_lib
    perf_lib.start_resource_sampler()
    num_hosts = max(1, int(getattr(config, "replay_hosts", 1)))
    store = ReplayStore(
        # The spec comes from the same learner constructor every other
        # process uses — structural agreement by construction.
        _build_learner(config).transition_specification(),
        capacity=max(1, config.replay_capacity // num_hosts),
        num_shards=1,  # one shard per host IS the sharding
        seed=config.seed + 11 + 97 * (shard_index + 1))
    service = ReplayWriteService(
        store,
        queue_batches=config.queue_batches,
        overflow=config.overflow)
    self.front = ReplayFront(store, service)
    self.shutdown_requested = threading.Event()

  def handle(self, method: str, payload: Any, ctx: dict) -> Any:
    if method == "commit":
      return self.front.commit(payload, ctx)
    if method == "begin_episode":
      return self.front.begin_episode(payload, ctx)
    if method == "append":
      return self.front.append(payload, ctx)
    if method == "end_episode":
      return self.front.end_episode(payload, ctx)
    if method == "sample":
      return self.front.sample(int(payload))
    if method == "size":
      return self.front.size()
    if method == "set_learner_step":
      self.front.set_learner_step(int(payload))
      return True
    if method == "metrics":
      out = self.front.metrics()
      out["shard_index"] = self.shard_index
      return out
    if method == "metrics_scalars":
      return self.front.metrics_scalars()
    if method == "hello":
      return {"capacity": self.front.store.capacity,
              "shard_index": self.shard_index,
              "monotonic": time.monotonic()}
    if method == "telemetry":
      return {"host": tmetrics.registry().snapshot(),
              "pushed": {},
              "monotonic": time.monotonic()}
    if method == "flight_record":
      return flightrec.dump(payload["out_dir"],
                            payload.get("reason", "requested"))
    if method == "shutdown":
      self.shutdown_requested.set()
      return True
    if method == rpc_lib.DISCONNECT_METHOD:
      self.front.abort_sessions(ctx)
      return None
    raise ValueError(f"unknown replay shard rpc method {method!r}")

  def close(self) -> None:
    self.front.close()


def _build_learner(config):
  """The host's own QTOptLearner: the same constructor the learner
  process uses, so the published TrainState trees match structurally
  (CEM serving params here, gradient state there)."""
  from tensor2robot_tpu.research.qtopt.qtopt_learner import QTOptLearner
  from tensor2robot_tpu.research.qtopt.t2r_models import GraspingQModel

  model = GraspingQModel(
      image_size=config.image_size,
      action_dim=config.action_dim,
      torso_filters=tuple(config.torso_filters),
      head_filters=tuple(config.head_filters),
      dense_sizes=tuple(config.dense_sizes))
  return QTOptLearner(
      model,
      cem_population=config.cem_population,
      cem_iterations=config.cem_iterations,
      cem_elites=config.cem_elites,
      cem_inference=config.cem_inference)


def host_main(config, ready_conn, stop_event, heartbeat,
              host_index: int = 0, root_address=None) -> None:
  """Child-process entry: build → handshake → serve → drain → exit.

  `ready_conn` (a Pipe end) carries the bound RPC address back to the
  orchestrator once the engine is warmed; the orchestrator spawns
  actors/learner only after this handshake, so clients never race a
  cold host.

  `stop_event` is the host's OWN stop signal, set by the orchestrator
  only AFTER the final metrics read — the host must outlive the
  actor/learner drain (it is the last process standing in the
  shutdown barrier). The RPC `shutdown` method is the other exit.

  `host_index` > 0 spawns a broadcast-tree engine replica (no store);
  `root_address` lets non-root hosts align their trace clock to the
  root's before serving.
  """
  proc.scrub_inherited_distributed_env()
  role = "host" if host_index == 0 else f"host{host_index}"
  # Server-side fault seam (slow_host stalls, injected disconnects):
  # armed BEFORE the server accepts, so call counting is deterministic
  # from the first RPC.
  faults_lib.install(config, role)
  try:
    proc.claim_device(role)
    state = _HostState(config, host_index=host_index)
    server = rpc_lib.RpcServer(state.handle, **_server_kwargs(config))
  except BaseException as e:
    # A host that dies building (bad config, compile failure) leaves
    # its last moments in the flight recorder before the orchestrator
    # sees the exit code.
    if getattr(config, "flightrec_dir", ""):
      flightrec.dump(config.flightrec_dir,
                     f"{role} launch failed: {e!r}")
    raise
  try:
    ready_conn.send({"address": server.address})
    ready_conn.close()
    if host_index != 0:
      _handshake_clock(config, root_address)
    while not (stop_event.is_set() or state.shutdown_requested.is_set()):
      proc.beat(heartbeat)
      time.sleep(0.1)
  finally:
    from tensor2robot_tpu.telemetry import perf as perf_lib
    perf_lib.stop_resource_sampler()  # no jax calls past teardown
    server.close()
    state.close()
    telemetry.get_tracer().close()  # flush the host's trace tail


def replay_shard_main(config, shard_index: int, root_address,
                      ready_conn, stop_event, heartbeat) -> None:
  """Child-process entry for one replay shard service (ISSUE 16).

  Same lifecycle contract as `host_main`: address handshake over
  `ready_conn`, heartbeat while serving, drain on `stop_event` (set
  only after the orchestrator's final metrics read) or the RPC
  `shutdown`.
  """
  proc.scrub_inherited_distributed_env()
  role = f"shard{shard_index}"
  faults_lib.install(config, role)
  try:
    state = _ShardState(config, shard_index)
    server = rpc_lib.RpcServer(state.handle, **_server_kwargs(config))
  except BaseException as e:
    if getattr(config, "flightrec_dir", ""):
      flightrec.dump(config.flightrec_dir,
                     f"{role} launch failed: {e!r}")
    raise
  try:
    ready_conn.send({"address": server.address})
    ready_conn.close()
    _handshake_clock(config, root_address)
    while not (stop_event.is_set() or state.shutdown_requested.is_set()):
      proc.beat(heartbeat)
      time.sleep(0.1)
  finally:
    from tensor2robot_tpu.telemetry import perf as perf_lib
    perf_lib.stop_resource_sampler()
    server.close()
    state.close()
    telemetry.get_tracer().close()
