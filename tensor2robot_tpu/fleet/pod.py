"""Fleet Anakin pod: a whole vectorized collector as ONE fleet unit.

The hybrid Podracer topology (ISSUE 19, PAPERS.md): where a process
actor steps one env and pays an `act` RPC per decision, a pod runs
`make_anakin_collect_fn` — `envs_per_pod` functional envs vmapped
INSIDE pmap over its local devices — so acting and env stepping are
one device program and the wire carries whole rollout SEGMENTS, not
per-step traffic. The pod is a pure collector: it never trains.

Three seams tie it into the existing fleet contracts:

  * Params come from the pod's assigned serving replica via the
    `acting_state` RPC (host.py): the broadcast tree already pushed
    the publication there, so the pod polls its replica — version
    stamp only when unchanged, full acting `TrainState` when it moved
    — and acts with device-resident params until the next refresh.
    `param_refresh_lag` attribution rides the same version/step/hop
    stamp process actors use.
  * Experience lands on the pod's rendezvous-hashed home shard through
    the SAME `FleetReplaySession.add` one-commit-per-call contract:
    each segment ([T·N] rows after `flatten_devices`) is one atomic
    episode-batch commit, so a pod death can never leave partial rows
    (`adds_total % (envs_per_pod * pod_rollout_length) == 0` is the
    pin).
  * Supervision: pods share the actor crash policy, restart budget,
    chaos schedule, heartbeat cadence (one beat per segment), and
    telemetry merge — the orchestrator treats `pod-N` exactly like a
    (much louder) `actor-N`.

Unlike `fleet.actor`, this module's MAIN does import jax (the whole
point is on-device collection) — but only inside `pod_main`, after
the scrub/telemetry/RPC bring-up, so importing the module stays cheap
and worker-safe (the orchestrator imports it to spawn).
"""

from __future__ import annotations

import logging
import os
import time
from typing import Optional

import numpy as np

from tensor2robot_tpu import telemetry
from tensor2robot_tpu.fleet import faults as faults_lib
from tensor2robot_tpu.fleet import proc
from tensor2robot_tpu.fleet.actor import (
    CRASH_EXIT_CODE,
    FleetReplaySession,
    _push_telemetry,
    address_book,
    home_shard,
)
from tensor2robot_tpu.fleet.rpc import RpcClient
from tensor2robot_tpu.telemetry import flightrec
from tensor2robot_tpu.telemetry import metrics as tmetrics

log = logging.getLogger(__name__)


def pod_env_family(env: str) -> str:
  """Maps a FleetConfig env name onto a FUNCTIONAL env family.

  Pods compile the env into the rollout program, so only pure
  `envs/core.FunctionalEnv` families qualify. `mujoco_pose` process
  actors drive real physics on the host; a pod in the same fleet
  collects from the functional `pose` renderer instead — same wire
  spec, same reward rule, no host stepping.
  """
  if env in ("pose", "mujoco_pose"):
    return "pose"
  if env == "procgen":
    return "procgen"
  raise ValueError(
      f"env {env!r} has no functional family for Anakin pods "
      "(pose/mujoco_pose/procgen)")


def trim_devices(devices, num_envs: int):
  """The largest device prefix that divides `num_envs` evenly.

  `make_anakin_collect_fn` requires `num_envs % num_devices == 0`;
  rather than force every config to know the host's device count, the
  pod shrinks its pmap axis until the batch divides (worst case one
  device — always valid). Pure so tests pin it.
  """
  devices = list(devices)
  num_devices = max(1, len(devices))
  while num_envs % num_devices:
    num_devices -= 1
  return devices[:num_devices]


class PodParamClient:
  """Acting-params cache refreshed over the `acting_state` RPC.

  Duck-types the `FleetPolicyClient` stamp surface
  (`params_version` / `params_learner_step` / `params_hop`) so the
  shared `FleetReplaySession` attributes committed segments to the
  publication that produced them, exactly like process actors.
  """

  def __init__(self, client: RpcClient):
    self._client = client
    self.state = None
    self.params_version = -1
    self.params_learner_step = 0
    self.params_hop = 0

  def refresh(self) -> bool:
    """One poll; True when a NEW publication replaced the cache."""
    reply = self._client.call(
        "acting_state", {"have_version": self.params_version})
    self.params_learner_step = int(reply["params_learner_step"])
    self.params_hop = int(reply.get("params_hop", 0))
    if reply.get("state") is None:
      return False
    self.state = reply["state"]
    self.params_version = int(reply["params_version"])
    return True


def _inject_crash(mode: str, sink: FleetReplaySession) -> None:
  """Pod-side twin of `actor._inject_crash`: the mid_episode mode
  stages one wire batch in a host-side session before dying, so the
  disconnect-abort contract is exercised by pod-sized payloads too."""
  if mode == "mid_episode":
    sink.begin_episode()
    if sink.last_transitions is not None:
      sink.append(sink.last_transitions)
    os._exit(CRASH_EXIT_CODE)
  if mode == "hard":
    os._exit(CRASH_EXIT_CODE)
  raise RuntimeError("injected pod crash (FleetConfig.actor_crash_*)")


def pod_main(config, pod_index: int, address, stop_event,
             heartbeat, incarnation: int = 0) -> None:
  """Child-process entry: connect → refresh/collect/commit until told
  to stop."""
  proc.scrub_inherited_distributed_env()
  pod_id = f"pod-{pod_index}"
  telemetry.configure(
      pod_id, trace_dir=getattr(config, "telemetry_dir", "") or None,
      actor_id=pod_id)
  from tensor2robot_tpu.telemetry import perf as perf_lib
  perf_lib.start_resource_sampler()
  injector = faults_lib.install(config, pod_id,
                                incarnation=incarnation)
  proc.claim_device(pod_id)
  rpc_kwargs = dict(
      authkey=config.authkey,
      call_timeout_secs=config.rpc_call_timeout_secs,
      max_retries=config.rpc_max_retries,
      transport=getattr(config, "transport", "loopback"),
      sndbuf=getattr(config, "tcp_sndbuf", 0),
      rcvbuf=getattr(config, "tcp_rcvbuf", 0))
  book = address_book(address)
  serving = book["serving"]
  # Same placement rule as actors: refresh from this pod's serving
  # replica (round-robin over the broadcast tree), commit to the
  # rendezvous-hash home shard.
  refresh_address = serving[pod_index % len(serving)]
  client = RpcClient(refresh_address, **rpc_kwargs)
  commit_client: Optional[RpcClient] = None
  try:
    t_before = time.monotonic()
    hello = client.call("hello")
    t_after = time.monotonic()
    if "monotonic" in hello and refresh_address == serving[0]:
      telemetry.get_tracer().set_clock_offset(
          telemetry.clock_offset_from_handshake(
              hello["monotonic"], t_before, t_after))
    if refresh_address != serving[0]:
      # The reference clock is the root's — one transient hello
      # aligns this trace (the actor_main contract).
      with RpcClient(serving[0], **rpc_kwargs) as root:
        t_before = time.monotonic()
        root_hello = root.call("hello")
        t_after = time.monotonic()
        if "monotonic" in root_hello:
          telemetry.get_tracer().set_clock_offset(
              telemetry.clock_offset_from_handshake(
                  root_hello["monotonic"], t_before, t_after))
    params = PodParamClient(client)
    if book["shards"]:
      shard = home_shard(pod_id, len(book["shards"]))
      commit_client = RpcClient(book["shards"][shard], **rpc_kwargs)
      sink = FleetReplaySession(commit_client, pod_id, params)
      log.info("%s commits to replay shard %d at %s", pod_id, shard,
               book["shards"][shard])
    else:
      sink = FleetReplaySession(client, pod_id, params)

    # jax from here down: build the on-device collector. The serving
    # engine publishes version 0 at construction, so the first refresh
    # always lands acting params before any rollout runs.
    import jax

    from tensor2robot_tpu.envs.pose import PoseBanditEnv
    from tensor2robot_tpu.envs.procgen import ProcGenGraspEnv
    from tensor2robot_tpu.envs.rollout import (
        flatten_devices,
        make_anakin_collect_fn,
    )
    from tensor2robot_tpu.fleet.host import _build_learner

    family = pod_env_family(config.env)
    if family == "pose":
      env = PoseBanditEnv(image_size=config.image_size,
                          action_dim=config.action_dim)
    else:
      env = ProcGenGraspEnv(image_size=config.image_size,
                            action_dim=config.action_dim)
    devices = trim_devices(jax.local_devices(), config.envs_per_pod)
    learner = _build_learner(config)
    init_fn, collect_fn = make_anakin_collect_fn(
        learner, env,
        num_envs=config.envs_per_pod,
        rollout_length=config.pod_rollout_length,
        epsilon=config.epsilon,
        devices=devices,
        cem_population=getattr(config, "cem_population", None),
        cem_iterations=getattr(config, "cem_iterations", None))
    segment_rows = config.envs_per_pod * config.pod_rollout_length

    key = jax.random.PRNGKey(
        config.seed + 7013 * (pod_index + 1) + incarnation)
    key, init_key = jax.random.split(key)
    env_states = init_fn(init_key)
    if not params.refresh():
      # version 0 exists from engine construction; an empty reply
      # means the engine was released under us — fatal, like an
      # actor's first act failing.
      raise RuntimeError(
          f"{pod_id}: serving replica at {refresh_address} returned "
          "no acting state")

    segments = 0
    tm_env_steps = tmetrics.counter("fleet.pod.env_steps")
    tm_segments = tmetrics.counter("fleet.pod.segments")
    tm_dropped = tmetrics.counter("fleet.pod.segments_dropped")
    tm_refreshes = tmetrics.counter("fleet.pod.param_refreshes")
    tm_version = tmetrics.gauge("fleet.pod.params_version")
    push_period = (max(float(getattr(config, "telemetry_poll_secs",
                                     0.0)), 1.0)
                   if getattr(config, "telemetry_dir", "")
                   and getattr(config, "telemetry_poll_secs", 0.0)
                   else None)
    t_last_push = 0.0
    while not stop_event.is_set():
      # Refresh BEFORE the segment (not after): the segment trains
      # someone else, but the pod should act on the freshest
      # publication its replica holds.
      if params.refresh():
        tm_refreshes.inc()
      tm_version.set(params.params_version)
      key, collect_key = jax.random.split(key)
      with telemetry.span("pod.collect_segment",
                          rows=segment_rows):
        env_states, batch = collect_fn(params.state, env_states,
                                       collect_key)
        wire = {k: np.asarray(v)
                for k, v in flatten_devices(batch).items()}
      if sink.add(wire):
        tm_env_steps.inc(segment_rows)
      else:
        tm_dropped.inc()
      segments += 1
      tm_segments.inc()
      # Fault-plan seam between segments, before the beat — the same
      # placement actors use (an injected hang leaves the heartbeat
      # one full segment stale).
      event = injector.on_batch(segments)
      if event is not None:
        if event.fault == faults_lib.ACTOR_HANG:
          proc.hang(event.duration_secs)
        else:
          _inject_crash(event.mode, sink)
      proc.beat(heartbeat)
      if (push_period is not None
          and time.monotonic() - t_last_push >= push_period):
        t_last_push = time.monotonic()
        _push_telemetry(client, pod_id)
    if push_period is not None:
      _push_telemetry(client, pod_id)
    log.info("pod %s stopping cleanly: %d segments (%d rows each), "
             "last params version %d", pod_id, segments, segment_rows,
             params.params_version)
  except BaseException as e:
    if getattr(config, "flightrec_dir", ""):
      flightrec.dump(config.flightrec_dir, f"{pod_id}: {e!r}")
    raise
  finally:
    perf_lib.stop_resource_sampler()
    telemetry.get_tracer().close()
    if commit_client is not None:
      commit_client.close()
    client.close()
