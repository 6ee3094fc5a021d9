"""Unified telemetry plane: spans, metrics registry, flight recorder.

Three legs (ISSUE 11, docs/OBSERVABILITY.md):

  * `core` — the cross-process span tracer: monotonic-clock spans
    tagged pid/role/actor_id in a lock-free bounded ring, flushed to
    per-process ``trace_<role>.jsonl``; `merge`
    (``python -m tensor2robot_tpu.telemetry.merge``) folds every
    process of a run into one Chrome-trace/Perfetto timeline with
    clock offsets reconciled via the fleet RPC handshake.
  * `metrics` — the process-wide counter/gauge/histogram registry the
    existing subsystems publish into (replay, serving, data plane,
    trainers, compile cache), snapshotted on the trainers' log cadence
    and pollable over the fleet's ``telemetry`` RPC.
  * `flightrec` — on a latched error / crash-policy trigger / hang
    detection, every process dumps its span ring + metrics snapshot to
    ``<model_dir>/flightrec/``.

`records` defines the unified `metrics_<tag>.jsonl` envelope
(``{step, wall, role, payload}``) and its one reader.

The always-on performance plane (ISSUE 15) rides the same three legs:
`perf` (live MFU attribution on one analytic FLOPs denominator +
`rsrc.*` resource watermarks from a per-role sampler thread),
`sentinel` (gin-configurable watch rules over the registry's scalar
view, alert events/counters/`alerts.jsonl`, page severity → flight
records), and `report` (``python -m tensor2robot_tpu.telemetry.report``
— one markdown page per run dir).

The whole package is jax-free BY CONTRACT: fleet actors and data-plane
workers import it at spawn (IMP401 worker-safe set; subprocess-pinned
by tests/test_telemetry.py).
"""

from tensor2robot_tpu.telemetry import core
from tensor2robot_tpu.telemetry import flightrec
from tensor2robot_tpu.telemetry import merge
from tensor2robot_tpu.telemetry import metrics
from tensor2robot_tpu.telemetry import perf
from tensor2robot_tpu.telemetry import prometheus
from tensor2robot_tpu.telemetry import records
from tensor2robot_tpu.telemetry import report
from tensor2robot_tpu.telemetry import sentinel
from tensor2robot_tpu.telemetry.core import (
    clock_offset_from_handshake,
    configure,
    current_role,
    event,
    get_tracer,
    span,
)
from tensor2robot_tpu.telemetry.metrics import registry

__all__ = [
    "clock_offset_from_handshake",
    "configure",
    "core",
    "current_role",
    "event",
    "flightrec",
    "get_tracer",
    "merge",
    "metrics",
    "perf",
    "prometheus",
    "records",
    "registry",
    "report",
    "sentinel",
    "span",
]
