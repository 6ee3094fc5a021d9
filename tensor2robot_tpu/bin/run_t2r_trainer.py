"""Main training binary: flags → gin configs → train_eval_model().

Reference parity: tensor2robot `bin/run_t2r_trainer.py` — absl flags
`--gin_configs` / `--gin_bindings` parsed into gin, then
`train_eval_model()` (SURVEY.md §3 "Main binary", §4.1; file:line
unavailable — empty reference mount).

Usage:
  python -m tensor2robot_tpu.bin.run_t2r_trainer \
    --gin_configs path/to/config.gin \
    --gin_bindings "train_eval_model.model_dir='/tmp/run'"
"""

from __future__ import annotations

import importlib
import os

from absl import app
from absl import flags

from tensor2robot_tpu import config as gin
from tensor2robot_tpu import train_eval

FLAGS = flags.FLAGS

flags.DEFINE_multi_string(
    "gin_configs", [], "Paths to gin config files, comma-ok.")
flags.DEFINE_multi_string(
    "gin_bindings", [], "Individual gin binding strings.")
flags.DEFINE_multi_string(
    "import_modules", [],
    "Extra modules to import before parsing (to register configurables).")
flags.DEFINE_bool(
    "validate_only", False,
    "Statically validate --gin_configs (t2rcheck gin rules: unknown "
    "configurables/params, dangling macros/refs, bad includes) and "
    "exit non-zero on findings instead of training.")
flags.DEFINE_string(
    "jax_coordinator_address", None,
    "host:port of process 0 for multi-host training "
    "(jax.distributed.initialize). On TPU pods leave unset — workers "
    "auto-discover; --jax_init_distributed still opts in.")
flags.DEFINE_integer("jax_num_processes", None,
                     "Total process count for multi-host training.")
flags.DEFINE_integer("jax_process_id", None,
                     "This process's index for multi-host training.")
flags.DEFINE_bool(
    "jax_init_distributed", False,
    "Force jax.distributed.initialize() even without an explicit "
    "coordinator (TPU pod auto-discovery).")
flags.DEFINE_integer(
    "prometheus_port", None,
    "Start the telemetry/prometheus.py scrape endpoint on this port "
    "in THIS process before training (0 = ephemeral; the bound port "
    "is printed). Unset, the gin-backed default applies "
    "(`default_port.port` in telemetry.prometheus) — so scraping no "
    "longer requires caller-side wiring (docs/OBSERVABILITY.md).")
flags.DEFINE_enum(
    "trainer", "train_eval", ["train_eval", "qtopt", "fleet",
                              "anakin"],
    "Entry to run after gin parsing: the supervised "
    "train_eval_model() loop (default), the QT-Opt learner loop "
    "(train_qtopt — configs binding train_qtopt.*, e.g. "
    "research/qtopt/configs/qtopt_int8.gin), the multi-process "
    "learner/actor fleet (run_fleet — configs binding run_fleet.* / "
    "FleetConfig.*, e.g. research/qtopt/configs/qtopt_fleet.gin; "
    "docs/FLEET.md), or the fully-on-device Anakin online mode "
    "(train_anakin — configs binding train_anakin.*, e.g. "
    "research/qtopt/configs/qtopt_anakin.gin; docs/ENVS.md).")

# Configurable registration happens at import; pull in every in-tree
# family so configs can reference them without import lines.
_DEFAULT_MODULES = (
    "tensor2robot_tpu.models",
    "tensor2robot_tpu.data",
    "tensor2robot_tpu.preprocessors",
    "tensor2robot_tpu.export",
    "tensor2robot_tpu.predictors",
    "tensor2robot_tpu.hooks",
    "tensor2robot_tpu.meta_learning",
    "tensor2robot_tpu.fleet",
    "tensor2robot_tpu.envs",
    "tensor2robot_tpu.serving",
    "tensor2robot_tpu.research.grasp2vec",
    "tensor2robot_tpu.research.pose_env",
    "tensor2robot_tpu.research.qtopt",
    "tensor2robot_tpu.research.vrgripper",
)


def main(argv):
  del argv
  configs = [c for entry in FLAGS.gin_configs for c in entry.split(",")]
  if FLAGS.validate_only:
    # Fleet pre-flight: catch a typo'd binding in seconds instead of
    # minutes into a training run (docs/ANALYSIS.md). Runs BEFORE the
    # multi-host wiring — validation needs registrations, not devices,
    # and a lone pre-flight process must never block inside
    # jax.distributed.initialize waiting for peers that aren't there.
    import sys

    # Distributed pre-flight (ISSUE 20) runs FIRST and pure-AST —
    # before the configurable families (and therefore jax) load, and
    # long before any jax.distributed init: a typo'd rpc method or a
    # chief-gated collective fails here in a second instead of as a
    # wedged barrier minutes into a fleet spawn.
    from tensor2robot_tpu.analysis import cli as t2rcheck_cli

    dist_rc = t2rcheck_cli.main(["--checks", "fleet,spmd", "--quiet"])

    from tensor2robot_tpu.analysis import gin_check

    _import_configurable_families()
    findings = []
    for config in configs:
      resolved = gin.resolve_config_path(config) or config
      findings.extend(gin_check.validate_config_file(
          resolved, os.getcwd()))
    for finding in findings:
      print(finding.render())
    print(f"validate_only: {len(findings)} finding(s) in "
          f"{len(configs)} config(s)")
    sys.exit(1 if (findings or dist_rc) else 0)
  # Multi-host wiring comes first: jax.distributed must initialize
  # before any device use (SURVEY §3 "multi-slice via jax distributed
  # init"). Single-process runs no-op.
  from tensor2robot_tpu.parallel import maybe_initialize_distributed
  maybe_initialize_distributed(
      coordinator_address=FLAGS.jax_coordinator_address,
      num_processes=FLAGS.jax_num_processes,
      process_id=FLAGS.jax_process_id,
      force=FLAGS.jax_init_distributed,
  )
  _import_configurable_families()
  gin.parse_config_files_and_bindings(configs, FLAGS.gin_bindings)
  # Prometheus scrape endpoint (ISSUE 15): flag wins, else the
  # gin-backed default (telemetry.prometheus.default_port). Started
  # here so EVERY trainer entry — and the fleet orchestrator — serves
  # /metrics off its live registry with no caller-side wiring.
  from tensor2robot_tpu.telemetry import prometheus as prometheus_lib
  prometheus_port = FLAGS.prometheus_port
  if prometheus_port is None:
    prometheus_port = prometheus_lib.default_port()
  if prometheus_port is not None and prometheus_port >= 0:
    endpoint = prometheus_lib.serve(port=prometheus_port)
    print(f"prometheus: serving /metrics on port {endpoint.port}")
  if FLAGS.trainer == "qtopt":
    from tensor2robot_tpu.research.qtopt.train_qtopt import train_qtopt
    train_qtopt()
  elif FLAGS.trainer == "fleet":
    # The orchestrator re-runs these configs through --validate_only
    # as its pre-spawn launch gate (docs/FLEET.md).
    from tensor2robot_tpu.fleet import run_fleet
    run_fleet(gin_configs=configs)
  elif FLAGS.trainer == "anakin":
    from tensor2robot_tpu.envs import train_anakin
    train_anakin()
  else:
    train_eval.train_eval_model()


def _import_configurable_families() -> None:
  # Every in-tree family's dependencies are part of the pinned
  # installation (requirements.txt): one that fails to import is a
  # bug, and skipping it would only move the error to the first
  # config that names one of its configurables.
  for module in list(_DEFAULT_MODULES) + list(FLAGS.import_modules):
    importlib.import_module(module)


if __name__ == "__main__":
  app.run(main)
