"""t2rcheck CLI: `python -m tensor2robot_tpu.analysis`.

Exit codes: 0 clean (or everything suppressed/baselined), 1 new
findings, 2 usage/internal error. The `gin` family imports the
framework (and jax); `jax` / `concurrency` / `imports` are pure-AST
and run without importing any analyzed code — `scripts/lint.sh` runs
them first so a lint failure costs ~a second, not a jax import.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional, Sequence

from tensor2robot_tpu.analysis.findings import (
    Baseline,
    DEFAULT_BASELINE,
    FAMILIES,
    Finding,
    RULE_CATALOG,
    apply_pragmas,
)

_PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(
    __file__)))
REPO_ROOT = os.path.dirname(_PACKAGE_DIR)

# Default scan scope per family. The concurrency family covers the
# subsystems the lock-order graph is specified over (ISSUE 5;
# fleet added by ISSUE 8 — the orchestrator's process/thread
# lifecycle lands with zero pragmas, baseline stays empty;
# envs added by ISSUE 9 — pure functional code, so CON findings there
# would mean the purity contract broke;
# telemetry added by ISSUE 11 — the tracer/registry sit on RPC
# handlers and train loops from many threads, so a blocking-under-lock
# hazard there would stall the very paths it measures);
# jax covers the whole package (traced code lives everywhere: models,
# ops, parallel, research — and the envs family is scanned code by
# construction: envs ARE traced functions).
_JAX_PATHS = ("tensor2robot_tpu",)
_CONCURRENCY_PATHS = (
    "tensor2robot_tpu/replay",
    "tensor2robot_tpu/serving",
    "tensor2robot_tpu/data",
    "tensor2robot_tpu/startup",
    "tensor2robot_tpu/fleet",
    "tensor2robot_tpu/envs",
    "tensor2robot_tpu/telemetry",
    "tensor2robot_tpu/control",
)
_GIN_PATHS = ("tensor2robot_tpu",)
# obs (OBS501, ISSUE 15) scans the package's literal metric names
# against the docs/OBSERVABILITY.md catalog; the tests construct
# fixture names on purpose and are out of scope.
_OBS_PATHS = ("tensor2robot_tpu",)
# fleet (FLT5xx, ISSUE 20) resolves string-literal rpc sends against
# the union of handle() dispatchers — both live in fleet/ + serving/
# (tests dial fixture methods on purpose and are out of scope).
_FLEET_PATHS = (
    "tensor2robot_tpu/fleet",
    "tensor2robot_tpu/serving",
)
# spmd (SPMD601/JAX205, ISSUE 20) covers the whole package: chief
# gates live in train loops, import-time backend hazards anywhere in
# the entry binary's spawn closure.
_SPMD_PATHS = ("tensor2robot_tpu",)


def _resolve_paths(paths: Sequence[str], root: str) -> List[str]:
  return [p if os.path.isabs(p) else os.path.join(root, p)
          for p in paths]


def run_checks(checks: Sequence[str], root: str,
               paths: Optional[Sequence[str]] = None
               ) -> List[Finding]:
  """Raw findings (pragma/baseline filtering happens in main())."""
  findings: List[Finding] = []
  for family in checks:
    if family == "jax":
      from tensor2robot_tpu.analysis.jax_rules import run_jax_rules
      findings.extend(run_jax_rules(
          _resolve_paths(paths or _JAX_PATHS, root), root))
    elif family == "concurrency":
      from tensor2robot_tpu.analysis.concurrency_rules import (
          run_concurrency_rules,
      )
      findings.extend(run_concurrency_rules(
          _resolve_paths(paths or _CONCURRENCY_PATHS, root), root))
    elif family == "imports":
      from tensor2robot_tpu.analysis.import_rules import (
          run_import_rules,
      )
      findings.extend(run_import_rules(root))
    elif family == "obs":
      from tensor2robot_tpu.analysis.obs_rules import run_obs_rules
      findings.extend(run_obs_rules(
          _resolve_paths(paths or _OBS_PATHS, root), root))
    elif family == "fleet":
      from tensor2robot_tpu.analysis.fleet_rules import (
          run_fleet_rules,
      )
      findings.extend(run_fleet_rules(
          _resolve_paths(paths or _FLEET_PATHS, root), root))
    elif family == "spmd":
      from tensor2robot_tpu.analysis.spmd_rules import run_spmd_rules
      findings.extend(run_spmd_rules(
          _resolve_paths(paths or _SPMD_PATHS, root), root))
    elif family == "gin":
      from tensor2robot_tpu.analysis.gin_check import run_gin_rules
      findings.extend(run_gin_rules(
          _resolve_paths(paths or _GIN_PATHS, root), root))
    else:
      raise ValueError(f"unknown check family {family!r}; "
                       f"known: {', '.join(FAMILIES)}")
  return findings


def _list_rules() -> str:
  lines = ["rule     family       description",
           "-------  -----------  -----------"]
  for rule, (family, desc) in sorted(RULE_CATALOG.items()):
    lines.append(f"{rule:<7}  {family:<11}  {desc}")
  return "\n".join(lines)


def build_parser() -> argparse.ArgumentParser:
  parser = argparse.ArgumentParser(
      prog="python -m tensor2robot_tpu.analysis",
      description="t2rcheck: repo-native static analysis "
                  "(gin validator, JAX tracing-hazard linter, "
                  "concurrency/lifecycle linter).")
  parser.add_argument(
      "--checks", default="jax,concurrency,imports,obs,fleet,spmd,gin",
      help="comma-separated families to run "
           f"({','.join(FAMILIES)}); note `gin` imports the "
           "framework, the rest are pure-AST")
  parser.add_argument(
      "--paths", nargs="*", default=None,
      help="files/directories to scan (default: per-family repo "
           "defaults)")
  parser.add_argument(
      "--root", default=REPO_ROOT,
      help="repo root findings are reported relative to")
  parser.add_argument(
      "--baseline", default=None,
      help=f"baseline file (default: <root>/{DEFAULT_BASELINE})")
  parser.add_argument(
      "--write-baseline", action="store_true",
      help="write all current findings to the baseline and exit 0")
  parser.add_argument("--json", action="store_true",
                      help="machine-readable output")
  parser.add_argument("--quiet", action="store_true",
                      help="suppress the summary line on success")
  parser.add_argument("--list-rules", action="store_true")
  return parser


def main(argv: Optional[Sequence[str]] = None) -> int:
  args = build_parser().parse_args(argv)
  if args.list_rules:
    print(_list_rules())
    return 0
  root = os.path.abspath(args.root)
  checks = [c.strip() for c in args.checks.split(",") if c.strip()]
  try:
    raw = run_checks(checks, root, args.paths)
  except ValueError as e:
    print(f"t2rcheck: {e}", file=sys.stderr)
    return 2

  active, suppressed = apply_pragmas(raw, root)
  baseline_path = args.baseline or os.path.join(root, DEFAULT_BASELINE)
  if args.write_baseline:
    Baseline().write(baseline_path, active)
    print(f"t2rcheck: wrote {len(active)} finding(s) to "
          f"{baseline_path}")
    return 0
  try:
    baseline = Baseline.load(baseline_path)
  except (ValueError, json.JSONDecodeError) as e:
    print(f"t2rcheck: bad baseline {baseline_path!r}: {e}",
          file=sys.stderr)
    return 2
  new, baselined = baseline.split(active)

  if args.json:
    print(json.dumps({
        "checks": checks,
        "new": [f.as_dict() for f in new],
        "baselined": [f.as_dict() for f in baselined],
        "suppressed": [f.as_dict() for f in suppressed],
    }, indent=2))
  else:
    for finding in new:
      print(finding.render())
    summary = (f"t2rcheck [{','.join(checks)}]: "
               f"{len(new)} new finding(s), "
               f"{len(baselined)} baselined, "
               f"{len(suppressed)} pragma-suppressed")
    if new or not args.quiet:
      print(summary)
  return 1 if new else 0


if __name__ == "__main__":
  sys.exit(main())
