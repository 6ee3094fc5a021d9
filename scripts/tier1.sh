#!/usr/bin/env bash
# Tier-1 verify: the lint gate, the ROADMAP.md pytest command VERBATIM
# (same log path, same DOTS_PASSED accounting the driver greps), then
# the run-report smoke over the committed trace. All of it runs on the
# CPU (JAX_PLATFORMS=cpu) and checks correctness and counters only; no
# number from here is a device metric. The chip is measured by
# `python3 benchmark/run.py --workload <cell>` and exercised by
# `python chip_smoke.py` (README "Running"); `python chip_smoke.py
# --rehearse-cpu` rehearses the latter here.
#
# Usage: scripts/tier1.sh   (from the repo root)
set -u
cd "$(dirname "$0")/.."

# Static analysis FIRST: a gin typo or a concurrency hazard fails in
# seconds here instead of minutes into the pytest run (ISSUE 5).
echo "--- t2rcheck static analysis (scripts/lint.sh) ---"
scripts/lint.sh
lint_rc=$?
if [ "$lint_rc" -ne 0 ]; then exit "$lint_rc"; fi

set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)

# The run-report tool (ISSUE 15) must stay able to fold a run dir —
# the committed artifacts/telemetry/ merged trace is the fixture; a
# report with zero renderable sections exits nonzero.
echo "--- telemetry report smoke (python -m tensor2robot_tpu.telemetry.report) ---"
env JAX_PLATFORMS=cpu python -m tensor2robot_tpu.telemetry.report \
  --run-dir artifacts/telemetry --out /tmp/_t1_report.md > /dev/null
report_rc=$?

if [ "$rc" -ne 0 ]; then exit "$rc"; fi
exit "$report_rc"
