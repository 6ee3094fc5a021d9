#!/usr/bin/env bash
# Tier-1 verify: the ROADMAP.md command VERBATIM (same log path, same
# DOTS_PASSED accounting the driver greps), then every bench axis's
# --dry-run smoke. All of it runs on the CPU (JAX_PLATFORMS=cpu) and
# checks correctness and counters only; no number from here is a
# device metric, and the non---dry-run bench refuses to start without
# a TPU. The chip is exercised by `python chip_smoke.py` (README
# "Running"); `python chip_smoke.py --rehearse-cpu` rehearses it here.
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

# The serving smoke carries the ISSUE-13 multi-tenant front leg next
# to the classic closed-loop one: a tiny open-loop (Poisson) point
# through the ServingFront, the overload check (admission MUST shed
# the over-limit tenant or the smoke fails), and the arena
# eviction→reload gate (a reload that RECOMPILES — cache_misses != 0
# — fails the smoke).
echo "--- serving bench smoke (bench.py --serving --dry-run; front/open-loop leg) ---"
env JAX_PLATFORMS=cpu python bench.py --serving --dry-run
smoke_rc=$?

echo "--- coldstart bench smoke (bench.py --coldstart --dry-run) ---"
env JAX_PLATFORMS=cpu python bench.py --coldstart --dry-run
coldstart_rc=$?

echo "--- replay bench smoke (bench.py --replay --dry-run) ---"
env JAX_PLATFORMS=cpu python bench.py --replay --dry-run
replay_rc=$?

echo "--- input bench smoke (bench.py --input --dry-run) ---"
env JAX_PLATFORMS=cpu python bench.py --input --dry-run
input_rc=$?

echo "--- mfu bench smoke (bench.py --mfu --dry-run) ---"
env JAX_PLATFORMS=cpu python bench.py --mfu --dry-run
mfu_rc=$?

echo "--- fleet bench smoke (bench.py --fleet --dry-run) ---"
env JAX_PLATFORMS=cpu python bench.py --fleet --dry-run
fleet_rc=$?

# The envs smoke includes the pod device-scaling leg: a REAL (tiny)
# 2-virtual-device pmap'd collect-and-learn training next to the PR-9
# single-device program (ISSUE 10), plus the jit+shard_map pod
# program on the rules seam with the ZeRO update sharded over the
# pod axis (ISSUE 12) head-to-head on the same 2-device mesh.
echo "--- envs bench smoke (bench.py --envs --dry-run; 2-device pod legs: pmap + shard_map) ---"
env JAX_PLATFORMS=cpu python bench.py --envs --dry-run
envs_rc=$?

# The telemetry smoke is the ISSUE-11 trace-merge gate: a REAL (tiny)
# 2-actor fleet runs with the telemetry plane on, every process's
# trace merges into one timeline, and the smoke FAILS unless spans
# from the learner, the host, and both actors are present; the
# tracing-overhead A/B probe rides along (now with the ISSUE-15
# sampler + sentinel on in the ON arm). The sentinel legs ride too:
# the quiet fleet must fire ZERO alerts and a second fleet with an
# injected slow_host stall must fire exactly one page alert train
# with flight records attached.
echo "--- telemetry smoke (bench.py --telemetry --dry-run; trace merge + sentinel) ---"
env JAX_PLATFORMS=cpu python bench.py --telemetry --dry-run
telemetry_rc=$?

# The run-report tool (ISSUE 15) must stay able to fold a run dir —
# the committed artifacts/telemetry/ merged trace is the fixture; a
# report with zero renderable sections exits nonzero.
echo "--- telemetry report smoke (python -m tensor2robot_tpu.telemetry.report) ---"
env JAX_PLATFORMS=cpu python -m tensor2robot_tpu.telemetry.report \
  --run-dir artifacts/telemetry --out /tmp/_t1_report.md > /dev/null
report_rc=$?

# The chaos smoke is the ISSUE-14 recovery gate: a REAL (tiny)
# 2-actor fleet runs the full seeded 7-class fault schedule through
# the production rpc/actor/learner seams — actor crash mid-episode,
# actor hang, learner crash under the resume policy, RPC drop/delay,
# host stall/forced disconnect, plus an elastic scale_to leg — and
# the smoke FAILS unless every class recovers, zero partial rows
# land, and the resumed learner reaches its exact final step.
echo "--- chaos smoke (bench.py --chaos --dry-run; recovery gates) ---"
env JAX_PLATFORMS=cpu python bench.py --chaos --dry-run
chaos_rc=$?

# The control smoke is the ISSUE-18 closed-loop gate: a live
# Controller over a real TCP front tier must actuate a scale-up off a
# breaching p95 through the production actuator adapters at a
# replica-seconds integral below static max-provisioning, every
# decision record must validate against the envelope schema, and a
# hard-killed front of a real fleet must auto-respawn and rejoin the
# router via mark_alive with no manual step and no unremediated page.
echo "--- control smoke (bench.py --control --dry-run; closed-loop gates) ---"
env JAX_PLATFORMS=cpu python bench.py --control --dry-run
control_rc=$?

if [ "$rc" -ne 0 ]; then exit "$rc"; fi
if [ "$smoke_rc" -ne 0 ]; then exit "$smoke_rc"; fi
if [ "$coldstart_rc" -ne 0 ]; then exit "$coldstart_rc"; fi
if [ "$replay_rc" -ne 0 ]; then exit "$replay_rc"; fi
if [ "$input_rc" -ne 0 ]; then exit "$input_rc"; fi
if [ "$mfu_rc" -ne 0 ]; then exit "$mfu_rc"; fi
if [ "$fleet_rc" -ne 0 ]; then exit "$fleet_rc"; fi
if [ "$envs_rc" -ne 0 ]; then exit "$envs_rc"; fi
if [ "$telemetry_rc" -ne 0 ]; then exit "$telemetry_rc"; fi
if [ "$report_rc" -ne 0 ]; then exit "$report_rc"; fi
if [ "$chaos_rc" -ne 0 ]; then exit "$chaos_rc"; fi
exit "$control_rc"
