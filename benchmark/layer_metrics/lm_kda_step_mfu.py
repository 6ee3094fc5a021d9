"""Model FLOPs of the channel-gated delta-rule language model's
training step (`harness/kda_lm_flops.py`: the benchmark's own count,
backward twice the forward, no recomputation, the routed experts as the
run's own counter says they were routed) over what the chips could do
at the bf16 peak in the device time of the whole executions of the
K-step program that the recording holds: `lm_step_mfu` for this family.
A share of the FLOP peak of the whole step."""

from benchmark.harness import kda_lm_flops, peaks
from benchmark.layer_metrics import step_device_ms


def read(run):
  ms = step_device_ms.read(run)
  if not ms:
    return None
  shares = [rec["moe.assignments_here_share"] for rec in run["records"]
            if "moe.assignments_here_share" in rec]
  need = kda_lm_flops.step_flops(
      run["config"]["model"], run["batch"],
      sum(shares) / len(shares) if shares else None)
  can = ms / 1e3 * peaks.peak(run["device_kind"], "bf16_flops") \
      * run["chips"]
  return 100.0 * need / can
