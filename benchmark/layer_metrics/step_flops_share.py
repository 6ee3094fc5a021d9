"""Model FLOPs of one step over what the chips could do in the step's
device time at the bf16 peak. A share of the FLOP peak, not a roofline
share: the hot fusions of this step are bound by HBM, not by the MXU."""

from benchmark.harness import flops, peaks
from benchmark.layer_metrics import step_device_ms


def read(run):
  ms = step_device_ms.read(run)
  if not ms:
    return None
  need = flops.qtopt_step_flops(run["config"], run["batch"])
  can = ms / 1e3 * peaks.peak(run["device_kind"], "bf16_flops") \
      * run["chips"]
  return 100.0 * need / can
