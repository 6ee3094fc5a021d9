"""Roofline share of the flash kernel at equal widths under grouped
queries: the `flash_attention` custom calls under `gated_attention` in
the whole executions of the K-step program against
`lm_flops.attention_kernel_costs` (device trace;
`device_scopes.flash_roofline`). A call covers the rows of a step on
this chip, all query heads, `sequence_length` positions. The FLOP peak
bounds all three programs at the cell's widths."""

from benchmark.harness import lm_flops
from benchmark.layer_metrics import device_scopes


def read(run):
  return device_scopes.flash_roofline(
      run, "gated_attention", lm_flops.attention_kernel_costs)
