"""Roofline share of the flash kernel at unequal widths (keys of
nope + rope over values of `v_head_dim`): the `flash_attention` custom
calls under `mla/attend` in the whole executions of the K-step program
against `mla_lm_flops.attention_kernel_costs` (device trace;
`device_scopes.flash_roofline`). A call covers the rows of a step on
this chip, all heads, `sequence_length` positions (the module's call
has one position fewer, 0.02 % of its pairs). The FLOP peak bounds all
three programs at the cell's widths."""

from benchmark.harness import mla_lm_flops
from benchmark.layer_metrics import device_scopes


def read(run):
  return device_scopes.flash_roofline(
      run, "mla/attend", mla_lm_flops.attention_kernel_costs)
