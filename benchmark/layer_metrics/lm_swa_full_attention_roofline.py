"""Roofline share of the causal flash kernel on the full-attention
layers (48 query heads over 8 key-value heads, read unrepeated): the
`flash_attention` custom calls under `gated_attention` in the whole
executions of the K-step program against
`swa_lm_flops.attention_kernel_costs` (device trace;
`device_scopes.flash_roofline`). A call covers the rows of a step on
this chip, all heads, `sequence_length` positions."""

from benchmark.harness import swa_lm_flops
from benchmark.layer_metrics import device_scopes, swa_scopes


def read(run):
  return device_scopes.flash_roofline(
      run, swa_scopes.FULL[0], swa_lm_flops.attention_kernel_costs)
