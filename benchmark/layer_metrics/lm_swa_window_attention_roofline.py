"""Roofline share of the banded flash kernel on the sliding-window
layers (64 query heads over 8 key-value heads, a band of
`sliding_window`): the `flash_attention` custom calls of those layers
in the whole executions of the K-step program against
`swa_lm_flops.window_kernel_costs`, the products over the band's pairs
alone and the least bytes (device trace;
`device_scopes.flash_roofline`). The calls stand under
`window_attention` once the scope list has it and under `other` until
then (`swa_scopes.py`): no other layer's kernel stands there."""

from benchmark.harness import swa_lm_flops
from benchmark.layer_metrics import device_scopes, swa_scopes


def read(run):
  for scope in swa_scopes.WINDOW_KERNELS:
    share = device_scopes.flash_roofline(
        run, scope, swa_lm_flops.window_kernel_costs)
    if share is not None:
      return share
  return None
