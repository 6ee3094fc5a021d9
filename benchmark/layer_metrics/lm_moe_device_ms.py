"""Device time of the expert layers a step: the self time of the
operations under `moe/route`, `moe/experts` and `moe/shared`, and of
XLA's `ragged-dot*` custom calls, the grouped products, which carry no
`op_name` and are found by name, in the whole executions of the K-step
program (device trace; `device_scopes.py`)."""

from benchmark.layer_metrics import device_scopes


def read(run):
  return device_scopes.scopes_ms(run, device_scopes.MOE,
                                 device_scopes.MOE_KERNELS)
