"""Device time of the Gated-DeltaNet mixers a step: the self time of
the operations under the scopes `gated_delta/scan` (the rule: a
chunk's preparation, the walk's two kernels, its recomputations) and
`gated_delta/conv`, in the whole executions of the K-step program
(device trace; `device_scopes.py`). None where the model has none."""

from benchmark.layer_metrics import device_scopes


def read(run):
  return device_scopes.scopes_ms(run, device_scopes.GDN)
