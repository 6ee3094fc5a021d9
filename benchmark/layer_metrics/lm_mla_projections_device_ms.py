"""Device time of latent attention's low-rank projections a step: the
self time of the operations under `mla/q_proj`, `mla/kv_proj` and
`mla/o_proj` in the whole executions of the K-step program (device
trace; `device_scopes.py`). None where the model has none."""

from benchmark.layer_metrics import device_scopes


def read(run):
  return device_scopes.scopes_ms(run, device_scopes.PROJECTIONS)
