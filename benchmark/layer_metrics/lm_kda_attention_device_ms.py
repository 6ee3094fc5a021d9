"""Device time of the latent-attention layers' mixers a step: the self
time of the operations under `mla/attend` (the concatenation of the
keys' two parts and the flash kernel) and under the three projections'
scopes (`mla/q_proj`: here one projection with no latent; `mla/kv_proj`;
`mla/o_proj`), in the whole executions of the K-step program (device
trace; `device_scopes.py`). One row for the mixer, as
`lm_kda_delta_device_ms` is one for the delta rule's. None where the
model has none."""

from benchmark.layer_metrics import device_scopes


def read(run):
  return device_scopes.scopes_ms(
      run, ("mla/attend",) + device_scopes.PROJECTIONS)
