"""Device time of latent attention's kernel a step: the self time of
the operations under `mla/attend` (the concatenation of the keys' two
parts and the flash kernel; the projections are
`lm_mla_projections_device_ms`) in the whole executions of the K-step
program (device trace; `device_scopes.py`). None where the model has
none (the hybrid family's is `lm_attention_device_ms`)."""

from benchmark.layer_metrics import device_scopes


def read(run):
  return device_scopes.scopes_ms(run, ("mla/attend",))
