"""Device time a step of the full-attention layers' attention
(projections, norms, YaRN's rotary, the causal flash kernel's three
programs, the gate and `o_proj`): the self time of the operations
under `gated_attention` in the whole executions of the K-step program
(device trace; `device_scopes.py`)."""

from benchmark.layer_metrics import device_scopes, swa_scopes


def read(run):
  return device_scopes.scopes_ms(run, swa_scopes.FULL)
