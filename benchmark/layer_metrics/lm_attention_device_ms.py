"""Device time of gated softmax attention a step: the self time of the
operations under `gated_attention` (the hybrid model's: projections,
norms, rotary, the flash kernel, the gate) in the whole executions of
the K-step program (device trace; `device_scopes.py`). None where the
model has none (the latent-attention family's is
`lm_mla_attention_device_ms`)."""

from benchmark.layer_metrics import device_scopes


def read(run):
  return device_scopes.scopes_ms(run, ("gated_attention",))
