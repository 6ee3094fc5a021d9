"""Device time a step of everything that `lm_gdn_device_ms`,
`lm_attention_device_ms` and `lm_moe_device_ms` (in the
latent-attention family's cell `lm_mla_attention_device_ms`,
`lm_mla_projections_device_ms`, `lm_mla_moe_device_ms`) do not hold:
the two head losses, the dense FFN, the multi-token-prediction
module's own operations, the residuals and
norms under no scope (`other`), and what carries no `op_name` (Adam's
update, copies). With the rows a cell reports it adds up to the self
time a step of the whole executions of the K-step program (device
trace; `device_scopes.py`)."""

from benchmark.layer_metrics import device_scopes as ds


def read(run):
  n = ds.steps(run)
  if not n:
    return None
  named = [ds.scopes_ms(run, ds.GDN), ds.scopes_ms(run, ds.ATTENTION),
           ds.scopes_ms(run, ds.PROJECTIONS),
           ds.scopes_ms(run, ds.MOE, ds.MOE_KERNELS)]
  return 1e3 * run["trace"]["program_self_s"] / n \
      - sum(ms for ms in named if ms)
