"""`lm_moe_device_ms` under the latent-attention family's name: the two
families share `parallel/moe.py`, its scopes and so the reader; a
metric's name says whose cell reports it (`lm_` the hybrid family's,
`lm_mla_` this one's), as `lm_step_mfu` and `lm_mla_step_mfu` do."""

from benchmark.layer_metrics.lm_moe_device_ms import read  # noqa: F401
