"""`lm_other_device_ms` under the latent-attention family's name: what
`lm_mla_attention_device_ms`, `lm_mla_projections_device_ms` and
`lm_mla_moe_device_ms` do not hold (the two head losses, the dense FFN,
the multi-token-prediction module's own operations, what stands under
no scope), so that the cell's rows add up to the program's self time a
step. One reader: it takes off every family's rows, and a family's
absent scopes are nought."""

from benchmark.layer_metrics.lm_other_device_ms import read  # noqa: F401
