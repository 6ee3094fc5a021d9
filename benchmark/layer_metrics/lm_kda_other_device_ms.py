"""`lm_other_device_ms` under the channel-gated family's name: what
`lm_kda_delta_device_ms`, `lm_kda_attention_device_ms` and
`lm_kda_moe_device_ms` do not hold (the head's loss, the dense FFN, the
blocks' norms and residual adds, the embedding's gather, what stands
under no scope: Adam's update, copies), so that the cell's four rows
add up to the program's self time a step. One reader: it takes off
every family's rows (the delta rule's two scopes, `mla/attend`, the
three projections, the expert layer's), and a family's absent scopes
are nought."""

from benchmark.layer_metrics.lm_other_device_ms import read  # noqa: F401
