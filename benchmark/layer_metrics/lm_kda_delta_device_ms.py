"""`lm_gdn_device_ms` under the channel-gated family's name: Kimi Delta
Attention runs under `GatedDeltaNet`'s two scopes, `gated_delta/scan`
(the gates, a chunk's preparation with its reference points, the walk's
two kernels, the norm, their recomputations) and `gated_delta/conv`
(the three projections' convolutions), so the reader is one; a metric's
name says whose cell reports it."""

from benchmark.layer_metrics.lm_gdn_device_ms import read  # noqa: F401
