"""`lm_gdn_recompute_device_ms` under the channel-gated family's name:
of `gated_delta/scan`, the self time a step that stands under a
checkpoint's `rematted_computation` (the block's checkpoint and each
row's own). One reader: the scope is the same."""

from benchmark.layer_metrics.lm_gdn_recompute_device_ms import (  # noqa: F401
    read,
)
