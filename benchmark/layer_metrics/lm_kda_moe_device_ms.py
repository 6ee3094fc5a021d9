"""`lm_moe_device_ms` under the channel-gated family's name: the
families share `parallel/moe.py`, its scopes and so the reader (`moe/*`
and XLA's `ragged-dot*` kernels by name)."""

from benchmark.layer_metrics.lm_moe_device_ms import read  # noqa: F401
