"""`lm_moe_device_ms` under the windowed-attention family's name: the
families share `parallel/moe.py`, its scopes and so the reader; a
metric's name says whose cell reports it (`lm_swa_` this one's)."""

from benchmark.layer_metrics.lm_moe_device_ms import read  # noqa: F401
