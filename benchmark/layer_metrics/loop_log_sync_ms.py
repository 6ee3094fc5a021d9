"""How long the loop's log block waits for the device: median
`loop.log_sync` span (`jax.device_get` of the metrics of the dispatch
enqueued a moment before) over the window's log steps."""

from benchmark.layer_metrics import span_window


def read(run):
  return span_window.median_ms(run, "loop.log_sync")
