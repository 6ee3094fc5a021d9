"""How long a save holds the loop: median `loop.save` span (the
device-to-host copy of the state, the hand-over to the checkpoint
writer and the checkpoint hooks; not the log block before it) over the
window's saves."""

from benchmark.layer_metrics import span_window


def read(run):
  return span_window.median_ms(run, "loop.save")
