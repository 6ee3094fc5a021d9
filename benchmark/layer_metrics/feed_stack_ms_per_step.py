"""Feed thread, per Bellman step: the time inside `feed.stack` spans
(`np.stack` of a dispatch's K batches into one fresh array per key),
summed over the window's dispatches, over their steps."""

from benchmark.layer_metrics import span_window


def read(run):
  return span_window.total_ms_per_step(run, span_window.STACK)
