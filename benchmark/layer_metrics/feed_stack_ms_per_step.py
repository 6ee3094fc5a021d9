"""Feed thread, per Bellman step: the time inside `feed.stack` spans
(`np.stack` of a dispatch's K batches, leaf by leaf, into a slot of
the stream's ring of reused host buffers, or into fresh arrays where
the ring is not in use), summed over the window's dispatches, over
their steps."""

from benchmark.layer_metrics import span_window


def read(run):
  return span_window.total_ms_per_step(run, span_window.STACK)
