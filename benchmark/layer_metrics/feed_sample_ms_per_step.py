"""Feed thread, per Bellman step: the time inside `feed.sample` spans
(each pull of one batch from the replay stream: the index draw, the
gather of the rows and the sampler's book-keeping), summed over the
window's dispatches, over their steps."""

from benchmark.layer_metrics import span_window


def read(run):
  return span_window.total_ms_per_step(run, span_window.SAMPLE)
