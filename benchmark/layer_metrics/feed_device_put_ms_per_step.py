"""Feed thread, per Bellman step: the time inside `feed.device_put`
spans (`device_put_batch` of a stacked dispatch as the feed thread
lives it: the call, which may return before the bytes are on the
device), summed over the window's dispatches, over their steps."""

from benchmark.layer_metrics import span_window


def read(run):
  return span_window.total_ms_per_step(run, span_window.DEVICE_PUT)
