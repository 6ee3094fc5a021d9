"""Share of the feed thread's time in the window spent inside
`feed.queue_put` spans, the bounded put into the prefetcher's queue:
0 while the feed sets the pace (the loop takes each dispatch as it
comes), and above 0 once the feed is ahead of the loop."""

from benchmark.layer_metrics import span_window


def read(run):
  window = span_window.of_run(run)
  if window is None:
    return None
  blocked = sum(s["dur"]
                for s in window["spans"][span_window.QUEUE_PUT])
  return 100.0 * blocked / window["feed"]["seconds"]
