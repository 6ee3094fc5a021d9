"""The instrumentation's own audit: the share of the window that no
span names, on the loop thread and on the feed thread; the larger of
the two. What is left is the loops' own bytecode between spans and
whatever a later change puts there without naming it."""

from benchmark.layer_metrics import span_window


def read(run):
  window = span_window.of_run(run)
  if window is None:
    return None
  return 100.0 * max(
      part["unnamed_s"] / part["seconds"]
      for part in (window["loop"], window["feed"]))
