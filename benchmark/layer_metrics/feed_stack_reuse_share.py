"""Share of the dispatches `StackedBatchStream` stacked over the run
that went into its ring of reused host buffers, from the program's two
counters `feed.stack.reused_dispatches` and `.fresh_dispatches`: under
100 where the consumer did not lend the buffers (placement that may
alias host memory) or a dispatch's shapes were not the ring's. None
where the program has neither counter."""


def read(run):
  from tensor2robot_tpu import telemetry

  counts = telemetry.registry().scalars("feed.stack.")
  reused = counts.get("feed.stack.reused_dispatches", 0.0)
  total = reused + counts.get("feed.stack.fresh_dispatches", 0.0)
  return 100.0 * reused / total if total else None
