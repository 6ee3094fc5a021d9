"""Share of the batches of `StackedBatchStream`'s ring dispatches over
the run that their source gathered straight into their slice of the
ring slot, from the program's two counters
`feed.gather.in_place_batches` and `.copied_batches`: the rest were
copied there by the stack. Under 100 by the K batches of the first
dispatch, which gives the ring its shapes, and further where the
source does not take a destination or a dispatch's shapes were not the
ring's. None where the program has neither counter."""


def read(run):
  from tensor2robot_tpu import telemetry

  counts = telemetry.registry().scalars("feed.gather.")
  in_place = counts.get("feed.gather.in_place_batches", 0.0)
  total = in_place + counts.get("feed.gather.copied_batches", 0.0)
  return 100.0 * in_place / total if total else None
