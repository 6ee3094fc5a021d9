"""Share of the run's dispatches that the train loop enqueued while the
after-work of the one before (its hooks, its log with the wait for the
device, its save) was still owed, from the program's two counters
`loop.dispatches.ran_ahead` and `.drained`: the device had the next
program queued when that one ended. (n - 1)/n over a run's n
dispatches where the loop runs ahead (the first has none before it),
0 where it finishes each dispatch before the next (a trainer with work
between dispatches on the live state, hooks that drive online
collection). None where the program has neither counter."""


def read(run):
  from tensor2robot_tpu import telemetry

  counts = telemetry.registry().scalars("loop.dispatches.")
  ahead = counts.get("loop.dispatches.ran_ahead", 0.0)
  total = ahead + counts.get("loop.dispatches.drained", 0.0)
  return 100.0 * ahead / total if total else None
