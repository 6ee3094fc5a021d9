"""How long a save holds the loop: from the hook's `after_step` of a
checkpoint step to its `after_checkpoint` (the device-to-host copy of
the state and the hand-over to the async writer); median over the
saves that fell inside the window."""

import statistics


def read(run):
  stalls = run.get("checkpoint_stalls_ms")
  return statistics.median(stalls) if stalls else None
