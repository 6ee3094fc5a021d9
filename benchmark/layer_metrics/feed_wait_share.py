"""Share of the window the loop spent blocked on the prefetcher: the
loop's own `input_wait_fraction` (wall in the prefetcher's `__next__`
per log interval), weighted by each interval's length."""


def read(run):
  waited = total = 0.0
  for rec in run["records"]:
    if "input_wait_fraction" not in rec or not rec.get(
        "grad_steps_per_sec"):
      continue
    seconds = run["config"]["train"]["log_every_steps"] \
        / rec["grad_steps_per_sec"]
    waited += rec["input_wait_fraction"] * seconds
    total += seconds
  return 100.0 * waited / total if total else None
