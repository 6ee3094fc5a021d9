"""Device time of one Bellman step: the union of the operations'
intervals inside the traced executions of the K-step program, over the
steps in them (profiler trace, not the host clock)."""


def read(run):
  trace = run.get("trace")
  if not trace or not trace["program_runs"]:
    return None
  steps = trace["program_runs"] * run["k"]
  return 1e3 * trace["program_busy_s"] / steps
