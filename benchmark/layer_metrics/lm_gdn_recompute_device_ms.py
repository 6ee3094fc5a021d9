"""Device time a step that the Gated-DeltaNet rule spends running its
forward pass again on the way back: the self time of the operations
under the scope `gated_delta/scan` that stand under a checkpoint's
`rematted_computation` (the block's checkpoint and each row's own), in
the whole executions of the K-step program (device trace;
`device_scopes.py`, `trace_reduce.pass_of`). None where the model has
no such scope."""

from benchmark.layer_metrics import device_scopes


def read(run):
  n = device_scopes.steps(run)
  passes = n and run["trace"]["scope_ns"].get("gated_delta/scan")
  return passes["recompute"] / 1e6 / n if passes else None
