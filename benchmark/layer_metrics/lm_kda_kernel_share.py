"""Share of the traced calls of the delta rule with a decay per key
channel whose walk over chunks took the Pallas kernel pair
(`ops/delta_rule_walk.py`, the state's rows scaled) and not the
`lax.scan`, from the program's two counters
`gated_delta.channel_gate.kernel_traces` and `.scan_traces`
(`layers/gated_delta._prepared_rule` counts where it picks its path;
the compiled step runs what was traced). 100 on a TPU at widths that
tile, 0 on a CPU or at widths that do not. None where the program has
neither counter (the parent), or traced no such call."""


def read(run):
  from tensor2robot_tpu import telemetry

  counts = telemetry.registry().scalars("gated_delta.channel_gate.")
  kernel = counts.get("gated_delta.channel_gate.kernel_traces", 0.0)
  total = kernel + counts.get("gated_delta.channel_gate.scan_traces",
                              0.0)
  return 100.0 * kernel / total if total else None
