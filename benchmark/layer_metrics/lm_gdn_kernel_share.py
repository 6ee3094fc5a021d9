"""Share of the traced calls of the gated delta rule whose walk over
chunks took the Pallas kernel pair (`ops/delta_rule_walk.py`) and not
the `lax.scan`, from the program's two counters
`gated_delta.walk.kernel_traces` and `.scan_traces`
(`layers/gated_delta.gated_delta_rule` counts where it picks its path;
the compiled step runs what was traced). 100 on a TPU at widths that
tile, 0 on a CPU or at widths that do not. None where the program has
neither counter."""


def read(run):
  from tensor2robot_tpu import telemetry

  counts = telemetry.registry().scalars("gated_delta.walk.")
  kernel = counts.get("gated_delta.walk.kernel_traces", 0.0)
  total = kernel + counts.get("gated_delta.walk.scan_traces", 0.0)
  return 100.0 * kernel / total if total else None
