"""Share of the traced calls of the gated delta rule whose
undifferentiated evaluation (a forward pass that no backward pass
follows: a checkpointed row's first pass, an evaluation) is the fused
Pallas program (`ops/delta_rule_fused.py`: a chunk's operands built in
VMEM, the walk's step and `within @ new` in one kernel) and not the
preparation in XLA with the walk after it, from the program's two
counters `gated_delta.forward.fused_traces` and `.prepared_traces`
(`layers/gated_delta.gated_delta_rule` counts where it picks that
program; the compiled step runs what was traced). 100 on a TPU at
widths that tile, 0 on a CPU or at widths that do not. None where the
program has neither counter."""


def read(run):
  from tensor2robot_tpu import telemetry

  counts = telemetry.registry().scalars("gated_delta.forward.")
  fused = counts.get("gated_delta.forward.fused_traces", 0.0)
  total = fused + counts.get("gated_delta.forward.prepared_traces", 0.0)
  return 100.0 * fused / total if total else None
