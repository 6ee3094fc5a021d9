"""Share of the traced backward passes of the flash kernel that are
the one fused Pallas program (`ops/flash_attention._fused_bwd_kernel`:
a visited tile pair's scores, exponentials and dO V^T made once and
feeding dK, dV and dQ together) and not the pair of programs that make
them twice, from the program's two counters
`flash_attention.backward.fused_traces` and `.paired_traces`
(`ops/flash_attention._flash_bwd_impl` counts where it picks, by the
bytes a sequence's accumulators take in VMEM; the compiled step runs
what was traced). 100 where every attention layer's sequence fits, as
in the three language-model cells. None where the program has neither
counter (the parent) or traced no backward pass of the kernel (a CPU
run, whose mixers take materialised attention)."""


def read(run):
  from tensor2robot_tpu import telemetry

  counts = telemetry.registry().scalars("flash_attention.backward.")
  fused = counts.get("flash_attention.backward.fused_traces", 0.0)
  total = fused + counts.get("flash_attention.backward.paired_traces",
                             0.0)
  return 100.0 * fused / total if total else None
