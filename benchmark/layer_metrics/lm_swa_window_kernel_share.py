"""Share of the traced calls of a sliding-window attention layer that
took the banded Pallas flash kernel (`ops/flash_attention.py`) and not
materialised attention under a band mask, from the program's two
counters `attention.window.kernel_traces` and `.materialised_traces`
(`layers/transformer.GatedAttention` counts where it picks its path;
the compiled step runs what was traced). 100 on a TPU, 0 on a CPU. None
where the program has neither counter."""


def read(run):
  from tensor2robot_tpu import telemetry

  counts = telemetry.registry().scalars("attention.window.")
  kernel = counts.get("attention.window.kernel_traces", 0.0)
  total = kernel + counts.get("attention.window.materialised_traces",
                              0.0)
  return 100.0 * kernel / total if total else None
