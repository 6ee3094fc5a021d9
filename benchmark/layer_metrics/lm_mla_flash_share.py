"""Share of the traced calls of the latent-attention mixer that took
the Pallas flash kernel (`ops/flash_attention.py`, keys wider than
values) and not materialised attention, from the program's two counters
`mla.attend.kernel_traces` and `.materialised_traces`
(`layers/transformer.LatentAttention` counts where it picks its path;
the compiled step runs what was traced). 100 on a TPU, 0 on a CPU. None
where the program has neither counter."""


def read(run):
  from tensor2robot_tpu import telemetry

  counts = telemetry.registry().scalars("mla.attend.")
  kernel = counts.get("mla.attend.kernel_traces", 0.0)
  total = kernel + counts.get("mla.attend.materialised_traces", 0.0)
  return 100.0 * kernel / total if total else None
