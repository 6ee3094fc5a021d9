"""Share of the trunk's checkpointed blocks whose checkpoint keeps what
the flash kernel returned (its output and logsumexp, the residuals of
its backward), so that the backward pass does not run the forward
kernel a second time, from the program's two counters
`trunk.checkpoint.attention_saved_blocks` and `.recomputed_blocks`
(`layers/transformer.apply_block` counts each traced block by its
checkpoint's policy; the compiled step runs what was traced). 100 where
every block runs under `remat_policy = "save_attention"`, 0 under
`"full"`. None where the program has neither counter."""


def read(run):
  from tensor2robot_tpu import telemetry

  counts = telemetry.registry().scalars("trunk.checkpoint.")
  saved = counts.get("trunk.checkpoint.attention_saved_blocks", 0.0)
  total = saved + counts.get("trunk.checkpoint.recomputed_blocks", 0.0)
  return 100.0 * saved / total if total else None
