"""Roofline share of the flash kernel at unequal widths (keys of
nope + rope over values of `v_head_dim`) in the latent-attention
layers: the `flash_attention` custom calls under `mla/attend` in the
whole executions of the K-step program against
`kda_lm_flops.attention_kernel_costs` (device trace;
`device_scopes.family`). A call covers the rows of a step on this chip,
all heads, `sequence_length` positions.

A call outside `transpose(` costs the forward program. What a call
inside it ran is read off the program's two counters
(`flash_attention.backward.fused_traces`, `.paired_traces`: the kernel
counts where it picks): where every traced backward pass is the fused
program, each call costs `backward`, the ONE program's five products a
pair; where every one is the pair, the calls cost `dkdv` and `dq` in
equal numbers. No reading where the counters are missing or both
count, or the pair's calls are odd."""

from benchmark.harness import kda_lm_flops
from benchmark.layer_metrics import device_scopes


def read(run):
  from tensor2robot_tpu import telemetry

  found = device_scopes.family(run, "mla/attend", "flash_attention")
  if not found:
    return None
  forward, back, measured = found
  counts = telemetry.registry().scalars("flash_attention.backward.")
  fused = counts.get("flash_attention.backward.fused_traces", 0.0)
  paired = counts.get("flash_attention.backward.paired_traces", 0.0)
  if bool(fused) == bool(paired) or (paired and round(back) % 2):
    return None
  kind, model = run["device_kind"], run["config"]["model"]
  costs = kda_lm_flops.attention_kernel_costs(
      model, run["batch"] / run["chips"], model["sequence_length"])
  least = {name: device_scopes.least_s(cost, kind)
           for name, cost in costs.items()}
  way_back = (back * least["backward"] if fused
              else back / 2 * (least["dkdv"] + least["dq"]))
  return 100.0 * (forward * least["forward"] + way_back) / measured
