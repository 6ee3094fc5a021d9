"""Roofline share of the delta rule's walk over chunks
(`ops/delta_rule_walk.py`'s forward and backward Pallas programs): the
custom calls under `gated_delta/scan` in the whole executions of the
K-step program against `lm_flops.walk_kernel_costs` (device trace;
`device_scopes.py`). The HBM bounds both programs.

What one call covers, from `layers/gated_delta.py`: `GatedDeltaNet`
maps `gated_delta_rule` over the rows of the batch (`jax.lax.map`,
each row under its own `jax.checkpoint`), so a call walks ONE row: all
`linear_num_value_heads` heads, `sequence_length / 64` chunks. Which
program a call ran is read off its `tf_op`: inside `transpose(` and
outside `rematted_computation` it is the backward program; every other
call is the forward program. The gradient rule (`_walk.defvjp`,
`optimize_remat`) runs the forward program that writes the states once
for every backward call, and the one that does not for the rest (a
block's forward pass and its recomputation), so of F forward calls and
B backward calls B cost `forward_saving_states` and F - B `forward`."""

from benchmark.harness import lm_flops
from benchmark.layer_metrics import device_scopes


def read(run):
  found = device_scopes.family(run, "gated_delta/scan")
  if not found:
    return None
  forward, back, measured = found
  if back > forward:
    return None  # not this gradient rule's calls
  model = run["config"]["model"]
  costs = lm_flops.walk_kernel_costs(model, 1, model["sequence_length"])
  least = {name: device_scopes.least_s(cost, run["device_kind"])
           for name, cost in costs.items()}
  return 100.0 * ((forward - back) * least["forward"]
                  + back * (least["forward_saving_states"]
                            + least["backward"])) / measured
