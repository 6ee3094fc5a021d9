"""What the readers of device time by named scope share: milliseconds
a step under a set of scopes, and a kernel family's share of its
roofline, from the two tables that `harness/trace_reduce.py` keeps of
the whole executions of the timed program (`scope_ns`, `kernels`:
each operation at the median of its occurrences there).

A time and not a share of the step: a share moves when another layer
does. A roofline share is, over all the calls of a family in those
executions, the least time the chip could take for them (a call's
`max(flops / peak FLOP/s, bytes / peak bytes/s)` from the benchmark's
own cost function, which counts the least the mathematics needs: over
100 % means a wrong count, not a fast kernel) over the self time the
trace measured."""

import sys

from benchmark.harness import peaks

GDN = ("gated_delta/scan", "gated_delta/conv")
ATTENTION = ("gated_attention", "mla/attend")  # a family has one of them
PROJECTIONS = ("mla/q_proj", "mla/kv_proj", "mla/o_proj")
MOE = ("moe/route", "moe/experts", "moe/shared")
# XLA's grouped products (`lax.ragged_dot`: the custom calls
# `ragged-dot-none` and `ragged-dot-metadata`) carry their own name for
# a `tf_op`, so no scope holds them: the expert layer's time adds them
# by name.
MOE_KERNELS = "ragged-dot"
PALLAS = "pallas_call"  # the `tf_op`'s last component of a Pallas program


def steps(run):
  """Train steps in the whole executions the recording holds, or None
  (untraced, or it holds none whole)."""
  trace = run.get("trace")
  if not trace or not trace.get("program_runs") \
      or "scope_ns" not in trace:
    return None
  return trace["program_runs"] * run["k"]


def scopes_ms(run, scopes, kernels_named=None):
  """Self time a step of the operations under `scopes` (and of the
  kernels under none of them whose name starts with `kernels_named`),
  in ms; None where there is no whole execution or none of it occurs."""
  n = steps(run)
  if not n:
    return None
  trace = run["trace"]
  found = [sum(trace["scope_ns"][scope].values())
           for scope in scopes if scope in trace["scope_ns"]]
  if kernels_named:
    found += [call["ns"] for call in trace["kernels"]
              if call["name"].startswith(kernels_named)
              and call["scope"] not in scopes]
  return sum(found) / 1e6 / n if found else None


def family(run, scope, name=None):
  """(calls on the way forward, recomputations among them; calls on
  the way back; their self time in s) of the Pallas programs under
  `scope` (of that `name`, or any), or None."""
  if not steps(run):
    return None
  calls = [call for call in run["trace"]["kernels"]
           if call["scope"] == scope and call["primitive"] == PALLAS
           and name in (None, call["name"])]
  if not calls:
    return None
  back = sum(call["calls"] for call in calls
             if call["pass"] == "backward")
  return (sum(call["calls"] for call in calls) - back, back,
          sum(call["ns"] for call in calls) / 1e9)


def least_s(cost, device_kind):
  """The least time one call of `cost` ({"flops", "bytes"}) takes."""
  return max(cost["flops"] / peaks.peak(device_kind, "bf16_flops"),
             cost["bytes"] / peaks.peak(device_kind, "hbm_bytes_per_s"))


def flash_roofline(run, scope, attention_kernel_costs):
  """The roofline share of the `flash_attention` calls under `scope`
  against a family's `attention_kernel_costs(model, rows, positions)`:
  one call's cost of each of the kernel's three programs, a call
  covering the rows of a step on this chip, all heads and
  `sequence_length` positions. Which program a call ran is read off its
  `tf_op`: outside `transpose(` the forward program (recomputations
  too), inside it the dK/dV and the dQ program in equal numbers, since
  the gradient rule calls both; an odd number is no reading."""
  found = family(run, scope, "flash_attention")
  if not found:
    return None
  forward, back, measured = found
  if round(back) % 2:
    print(f"{scope}: {back} flash_attention calls on the way back, "
          "not pairs of dK/dV and dQ: no roofline share", file=sys.stderr)
    return None
  kind, model = run["device_kind"], run["config"]["model"]
  costs = attention_kernel_costs(model, run["batch"] / run["chips"],
                                 model["sequence_length"])
  least = forward * least_s(costs["forward"], kind) + back / 2 * (
      least_s(costs["dkdv"], kind) + least_s(costs["dq"], kind))
  return 100.0 * least / measured
