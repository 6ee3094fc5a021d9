"""`lm_gdn_recompute_device_ms` (ISSUE 41): the recomputation's share
of `gated_delta/scan` in ms a step, on a made-up hybrid step and on
PR 40's recording of a TPU v5e (`data/v5e_scopes.xplane.pb`), whose
one scope under a checkpoint stands in for the rule's."""

import pytest

from benchmark.harness import trace_reduce as tr
from benchmark.layer_metrics import lm_gdn_recompute_device_ms
from benchmark.tests.test_trace_reduce import (  # noqa: F401
    STEP,
    _run,
    _scoped_recording,
    recorded_scopes,
)

read = lm_gdn_recompute_device_ms.read


def test_recomputation_of_the_rule_on_a_made_up_step():
  """Under `gated_delta/scan`: a forward call of the walk, then on the
  way back the block's recomputation (a fusion of the chunk's
  preparation and the walk again), the row's own (the walk that writes
  the states) and the backward program. The conv's recomputation is
  another scope's."""
  scan = STEP + "{}/trunk/blocks_1/mixer/gated_delta/scan/{}"
  back = "transpose(jvp(Net))/trunk/jvp(Net)/trunk/checkpoint"
  again = back + "/rematted_computation"
  row = "while/body/closed_call/checkpoint/"
  ops = [("while.1", "", "while", None),
         ("fusion.1", scan.format("jvp(Net)", row + "dot_general:"),
          "convolution fusion", 400.0),
         ("closed_call.4", scan.format("jvp(Net)", row + "closed_call/"
                                       "pallas_call:"),
          "custom-call", 900.0),
         ("fusion.2", scan.format(again, row + "dot_general:"),
          "convolution fusion", 400.0),
         ("closed_call.5", scan.format(again, row + "closed_call/"
                                       "pallas_call:"),
          "custom-call", 900.0),
         ("fusion.3", STEP + again + "/trunk/blocks_1/mixer/"
          "gated_delta/conv/conv_general_dilated:", "loop fusion", 50.0),
         ("rematted_computation.4", scan.format(
             back, row + "rematted_computation/pallas_call:"),
          "custom-call", 1500.0),
         ("checkpoint.4", scan.format(back, row + "pallas_call:"),
          "custom-call", 1900.0)]
  planes, metadata = _scoped_recording(ops, ("whole", "whole", "whole"))
  trace = tr.reduce_planes(planes, 1, program="jit_k_steps",
                           metadata=metadata)
  assert trace["scope_ns"]["gated_delta/scan"] == pytest.approx({
      "forward": 3 * 1300e3, "recompute": 3 * 2800e3,
      "backward": 3 * 1900e3})
  run = _run(trace, "qwen3next_80b_a3b_ep16", batch=4)
  assert read(run) == pytest.approx(2.8)
  assert read(_run(trace, "qwen3next_80b_a3b_ep16", k=3)) == \
      pytest.approx(2.8 / 3)


def test_recomputation_on_the_recorded_scopes(recorded_scopes):
  """Three whole executions of a step a recording; the reader finds
  nothing where the scope never occurs, the recording is cut, the run
  was not traced or the reducer kept no tables (a parent's)."""
  assert read(_run(recorded_scopes)) is None
  attend = recorded_scopes["scope_ns"]["mla/attend"]
  as_the_rule = dict(recorded_scopes,
                     scope_ns={"gated_delta/scan": attend})
  assert read(_run(as_the_rule)) == pytest.approx(
      attend["recompute"] / 3e6)
  assert 0 < attend["recompute"] < sum(attend.values())
  assert read(_run(dict(as_the_rule, program_runs=0))) is None
  assert read(_run(None)) is None
  assert read(_run({"program_runs": 2, "program_busy_s": 1.0})) is None
