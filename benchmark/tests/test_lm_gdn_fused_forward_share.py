"""`lm_gdn_fused_forward_share` (ISSUE 42): the reader resolves by its
entry's name and reads the program's two counters: 100 where every
traced call of the rule took the fused program for its forward pass, 0
where none did, `None` where the program has neither counter (the
parent)."""

import importlib
import json
import os

import pytest

from tensor2robot_tpu.telemetry import metrics as tmetrics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "lm_gdn_fused_forward_share"
FUSED = "gated_delta.forward.fused_traces"
PREPARED = "gated_delta.forward.prepared_traces"


@pytest.fixture
def registry():
  tmetrics.reset_for_tests()
  yield tmetrics
  tmetrics.reset_for_tests()


def _entry():
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    (entry,) = [m for m in json.load(f)["per_layer"]
                if m["name"] == NAME]
  return entry


def test_fused_forward_share_entry_resolves_to_its_reader():
  assert _entry() == {
      "name": NAME, "unit": "%", "better": "higher",
      "source": "program_counter", "layer": "sequence trunk",
      "moves": "train_steps_per_s",
      "workloads": ["qwen3next_80b_a3b_ep16.train_eval"]}
  reader = importlib.import_module(f"benchmark.layer_metrics.{NAME}")
  assert callable(reader.read)


@pytest.mark.parametrize("fused,prepared,share", [
    (0, 0, None),     # the parent: neither counter
    (36, 0, 100.0),   # a TPU at widths that tile
    (0, 36, 0.0),     # a CPU
    (3, 1, 75.0),
])
def test_fused_forward_share_reads_the_two_counters(registry, fused,
                                                    prepared, share):
  from benchmark.layer_metrics import lm_gdn_fused_forward_share
  if fused:
    registry.counter(FUSED).inc(fused)
  if prepared:
    registry.counter(PREPARED).inc(prepared)
  # The walk's counters are another reader's.
  registry.counter("gated_delta.walk.scan_traces").inc(5)
  got = lm_gdn_fused_forward_share.read({})
  assert got is None if share is None else got == pytest.approx(share)
