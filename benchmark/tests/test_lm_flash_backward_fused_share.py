"""`lm_flash_backward_fused_share` (ISSUE 45): the reader resolves by
its entry's name and reads the program's two counters: 100 where every
traced backward pass of the flash kernel is the one fused program, 0
where every one is the pair, `None` where the program has neither
counter (the parent)."""

import importlib
import json
import os

import pytest

from tensor2robot_tpu.telemetry import metrics as tmetrics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "lm_flash_backward_fused_share"
FUSED = "flash_attention.backward.fused_traces"
PAIRED = "flash_attention.backward.paired_traces"


@pytest.fixture
def registry():
  tmetrics.reset_for_tests()
  yield tmetrics
  tmetrics.reset_for_tests()


def test_flash_backward_fused_share_entry_resolves_to_its_reader():
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    (entry,) = [m for m in json.load(f)["per_layer"]
                if m["name"] == NAME]
  assert entry == {
      "name": NAME, "unit": "%", "better": "higher",
      "source": "program_counter", "layer": "kernels",
      "moves": "train_steps_per_s",
      "workloads": ["qwen3next_80b_a3b_ep16.train_eval",
                    "joyai_llm_flash_ep16.train_eval",
                    "laguna_xs2_ep16.train_eval"]}
  reader = importlib.import_module(f"benchmark.layer_metrics.{NAME}")
  assert callable(reader.read)


@pytest.mark.parametrize("fused,paired,share", [
    (0, 0, None),    # the parent: neither counter
    (6, 0, 100.0),   # the JoyAI cell: five layers and the module's
    (0, 5, 0.0),     # sequences whose accumulators do not fit
    (3, 1, 75.0),
])
def test_flash_backward_fused_share_reads_the_two_counters(
    registry, fused, paired, share):
  from benchmark.layer_metrics import lm_flash_backward_fused_share
  if fused:
    registry.counter(FUSED).inc(fused)
  if paired:
    registry.counter(PAIRED).inc(paired)
  # The mixers' own counters are other readers'.
  registry.counter("mla.attend.kernel_traces").inc(5)
  got = lm_flash_backward_fused_share.read({})
  assert got is None if share is None else got == pytest.approx(share)
