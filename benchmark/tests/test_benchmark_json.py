"""BENCHMARK.json against the contract's limits that can be checked
here, and every entry resolved by name to its files: a later PR adds a
cell, a configuration or a per-layer metric as new files plus entries."""

import importlib
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}
WIDTHS_DIR = os.path.join(HERE, "tests", "data", "widths")
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|projection)"
                    r"_size|_dim$|_rank$|head_size|filters|dense_sizes")


@pytest.fixture(scope="module")
def bench():
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    return json.load(f)


def test_keys_and_shapes(bench):
  assert set(bench) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
  assert 1 <= bench["run_seconds"] <= 51
  runs = 2 + 14 * 24  # a full check with all 24 cells
  assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
  assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
  assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
  four = sum(w["chips"] == 4 for w in bench["workloads"])
  assert four <= max(1, len(bench["workloads"]) // 4)


def test_names_units_and_lines(bench):
  names = []
  for group in ("configs", "workloads", "end_to_end", "per_layer"):
    for entry in bench[group]:
      assert NAME.match(entry["name"]), entry["name"]
      names.append((group in ("end_to_end", "per_layer"),
                    entry["name"]))
      for key in ("why", "layer", "source"):
        if key in entry and not (group in ("end_to_end", "per_layer")
                                 and key == "source"):
          text = entry[key]
          assert 1 <= len(text) <= 200 and "\n" not in text \
              and "\t" not in text, (entry["name"], key, len(text))
  assert len(names) == len(set(names))
  for entry in bench["end_to_end"] + bench["per_layer"]:
    assert UNIT.match(entry["unit"]), entry
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in SOURCES
  for entry in bench["end_to_end"]:
    assert set(entry) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    assert entry["source"] in ("host_clock", "device_trace")
    assert 0.01 <= entry["bound"] <= 0.1
  e2e = {m["name"] for m in bench["end_to_end"]}
  for entry in bench["per_layer"]:
    assert set(entry) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["moves"] in e2e


def test_every_entry_resolves_to_its_files(bench):
  configs = {}
  for entry in bench["configs"]:
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    path = os.path.join(ROOT, entry["file"])
    assert entry["file"].startswith(tuple(bench["paths"]))
    with open(path) as f:
      config = json.load(f)
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    for key in entry["reduced"]:
      assert NAME.match(key) and not WIDTHS.search(key), key
    assert os.path.exists(os.path.join(ROOT, config["gin_file"]))
    configs[entry["name"]] = config
  files = [c["file"] for c in bench["configs"]]
  assert len(files) == len(set(files))
  used = set()
  pairs = set()
  for cell in bench["workloads"]:
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    assert cell["config"] in configs
    assert NAME.match(cell["traffic"])
    used.add(cell["config"])
    assert (cell["config"], cell["traffic"]) not in pairs
    pairs.add((cell["config"], cell["traffic"]))
    with open(os.path.join(HERE, "traffic",
                           f"{cell['traffic']}.json")) as f:
      traffic = json.load(f)
    # A kind is valid when its driver module imports and has the
    # contract's three functions.
    run = importlib.import_module("benchmark.run")
    driver = run.driver_of(traffic["kind"])
    assert all(callable(getattr(driver, name))
               for name in ("run", "check", "numbers"))
    with open(os.path.join(HERE, "limits",
                           f"{cell['name']}.json")) as f:
      limits = json.load(f)
    assert all(isinstance(v, (int, float)) for k, v in limits.items()
               if not k.startswith("_"))
  assert used == set(configs)
  cells = {w["name"] for w in bench["workloads"]}
  for entry in bench["per_layer"]:
    reader = importlib.import_module(
        f"benchmark.layer_metrics.{entry['name']}")
    assert callable(reader.read)
    assert set(entry.get("workloads", cells)) <= cells
  layers = open(os.path.join(ROOT, "PERF.md")).read()
  for entry in bench["per_layer"]:
    assert entry["layer"] in layers


def _holds(pinned, stated) -> bool:
  """`stated` has every entry of `pinned`, group by group."""
  if isinstance(pinned, dict):
    return isinstance(stated, dict) and all(
        key in stated and _holds(value, stated[key])
        for key, value in pinned.items() if not key.startswith("_"))
  return pinned == stated


def _unpinned(configs, widths_dir):
  """The configurations whose file differs from its pin
  `<widths_dir>/<config>.json`, or that have none."""
  wrong = []
  for entry in configs:
    pin = os.path.join(widths_dir, f"{entry['name']}.json")
    if not os.path.exists(pin):
      wrong.append(f"{entry['name']}: no pin file {pin}")
      continue
    with open(pin) as f, open(os.path.join(ROOT, entry["file"])) as g:
      if not _holds(json.load(f), json.load(g)):
        wrong.append(f"{entry['name']}: differs from {pin}")
  return wrong


def test_widths_are_the_sources(bench):
  """No width, image size or CEM size of a configuration differs from
  its source. Each configuration brings a pin file
  `tests/data/widths/<config>.json` whose entries its file must have
  (QT-Opt's: the paper's 472x472 input and the width-64 stack, the
  shipped file's 64x64 network, CEM 2 x 64 with 6 elites, four action
  dimensions), so that a later change to either side is an edit."""
  assert _unpinned(bench["configs"], WIDTHS_DIR) == []


def test_a_configuration_without_a_pin_fails(bench, tmp_path):
  first = bench["configs"][0]["name"]
  with open(os.path.join(WIDTHS_DIR, f"{first}.json")) as f:
    pin = json.load(f)
  pin["model"]["action_dim"] += 1  # a pin its file does not hold
  (tmp_path / f"{first}.json").write_text(json.dumps(pin))
  wrong = _unpinned(bench["configs"], str(tmp_path))
  assert len(wrong) == len(bench["configs"])
  assert "differs" in wrong[0]
  assert all("no pin file" in line for line in wrong[1:])
