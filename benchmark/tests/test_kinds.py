"""A traffic kind is a file found by name: `harness/<kind>_driver.py`
with `run`, `check` and `numbers`. `run.py` holds no table of kinds."""

import sys
import types

import pytest

from benchmark import run as run_lib


@pytest.mark.parametrize("kind", run_lib.kinds())
def test_every_kind_resolves_by_name(kind):
  driver = run_lib.driver_of(kind)
  assert driver.__name__ == f"benchmark.harness.{kind}_driver"
  assert all(callable(getattr(driver, name))
             for name in ("run", "check", "numbers"))


def test_the_kinds_are_the_driver_files():
  assert {"train", "train_eval"} <= set(run_lib.kinds())
  assert not hasattr(run_lib, "DRIVERS")
  assert not hasattr(run_lib, "CHECKS")


def test_an_unknown_kind_names_the_known_ones():
  with pytest.raises(SystemExit) as refused:
    run_lib.driver_of("serve_nothing")
  assert "serve_nothing" in str(refused.value)
  for kind in run_lib.kinds():
    assert repr(kind) in str(refused.value)


def test_a_new_driver_module_is_found_with_no_table_edited(monkeypatch):
  fake = types.ModuleType("benchmark.harness.fake_driver")
  fake.run = fake.check = fake.numbers = lambda *args, **kwargs: None
  monkeypatch.setitem(sys.modules, fake.__name__, fake)
  assert run_lib.driver_of("fake") is fake


def test_a_driver_that_lacks_an_import_is_not_called_unknown(monkeypatch):
  """`no traffic kind` is for a driver file that is not there, not for
  one whose own import fails."""
  import importlib

  def broken(name):
    raise ModuleNotFoundError("No module named 'absent'", name="absent")

  monkeypatch.setattr(importlib, "import_module", broken)
  with pytest.raises(ModuleNotFoundError):
    run_lib.driver_of("train")


def test_a_stand_ins_file_resolves_beside_its_own_data():
  import os
  path = os.path.join(run_lib.HERE, "tests", "data", "standin",
                      "BENCHMARK.json")
  bench, cell, config, traffic = run_lib.load_cell(
      "standin.train_eval", path)
  assert traffic["kind"] == "train_eval"
  assert config["name"] == cell["config"] == "standin"
  assert run_lib.data_dir(bench).endswith(
      os.path.join("tests", "data", "standin"))
