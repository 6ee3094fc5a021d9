"""`tools/read_limits.py` off the chip: the seeds it takes, the table
it makes of several calls' outputs, and the rule for a precision
limit."""

import importlib.util
import json
import math
import os

import numpy as np
import pytest

from benchmark import run as run_lib

_spec = importlib.util.spec_from_file_location(
    "read_limits", os.path.join(run_lib.HERE, "tools", "read_limits.py"))
read_limits = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(read_limits)


def _lognormal(median, sd, n, seed):
  rng = np.random.default_rng(seed)
  return [float(median * math.exp(sd * z)) for z in rng.normal(size=n)]


def test_seeds_are_numbers_or_blocks():
  assert read_limits.parse_seeds(["3000:3", "1478447722"]) == [
      3000, 3001, 3002, 1478447722]


@pytest.mark.parametrize("control_median,control_sd,meets", [
    (0.3, 0.05, True),    # six times off, both steady
    (0.3, 0.5, False),    # the control's tail reaches the sound runs
    (0.08, 0.05, False),  # under 4x apart: no room for 2x on each side
], ids=["apart", "heavy_tail", "too_close"])
def test_rule_for_a_precision_limit(control_median, control_sd, meets):
  sound = read_limits.log_stats(_lognormal(0.05, 0.05, 120, 0))
  control = read_limits.log_stats(
      _lognormal(control_median, control_sd, 120, 1))
  rule = read_limits.precision_limit(sound, control)
  assert rule["meets"] is meets
  if meets:
    limit = math.sqrt(rule["lowest"] * rule["highest"])
    assert read_limits.sigmas_from(sound, limit) >= read_limits.SIGMAS
    assert read_limits.sigmas_from(control, limit) <= -read_limits.SIGMAS
    assert limit >= read_limits.FACTOR * sound["largest"]
    assert limit <= control["smallest"] / read_limits.FACTOR


def test_a_gap_of_scalars_meets_no_rule():
  """|a - b| of two scalars has density at zero: over enough seeds its
  logarithm's spread leaves no room, however far apart the medians."""
  rng = np.random.default_rng(2)
  sound = read_limits.log_stats(
      [abs(float(z)) * 1e-3 for z in rng.normal(size=120)])
  control = read_limits.log_stats(
      [abs(float(z)) * 1e-1 for z in rng.normal(size=120)])
  assert not read_limits.precision_limit(sound, control)["meets"]


def test_table_over_several_calls(tmp_path):
  files = []
  for tag, seeds in (("a", [1, 2, 3]), ("b", [4, 5])):
    numbers = {
        "sound": [{"adam_mu_rel_err": 0.05 + 0.001 * s} for s in seeds],
        "control": [{"adam_mu_rel_err": 0.25 + 0.001 * s}
                    for s in seeds],
        "tower_only": [{"adam_mu_rel_err": 0.1}]}  # the first seed only
    files.append(tmp_path / f"{tag}.json")
    files[-1].write_text(json.dumps({"seeds": seeds,
                                     "numbers": numbers}))
  lines = []
  summary = read_limits.summarize(files, {"adam_mu_rel_err": 0.12},
                                  lines.append)
  read = summary["numbers"]["adam_mu_rel_err"]
  assert summary["seeds"] == [1, 2, 3, 4, 5]
  assert read["sound"]["n"] == 5 and read["tower_only"]["n"] == 2
  assert read["sound"]["largest"] == pytest.approx(0.055)
  assert read["control"]["smallest"] == pytest.approx(0.251)
  assert read["precision_limit"]["meets"]
  assert any("adam_mu_rel_err" in line and "exists" in line
             for line in lines)
  held = summary["held"]["adam_mu_rel_err"]
  assert held["factor_above_largest_sound"] == pytest.approx(0.12 / 0.055)
  assert held["factor_below_smallest_control"] == pytest.approx(
      0.251 / 0.12)
  assert held["sigmas_above_sound"] > 4 < held["sigmas_below_control"]
  assert held["fitted_share_of_sound_runs_refused"] < 1e-4
  assert summary["refused"]["sound"]["any"] == 0
  assert summary["refused"]["control"] == {"n": 5, "any": 5,
                                           "adam_mu_rel_err": 5}
  assert summary["refused"]["tower_only"]["any"] == 0
  with pytest.raises(SystemExit, match="twice"):
    read_limits.summarize([files[0], files[0]], out=lines.append)
