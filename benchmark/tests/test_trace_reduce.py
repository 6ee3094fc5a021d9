"""The reduction from a profiler trace to busy time, per-operation time,
collective time and idle gaps: on made-up events, and on a small trace
recorded on a TPU v5e (tools/trace_probe.py, PR 23: three executions of
a jitted 4-iteration scan with 20 ms of sleep between them)."""

import os

import pytest

from benchmark.harness import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_scan4.xplane.pb")


def test_union_merges_overlaps():
  assert tr.union_ns([(0, 10), (5, 12), (20, 30), (30, 31)]) == 23


def test_umbrella_events_are_not_counted_twice():
  ops = [("%while = ...", 0, 100),           # spans its body
         ("%fusion.1 = ...", 0, 40),
         ("%all-reduce.2 = ...", 40, 30),
         ("%fusion.3 = ...", 80, 20),        # 10 ns of loop overhead
         ("%copy.4 = ...", 200, 50)]
  summary = tr.device_summary(ops, (0, 300))
  assert summary["busy_ns"] == 40 + 30 + 20 + 50
  assert summary["per_op_ns"]["while"] == 10
  assert summary["per_op_ns"]["fusion.1"] == 40
  assert summary["collective_ns"] == 30
  assert sum(summary["per_op_ns"].values()) == 150


def test_window_clips_operations():
  summary = tr.device_summary([("%a = ...", 0, 100)], (50, 80))
  assert summary["busy_ns"] == 30


def test_idle_gaps_are_named_by_the_host_event_inside_them():
  modules = [("jit_k_steps(1)", 0, 100), ("jit_k_steps(1)", 400, 100)]
  host = [("train_qtopt", 0, 500),            # enclosing frame: skipped
          ("$prefetch.py:1 __next__", 120, 250),
          ("device_get", 380, 10)]
  gaps = tr.idle_gaps(modules, host, (0, 500))
  assert gaps == [["$prefetch.py:1 __next__", 300 / 1e9]]


def test_a_gap_goes_to_the_innermost_of_nested_spans():
  """The program's stage spans nest (`loop.log` > `loop.log_sync`,
  `loop.save` > `loop.save_d2h`, `loop.save_write`): a gap is named by
  the innermost span that covers most of it, not by its parents."""
  modules = [("jit_k_steps(1)", 0, 100), ("jit_k_steps(1)", 400, 100),
             ("jit_k_steps(1)", 900, 100)]
  host = [("loop.wait_feed", 100, 20), ("qtopt.dispatch", 120, 30),
          ("loop.log", 150, 240),                 # parent
          ("loop.log_sync", 155, 230),            # covers the first gap
          ("loop.save", 500, 390),                # parent
          ("loop.save_d2h", 505, 40),
          ("loop.save_write", 545, 340)]          # covers the second
  gaps = dict(tr.idle_gaps(modules, host, (0, 1000)))
  assert gaps == {"loop.log_sync": 300 / 1e9,
                  "loop.save_write": 400 / 1e9}


def _made_up_recording(runs, ops_per_run=7, gap=2_000.0):
  """Planes of one chip on which `jit_k_steps` ran back to back, as a
  loop that keeps a dispatch in flight leaves it. `runs` is a list of
  `whole`, `head` (the recording stopped inside it) and `tail` (it
  started inside it); an operation takes 10 us of every 12, and a
  1 us copy stands between two executions."""
  modules, ops, t = [], [], 50_000.0
  for kind in runs:
    names = [f"%fusion.{i} = f32[8]" for i in range(ops_per_run)]
    if kind == "head":
      names = names[:3]
    elif kind == "tail":
      names = names[2:]
    start = t
    for name in names:
      ops.append((name, t, 10_000.0))
      t += 12_000.0
    # A whole execution's event stands a little clear of its
    # operations; a cut one's runs from its first to its last.
    lead = 500.0 if kind == "whole" else 0.0
    modules.append(("jit_k_steps(17)", start - lead,
                    t - 2_000.0 - start + 2 * lead))
    modules.append(("jit__copy_on_device(3)", t, 1_000.0))
    ops.append(("%copy.1 = f32[8]", t, 1_000.0))
    t += gap
  modules.pop(), ops.pop()  # nothing follows the last execution
  return {"/device:TPU:0": {tr.MODULES_LINE: modules, tr.OPS_LINE: ops},
          tr.HOST_PLANE: {}}


def _step_device_ms(trace, k=7):
  from benchmark.layer_metrics import step_device_ms
  return step_device_ms.read({"trace": trace, "k": k})


def test_a_recording_that_cuts_programs_counts_the_whole_ones():
  """Three whole programs, the tail of one before and the head of one
  after: 3 runs and their busy time alone, where every part used to
  count as a run (5 runs; ISSUE 33). Busy and idle keep every
  operation."""
  planes = _made_up_recording(["tail", "whole", "whole", "whole", "head"])
  trace = tr.reduce_planes(planes, 1, program="jit_k_steps")
  assert trace["program_runs"] == 3
  assert trace["program_busy_s"] == pytest.approx(3 * 7 * 10e-6)
  assert _step_device_ms(trace) == pytest.approx(10e-3)
  every_op = (5 + 3 * 7 + 3) * 10e-6 + 4 * 1e-6
  assert trace["busy_s"] == pytest.approx(every_op)
  per_op = dict(trace["device_ops"])  # the tail holds 2-6, the head 0-2
  assert per_op["fusion.2"] == pytest.approx(5 * 10e-6)
  assert per_op["fusion.6"] == pytest.approx(4 * 10e-6)
  assert trace["window_s"] == pytest.approx(
      max(s + d for _, s, d in planes["/device:TPU:0"][tr.MODULES_LINE])
      / 1e9)


@pytest.mark.parametrize("runs,whole", [
    (["whole", "whole", "whole"], 3),  # a loop that waits: none is cut
    (["whole", "whole"], 2),           # both at an edge, and they agree
    (["tail", "whole", "whole"], 2),   # the last one ends the recording
    (["whole", "whole", "head"], 2),
    (["tail", "whole", "head"], 1),    # clear of both edges
    (["tail", "head"], 0),             # parts only: they agree on nothing
    (["tail"], 0), (["head"], 0),
    (["whole"], 0),                    # nothing says that it is whole
])
def test_which_executions_are_whole(runs, whole):
  trace = tr.reduce_planes(_made_up_recording(runs), 1,
                           program="jit_k_steps")
  assert trace["program_runs"] == whole
  assert trace["program_busy_s"] == pytest.approx(whole * 7 * 10e-6)
  if not whole:  # never a partial number
    assert _step_device_ms(trace) is None
    assert trace["busy_s"] > 0


def test_whole_runs_differ_in_length_and_still_count():
  """Where the feed sets the pace whole executions differ by a tenth
  in length (0.123-0.136 s in `qtopt_472.train`) and a tail can be nine
  tenths of a whole: the count of operations tells them apart, a
  length would not."""
  ops = [(f"%fusion.{i} = f32[8]", 100.0 * i, 90.0) for i in range(4)]
  slow = [(f"%fusion.{i} = f32[8]", 1000.0 + 120.0 * i, 110.0)
          for i in range(4)]
  tail = [(f"%fusion.{i} = f32[8]", 2000.0 + 130.0 * i, 120.0)
          for i in range(1, 4)]
  runs = [(0.0, 390.0), (1000.0, 1470.0), (2000.0 + 130.0, 2510.0)]
  assert tr.whole_runs(runs, ops + slow + tail) == runs[:2]
  assert tr.whole_runs(runs, []) == []


@pytest.fixture(scope="module")
def recorded():
  return tr.reduce_trace(RECORDED, 1, program="jit_prog")


def test_recorded_trace_busy_and_idle(recorded):
  planes = tr.load(RECORDED)
  ops = planes["/device:TPU:0"][tr.OPS_LINE]
  modules = planes["/device:TPU:0"][tr.MODULES_LINE]
  summed = sum(d for _, _, d in ops) / 1e9
  in_programs = sum(d for _, _, d in modules) / 1e9
  assert recorded["program_runs"] == 3
  # Summing event durations counts the scan's body twice (the %while
  # umbrella and its operations); the union cannot exceed the time the
  # programs were on the chip.
  assert summed > 1.5 * recorded["busy_s"]
  assert recorded["busy_s"] <= in_programs
  assert recorded["busy_s"] > 0.9 * in_programs
  assert recorded["program_busy_s"] == pytest.approx(recorded["busy_s"])
  # 35 us of work in a 92 ms recording: the chip idles in the sleeps.
  assert 1 - recorded["busy_s"] / recorded["window_s"] > 0.99
  # The wait before the first program is the profiler starting up, the
  # two between programs are the script's sleeps.
  gaps = dict(recorded["idle_gaps"])
  assert gaps["$time sleep"] == pytest.approx(0.043, abs=0.003)
  assert "$profiler.py:101 start_trace" in gaps
  assert recorded["collective_s"] == 0.0


def test_recorded_trace_names_operations(recorded):
  names = [name for name, _ in recorded["device_ops"]]
  assert names[0].startswith("fusion")
  assert "while" in names  # present, with its self time only
  per_op = dict(recorded["device_ops"])
  assert per_op["while"] < 0.01 * per_op[names[0]]


def test_too_few_device_planes_is_an_error():
  with pytest.raises(ValueError):
    tr.reduce_trace(RECORDED, 4)
