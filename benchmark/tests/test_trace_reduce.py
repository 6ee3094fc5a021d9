"""The reduction from a profiler trace to busy time, per-operation time,
collective time and idle gaps: on made-up events, and on two small
traces recorded on a TPU v5e (tools/trace_probe.py; PR 23: three
executions of a jitted 4-iteration scan with 20 ms of sleep between
them; PR 40: three of a gradient step through two named scopes under a
checkpoint, the flash kernel in the first). Since PR 40 also self time
by named scope and the kernels' calls (`by_scope`), the readers of
`layer_metrics/` that turn them into ms a step and roofline shares,
and the kernels' cost functions of `harness/lm_flops.py` against
XLA's cost analysis of the plain products."""

import importlib
import json
import os

import pytest

from benchmark.harness import lm_flops, mla_lm_flops, peaks
from benchmark.harness import trace_reduce as tr

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_scan4.xplane.pb")
RECORDED_SCOPES = os.path.join(os.path.dirname(__file__), "data",
                               "v5e_scopes.xplane.pb")


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
  # One line a group of scope and kind (the tail holds 2-6, the head
  # 0-2), whatever the operations' numbers.
  assert dict(trace["device_ops"]) == {
      "unnamed:fusion": pytest.approx((5 + 3 * 7 + 3) * 10e-6),
      "unnamed:copy": pytest.approx(4 * 1e-6)}
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
  assert names[0] == "other:fusion"  # a `tf_op`, none of the scopes
  assert "unnamed:while" in names  # present, with its self time only
  per_op = dict(recorded["device_ops"])
  assert per_op["unnamed:while"] < 0.01 * per_op[names[0]]


def test_too_few_device_planes_is_an_error():
  with pytest.raises(ValueError):
    tr.reduce_trace(RECORDED, 4)


# ---- PR 40: the rounding race, scopes, kernels, readers, costs ----


@pytest.mark.parametrize("edge", ["first", "last", "both"])
def test_an_execution_rounded_clear_of_an_edge_still_holds_it(edge):
  """A cut execution's event and the operation at the recording's edge
  are rounded apart: the tail's event starts a quarter of a nanosecond
  after the first operation, the head's ends as much before the last
  one's end. Neither is clear of its edge (PERF.md §7 (1c): they
  counted as whole, 5 runs for 3)."""
  planes = _made_up_recording(["tail", "whole", "whole", "whole", "head"])
  lines = planes["/device:TPU:0"]
  programs = [i for i, (name, _, _) in enumerate(lines[tr.MODULES_LINE])
              if name.startswith("jit_k_steps")]
  if edge in ("first", "both"):
    name, start, dur = lines[tr.MODULES_LINE][programs[0]]
    lines[tr.MODULES_LINE][programs[0]] = (name, start + 0.25,
                                           dur - 0.25)
  if edge in ("last", "both"):
    name, start, dur = lines[tr.MODULES_LINE][programs[-1]]
    lines[tr.MODULES_LINE][programs[-1]] = (name, start, dur - 0.25)
  trace = tr.reduce_planes(planes, 1, program="jit_k_steps")
  assert trace["program_runs"] == 3
  assert trace["program_busy_s"] == pytest.approx(3 * 7 * 10e-6)


def test_whole_runs_margin_at_both_edges():
  ops = [(f"%fusion.{i} = f32[8]", 1000.0 * i, 900.0) for i in range(9)]
  first, last = 0.0, 8900.0
  cut_tail, whole, cut_head = (first + 0.5, 1900.0), (3000.0, 5900.0), \
      (7000.0, last - 0.5)
  assert tr.whole_runs([cut_tail, whole, cut_head], ops) == [whole]
  # A microsecond and more from both edges is clear, whatever it holds.
  clear = (first + 2 * tr.EDGE_MARGIN_NS, last - 2 * tr.EDGE_MARGIN_NS)
  assert tr.whole_runs([clear], ops) == [clear]
  at_margin = (first + 0.5 * tr.EDGE_MARGIN_NS, last - 3000.0)
  assert tr.whole_runs([at_margin], ops) == []


@pytest.mark.parametrize("tf_op,scope,which", [
    ("", "unnamed", "forward"),
    ("jit(prog)/while/body/closed_call/dot_general:", "other", "forward"),
    ("jit(k_steps)/jvp(mla/attend)/flash_attention:", "mla/attend",
     "forward"),
    # As the chip's recordings have them (PR 40).
    ("jit(k_steps)/while/body/closed_call/transpose(jvp(LanguageModel"
     "Network))/trunk/jvp(LanguageModelNetwork)/trunk/checkpoint/"
     "rematted_computation/blocks_2.<lambda>/blocks_2/mixer/gated_delta/"
     "scan/while/body/closed_call/closed_call/pallas_call:",
     "gated_delta/scan", "recompute"),
    ("jit(k_steps)/while/body/closed_call/transpose(jvp(LanguageModel"
     "Network))/trunk/jvp(LanguageModelNetwork)/trunk/checkpoint/"
     "blocks_3.<lambda>/blocks_3/mixer/gated_attention/"
     "jit(flash_attention)/pallas_call:", "gated_attention", "backward"),
    ("ragged-dot-none:", "other", "forward"),
    ("jit(k_steps)/transpose(jvp(mla/attend))/dot_general:",
     "mla/attend", "backward"),
    ("jit(k_steps)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/gated_delta/scan/mul:", "gated_delta/scan",
     "recompute"),
    ("jit(k_steps)/transpose(jvp())/checkpoint/dense_ffn/transpose:",
     "dense_ffn", "backward"),
    # The inner scope names an operation under an umbrella; QT-Opt's
    # `backward` is read whole, before the `torso` inside it.
    ("jit(k_steps)/jvp(mtp/block)/mla/q_proj/dot_general:", "mla/q_proj",
     "forward"),
    ("jit(k_steps)/jvp(mtp/block)/add:", "mtp/block", "forward"),
    ("jit(k_steps)/backward/transpose(jvp(torso))/conv:", "backward",
     "backward"),
    ("jit(k_steps)/cem_tower/conv_general_dilated:", "cem_tower",
     "forward"),
    # A scope is a whole component of the path, not a part of a name.
    ("jit(k_steps)/_backward_kernel/my_torso_fn/mul:", "other",
     "forward"),
])
def test_scope_and_pass_of_a_tf_op(tf_op, scope, which):
  assert tr.scope_of(tf_op) == scope
  assert tr.pass_of(tf_op) == which


@pytest.mark.parametrize("short,kind", [
    ("flash_attention.296", "flash_attention"), ("fusion.3204.remat",
                                                 "fusion"),
    ("ragged-dot-none.12", "ragged-dot-none"), ("while", "while"),
    ("bitcast_dynamic-update-slice_fusion.2",
     "bitcast_dynamic-update-slice_fusion"), ("copy.15.clone.1", "copy"),
])
def test_kind_name_drops_the_trailing_numbers(short, kind):
  assert tr.kind_name(short) == kind


@pytest.mark.parametrize("short,group", [
    ("multiply_add_fusion.416", "fusion"), ("fusion.3204.remat", "fusion"),
    ("flash_attention.296", "flash_attention"), ("copy.15", "copy"),
    ("ragged-dot-none.3", "ragged-dot-none")])
def test_the_breakdown_sums_fusions_of_every_flavour(short, group):
  assert tr.group_name(short) == group


def test_primitive_of_a_tf_op():
  assert tr.primitive_of("jit(k_steps)/while/body/mixer/gated_delta/scan/"
                         "checkpoint/pallas_call:") == "pallas_call"
  assert tr.primitive_of("ragged-dot-none:") == "ragged-dot-none"
  assert tr.primitive_of("") == ""


STEP = "jit(k_steps)/while/body/"
FLASH = "jit(flash_attention)/pallas_call:"
# One execution of a made-up step program: (name, tf_op, category, us),
# the `tf_op`s as the chip's recordings have them (PR 40). The `%while`
# is an umbrella over everything after it, with 2 us of its own; the
# grouped product has its own name for a `tf_op`.
SCOPED_OPS = [
    ("while.1", "", "while", None),
    ("fusion.1", STEP + "jvp(Net)/trunk/blocks_1/mixer/mla/q_proj/"
     "dot_general:", "convolution fusion", 30.0),
    ("flash_attention.7", STEP + "jvp(Net)/trunk/blocks_1/mixer/"
     "mla/attend/" + FLASH, "custom-call", 100.0),
    ("multiply_add_fusion.2", STEP + "jvp(Net)/trunk/blocks_1/ffn/"
     "moe/experts/gather:", "loop fusion", 8.0),
    ("ragged-dot-none.3", "ragged-dot-none:", "custom-call", 5.0),
    ("fusion.3", STEP + "jvp(lm_head_loss)/dot_general:",
     "convolution fusion", 20.0),
    ("fusion.4.remat", STEP + "transpose(jvp())/checkpoint/"
     "rematted_computation/mla/q_proj/dot_general:",
     "convolution fusion", 30.0),
    ("flash_attention.8", STEP + "transpose(jvp(Net))/trunk/checkpoint/"
     "blocks_1/mixer/mla/attend/" + FLASH, "custom-call", 150.0),
    ("flash_attention.9", STEP + "transpose(jvp(Net))/trunk/checkpoint/"
     "blocks_1/mixer/mla/attend/" + FLASH, "custom-call", 130.0),
    ("fusion.5", STEP + "transpose(jvp(mla/q_proj))/dot_general:",
     "convolution fusion", 60.0),
    ("fusion.6", "", "loop fusion", 10.0),  # Adam: no tf_op
]
SCOPED_OWN_US = 2.0


def _scoped_recording(ops=SCOPED_OPS, runs=("tail", "whole", "whole",
                                           "head")):
  """Planes and metadata of one chip on which the made-up program ran
  back to back; a `tail` lacks the execution's first two operations
  after the umbrella, a `head` its last three (so the two parts agree
  in nothing)."""
  events, modules, metadata, t = [], [], {}, 10_000.0
  for name, tf_op, category, _ in ops:
    metadata[f"%{name} = f32[8]"] = (tf_op, category)
  for kind in runs:
    body = list(ops[1:])
    if kind == "tail":
      body = body[2:]
    elif kind == "head":
      body = body[:-3]
    start = t
    t += SCOPED_OWN_US * 1e3 / 2
    for name, _, _, us in body:
      events.append((f"%{name} = f32[8]", t, us * 1e3))
      t += us * 1e3
    t += SCOPED_OWN_US * 1e3 / 2
    events.append((f"%{ops[0][0]} = f32[8]", start, t - start))
    lead = 1500.0 if kind == "whole" else 0.0
    modules.append(("jit_k_steps(3)", start - lead, t - start + 2 * lead))
    t += 5_000.0
  planes = {"/device:TPU:0": {tr.MODULES_LINE: modules,
                              tr.OPS_LINE: events}, tr.HOST_PLANE: {}}
  return planes, {"/device:TPU:0": metadata}


@pytest.fixture(scope="module")
def scoped_trace():
  planes, metadata = _scoped_recording()
  return tr.reduce_planes(planes, 1, program="jit_k_steps",
                          metadata=metadata)


def test_scope_ns_of_the_whole_executions(scoped_trace):
  """Two whole executions between a tail and a head: self time by
  scope and pass over the two alone, the umbrella's own time under
  `unnamed` with what carries no tf_op, nothing counted twice."""
  assert scoped_trace["program_runs"] == 2
  us = lambda scope: {which: ns / 2e3  # noqa: E731
                      for which, ns in scoped_trace["scope_ns"][scope].items()}
  assert us("mla/q_proj") == {"forward": 30.0, "recompute": 30.0,
                              "backward": 60.0}
  assert us("mla/attend") == {"forward": 100.0, "recompute": 0.0,
                              "backward": 280.0}
  assert us("moe/experts") == {"forward": 8.0, "recompute": 0.0,
                               "backward": 0.0}
  assert us("lm_head_loss")["forward"] == 20.0
  assert us("unnamed")["forward"] == pytest.approx(10.0 + SCOPED_OWN_US)
  assert us("other")["forward"] == 5.0  # the grouped product
  assert set(scoped_trace["scope_ns"]) == {
      "mla/q_proj", "mla/attend", "moe/experts", "lm_head_loss",
      "other", "unnamed"}
  whole = sum(us for _, _, _, us in SCOPED_OPS[1:]) + SCOPED_OWN_US
  assert scoped_trace["program_self_s"] == pytest.approx(2 * whole / 1e6)
  assert scoped_trace["program_busy_s"] == pytest.approx(
      2 * (whole - SCOPED_OWN_US) / 1e6)


def test_kernels_of_the_whole_executions(scoped_trace):
  kernels = {(k["name"], k["scope"], k["pass"], k["primitive"]):
             (k["calls"], k["ns"]) for k in scoped_trace["kernels"]}
  assert kernels == {
      ("flash_attention", "mla/attend", "forward", "pallas_call"):
          (2, 200e3),
      ("flash_attention", "mla/attend", "backward", "pallas_call"):
          (4, 560e3),
      ("ragged-dot-none", "other", "forward", "ragged-dot-none"):
          (2, 10e3)}
  # Heaviest first.
  assert scoped_trace["kernels"][0]["pass"] == "backward"


def test_device_ops_are_groups_of_scope_and_kind(scoped_trace):
  """The breakdown's lines, over every operation of the window (the
  tail's and the head's too): `mla/attend:flash_attention` is one line
  and leads."""
  lines = dict(scoped_trace["device_ops"])
  assert scoped_trace["device_ops"][0][0] == "mla/attend:flash_attention"
  # The forward call of the three executions that hold their start,
  # the dK/dV call of all four, the dQ call of the three that hold
  # their end.
  assert lines["mla/attend:flash_attention"] == pytest.approx(
      (3 * 100 + 4 * 150 + 3 * 130) / 1e6)
  assert lines["other:ragged-dot-none"] == pytest.approx(4 * 5 / 1e6)
  # A fusion of any flavour is `fusion`.
  assert lines["moe/experts:fusion"] == pytest.approx(4 * 8 / 1e6)
  assert lines["mla/q_proj:fusion"] == pytest.approx(
      (3 * 30 + 4 * 30 + 3 * 60) / 1e6)
  assert sum(lines.values()) == pytest.approx(scoped_trace["busy_s"]
                                              + 4 * SCOPED_OWN_US / 1e6)


def test_without_metadata_everything_is_unnamed():
  planes, _ = _scoped_recording()
  trace = tr.reduce_planes(planes, 1, program="jit_k_steps")
  assert set(trace["scope_ns"]) == {"unnamed"}
  assert trace["kernels"] == []
  assert trace["program_self_s"] == pytest.approx(2 * 545e-6)


def _run(trace, config="joyai_llm_flash_ep16", k=1, batch=2):
  with open(os.path.join(HERE, "configs", f"{config}.json")) as f:
    return {"trace": trace, "k": k, "batch": batch, "chips": 1,
            "device_kind": "TPU v5 lite", "config": json.load(f),
            "records": []}


def _reader(name):
  return importlib.import_module(f"benchmark.layer_metrics.{name}").read


# A metric's name says whose cell reports it: `lm_` the hybrid family's,
# `lm_mla_` the latent-attention family's (as `lm_step_mfu` and
# `lm_mla_step_mfu`), `step_` QT-Opt's.
NEW_READERS = [
    "lm_gdn_device_ms", "lm_attention_device_ms", "lm_moe_device_ms",
    "lm_other_device_ms", "lm_flash_attention_roofline",
    "lm_gdn_walk_roofline", "lm_mla_attention_device_ms",
    "lm_mla_projections_device_ms", "lm_mla_moe_device_ms",
    "lm_mla_other_device_ms", "lm_mla_attention_roofline",
    "step_cem_tower_device_ms", "step_backward_device_ms"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_scope_reader_finds_nothing_without_whole_executions(name):
  read = _reader(name)
  assert read(_run(None)) is None  # untraced
  planes, metadata = _scoped_recording(runs=("tail", "head"))
  cut = tr.reduce_planes(planes, 1, program="jit_k_steps",
                         metadata=metadata)
  assert cut["program_runs"] == 0
  assert read(_run(cut)) is None
  # A recording of the reducer before PR 40 (a parent's): no tables.
  assert read(_run({"program_runs": 2, "program_busy_s": 1.0})) is None


def test_device_ms_readers_on_the_made_up_program(scoped_trace):
  """Milliseconds a step, k = 1: two whole executions, two steps. The
  expert layer's row adds the grouped products by name; the rows add
  up to the program's self time a step."""
  run = _run(scoped_trace)
  assert _reader("lm_mla_attention_device_ms")(run) == pytest.approx(0.380)
  assert _reader("lm_mla_projections_device_ms")(run) == pytest.approx(
      0.120)
  assert _reader("lm_mla_moe_device_ms")(run) == pytest.approx(0.013)
  assert _reader("lm_mla_other_device_ms")(run) == pytest.approx(0.032)
  # The other family's scopes, and QT-Opt's, do not occur.
  assert _reader("lm_gdn_device_ms")(run) is None
  assert _reader("lm_attention_device_ms")(run) is None
  assert _reader("step_cem_tower_device_ms")(run) is None
  assert sum(_reader(name)(run) for name in (
      "lm_mla_attention_device_ms", "lm_mla_projections_device_ms",
      "lm_mla_moe_device_ms", "lm_mla_other_device_ms")) == pytest.approx(
          1e3 * scoped_trace["program_self_s"] / 2)
  # The families share the expert layer's reader and the rest's.
  assert _reader("lm_moe_device_ms") is _reader("lm_mla_moe_device_ms")
  assert _reader("lm_other_device_ms") is _reader("lm_mla_other_device_ms")
  # Two steps a program: half the time a step.
  assert _reader("lm_mla_attention_device_ms")(_run(scoped_trace, k=2)) \
      == pytest.approx(0.190)


def test_qtopt_scope_readers():
  ops = [("while.1", "", "while", None),
         ("fusion.1", "jit(k_steps)/while/body/cem_tower/conv:",
          "convolution fusion", 50.0),
         ("fusion.2", "jit(k_steps)/while/body/backward/jvp(torso)/conv:",
          "convolution fusion", 20.0),
         ("fusion.3", "jit(k_steps)/while/body/backward/"
          "transpose(jvp(torso))/conv:", "convolution fusion", 30.0),
         ("fusion.4", "jit(k_steps)/while/body/torso/conv:",
          "convolution fusion", 7.0)]
  planes, metadata = _scoped_recording(ops, ("whole", "whole", "whole"))
  run = _run(tr.reduce_planes(planes, 1, program="jit_k_steps",
                              metadata=metadata), "qtopt_64", k=4)
  assert _reader("step_cem_tower_device_ms")(run) == pytest.approx(
      0.050 / 4)
  assert _reader("step_backward_device_ms")(run) == pytest.approx(
      0.050 / 4)


def _least_s(cost):
  return max(cost["flops"] / peaks.peak("TPU v5 lite", "bf16_flops"),
             cost["bytes"] / peaks.peak("TPU v5 lite", "hbm_bytes_per_s"))


def test_mla_attention_roofline_on_made_up_calls(scoped_trace, capsys):
  """One forward and two backward calls a step against the cost
  function: the forward program's least time once, the dK/dV and the dQ
  program's once each, over the measured 380 us a step."""
  run = _run(scoped_trace)
  model = run["config"]["model"]
  costs = mla_lm_flops.attention_kernel_costs(model, 2, 8192)
  least = sum(_least_s(costs[name]) for name in ("forward", "dkdv", "dq"))
  assert _reader("lm_mla_attention_roofline")(run) == pytest.approx(
      100 * least / 380e-6)
  # The hybrid model's reader looks under `gated_attention`: nothing.
  assert _reader("lm_flash_attention_roofline")(
      _run(scoped_trace, "qwen3next_80b_a3b_ep16", batch=4)) is None
  # An odd number of calls on the way back is no reading.
  planes, metadata = _scoped_recording(
      [op for op in SCOPED_OPS if op[0] != "flash_attention.9"],
      ("whole", "whole", "whole"))
  odd = tr.reduce_planes(planes, 1, program="jit_k_steps",
                         metadata=metadata)
  assert _reader("lm_mla_attention_roofline")(_run(odd)) is None
  assert "not pairs" in capsys.readouterr().err


def test_flash_and_walk_rooflines_of_the_hybrid_model():
  """The hybrid model's step as its trace names it: one flash call
  each way under `gated_attention`; under `gated_delta/scan` two
  forward calls of the walk (one of them a recomputation that a
  backward call follows, so it saves the states) and one backward."""
  scan = (STEP + "{}/trunk/blocks_1/mixer/gated_delta/scan/while/body/"
          "closed_call/checkpoint/{}")
  back = "transpose(jvp(Net))/trunk/jvp(Net)/trunk/checkpoint"
  flash = STEP + "{}/trunk/blocks_3/mixer/gated_attention/" + FLASH
  ops = [("while.1", "", "while", None),
         ("closed_call.4", scan.format("jvp(Net)", "closed_call/"
                                       "pallas_call:"),
          "custom-call", 900.0),
         # XLA's own custom call beside a loop: no Pallas program.
         ("custom-call.77", scan.format(back, "")[:-len(
             "body/closed_call/checkpoint/")].rstrip("/") + ":",
          "custom-call", 0.001),
         ("flash_attention.1", flash.format("jvp(Net)"), "custom-call",
          20000.0),
         ("rematted_computation.4", scan.format(
             back, "rematted_computation/pallas_call:"), "custom-call",
          1500.0),
         ("checkpoint.4", scan.format(back, "pallas_call:"),
          "custom-call", 1900.0),
         ("fusion.9", scan.format(back, "dot_general:"),
          "convolution fusion", 700.0),
         ("flash_attention.2", flash.format(back), "custom-call", 33000.0),
         ("flash_attention.3", flash.format(back), "custom-call",
          25000.0)]
  planes, metadata = _scoped_recording(ops, ("whole", "whole", "whole"))
  run = _run(tr.reduce_planes(planes, 1, program="jit_k_steps",
                              metadata=metadata),
             "qwen3next_80b_a3b_ep16", batch=4)
  model = run["config"]["model"]
  costs = lm_flops.attention_kernel_costs(model, 4, 8192)
  least = sum(_least_s(costs[name]) for name in ("forward", "dkdv", "dq"))
  assert _reader("lm_flash_attention_roofline")(run) == pytest.approx(
      100 * least / 78e-3)
  walk = lm_flops.walk_kernel_costs(model, 1, 8192)
  least = sum(_least_s(walk[name]) for name in (
      "forward", "forward_saving_states", "backward"))
  assert _reader("lm_gdn_walk_roofline")(run) == pytest.approx(
      100 * least / 4.3e-3)
  assert 50 < _reader("lm_gdn_walk_roofline")(run) < 100
  # The scope's row holds the kernels and the fusion beside them.
  assert _reader("lm_gdn_device_ms")(run) == pytest.approx(5.0, rel=1e-5)
  assert _reader("lm_attention_device_ms")(run) == pytest.approx(78.0)


def _xla(fn, *shapes):
  import jax
  import jax.numpy as jnp
  args = [jax.ShapeDtypeStruct(shape, jnp.float32) for shape in shapes]
  outs = jax.tree_util.tree_leaves(jax.eval_shape(fn, *args))
  moved = sum(x.size for x in args) + sum(x.size for x in outs)
  flops = jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]
  return flops, moved


SMALL_GQA = dict(
    hidden_size=128, sequence_length=128, num_attention_heads=4,
    num_key_value_heads=2, head_dim=64, linear_num_key_heads=1,
    linear_num_value_heads=1, linear_key_head_dim=128,
    linear_value_head_dim=256)


def test_attention_kernel_costs_against_xlas_cost_analysis():
  """The three programs' products written out plainly on 2 rows of 128
  positions, 4 query heads over 2 key-value heads of 64: XLA counts
  all T x T pairs where the kernel's count keeps the causal T (T + 1)
  / 2, and nothing else differs; the bytes are the plain functions'
  arguments and results, one element each way, and the two row vectors
  in float32."""
  import jax.numpy as jnp
  b, t, h, kv, d = 2, 128, 4, 2, 64
  q_shape, k_shape = (b, h, t, d), (b, kv, t, d)
  costs = lm_flops.attention_kernel_costs(SMALL_GQA, b, t,
                                          bytes_per_element=1)
  every_pair = 2 * t / (t + 1)
  wide = lambda x: jnp.repeat(x, h // kv, axis=1)  # noqa: E731
  narrow = lambda x: x.reshape(b, kv, h // kv, t, d).sum(2)  # noqa: E731
  scores = lambda q, k: jnp.einsum("bhqd,bhkd->bhqk", q, wide(k))  # noqa: E731

  def forward(q, k, v):
    return jnp.einsum("bhqk,bhkd->bhqd", scores(q, k), wide(v))

  def dkdv(q, k, v, do):
    p = scores(q, k)
    dp = jnp.einsum("bhqd,bhkd->bhqk", do, wide(v))
    return (narrow(jnp.einsum("bhqk,bhqd->bhkd", dp, q)),
            narrow(jnp.einsum("bhqk,bhqd->bhkd", p, do)))

  def dq(q, k, v, do):
    dp = jnp.einsum("bhqd,bhkd->bhqk", do, wide(v))
    return jnp.einsum("bhqk,bhkd->bhqd", dp + scores(q, k), wide(k))

  rows = b * h * t * 4  # one float32 row vector
  for name, fn, shapes, vectors in (
      ("forward", forward, (q_shape, k_shape, k_shape), 1),
      ("dkdv", dkdv, (q_shape, k_shape, k_shape, q_shape), 2),
      ("dq", dq, (q_shape, k_shape, k_shape, q_shape), 2)):
    flops, moved = _xla(fn, *shapes)
    want = costs[name]["flops"] * every_pair
    assert want <= flops <= 1.03 * want, (name, flops, want)
    assert costs[name]["bytes"] == moved + vectors * rows, name
  # At the cell's widths the FLOP peak bounds all three programs.
  with open(os.path.join(HERE, "configs",
                         "qwen3next_80b_a3b_ep16.json")) as f:
    cell = lm_flops.attention_kernel_costs(json.load(f)["model"], 4, 8192)
  for cost in cell.values():
    assert cost["flops"] / 197e12 > 5 * cost["bytes"] / 819e9
  assert cell["forward"]["flops"] == 4 * 16 * 8192 * 8193 / 2 * 4 * 256


def test_walk_kernel_costs_against_xlas_cost_analysis():
  """One head and chunk of the walk written out plainly (the products
  of `ops/delta_rule_walk.py`'s two kernels), keys of 128 over values
  of 256: XLA's FLOPs are the products' and a per cent of elementwise
  work; the bytes are the arguments and results less the state, which
  stays on the chip, at 4 bytes an element for the float32 tiles and
  `bytes_per_element` for the three key operands."""
  c, dk, dv = lm_flops.CHUNK, 128, 256
  costs = lm_flops.walk_kernel_costs(SMALL_GQA, 1, c, bytes_per_element=4)
  tile, keys, state = (c, dv), (c, dk), (dk, dv)

  def forward(writes, k_decayed, q_decayed, k_to_end, end_decay, s):
    new = writes - k_decayed @ s
    return new, q_decayed @ s, end_decay * s + k_to_end.T @ new

  def backward(k_decayed, q_decayed, k_to_end, end_decay, new, s, d_new,
               d_carried, d_end):
    d_new = d_new + k_to_end @ d_end
    return (d_new, -(d_new @ s.T), d_carried @ s.T, new @ d_end.T,
            (s * d_end).sum(),
            d_end * end_decay + q_decayed.T @ d_carried
            - k_decayed.T @ d_new)

  size = lambda shape: 4 * shape[0] * shape[1]  # noqa: E731
  flops, moved = _xla(forward, tile, keys, keys, keys, (1, 1), state)
  assert costs["forward"]["flops"] == 3 * 2 * c * dk * dv
  assert costs["forward"]["flops"] <= flops \
      <= 1.02 * costs["forward"]["flops"]
  # Arguments and results less the state in and out.
  assert costs["forward"]["bytes"] == 4 * moved - 2 * size(state)
  assert costs["forward_saving_states"]["bytes"] \
      == costs["forward"]["bytes"] + size(state)
  flops, moved = _xla(backward, keys, keys, keys, (1, 1), tile, state,
                      tile, tile, state)
  assert costs["backward"]["flops"] == 6 * 2 * c * dk * dv
  assert costs["backward"]["flops"] <= flops \
      <= 1.02 * costs["backward"]["flops"]
  # Less the state's cotangent in and out: it stays on the chip too.
  assert costs["backward"]["bytes"] == 4 * moved - 2 * size(state)
  # The kernel's header: 208 and 288 KB a head and chunk at the cell's
  # widths in bfloat16; the HBM bounds both programs.
  with open(os.path.join(HERE, "configs",
                         "qwen3next_80b_a3b_ep16.json")) as f:
    cell = lm_flops.walk_kernel_costs(json.load(f)["model"], 1, 8192)
  units = 32 * 128
  assert cell["forward_saving_states"]["bytes"] / units // 1024 == 208
  assert cell["backward"]["bytes"] / units // 1024 == 288
  for cost in cell.values():
    assert cost["bytes"] / 819e9 > 5 * cost["flops"] / 197e12


def test_op_metadata_of_the_recorded_scan():
  """The wire-format reader on PR 23's recording: the scan's fusion
  with its `tf_op` and category, a copy with a category and no
  `tf_op`, nothing of the host's planes."""
  metadata = tr.op_metadata(RECORDED)
  assert list(metadata) == ["/device:TPU:0"]
  by_short = {tr.op_name(name): pair
              for name, pair in metadata["/device:TPU:0"].items()}
  assert by_short["fusion.9"] == (
      "jit(prog)/while/body/closed_call/dot_general:",
      "convolution fusion")
  assert by_short["while"] == ("", "while")
  assert by_short["copy.15"] == ("", "data formatting")
  assert by_short["custom-call"] == ("", "custom-call")
  # Every operation of the recording's line has its metadata.
  ops = tr.load(RECORDED)["/device:TPU:0"][tr.OPS_LINE]
  assert {name for name, _, _ in ops} <= set(metadata["/device:TPU:0"])


def test_new_per_layer_entries_resolve():
  """The thirteen entries of PR 40, each from the device trace, each
  with a reader of its name and the cells of its family."""
  with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
    bench = json.load(f)
  entries = {m["name"]: m for m in bench["per_layer"]}
  cells = {w["name"] for w in bench["workloads"]}
  for name in NEW_READERS:
    entry = entries[name]
    assert entry["source"] == "device_trace"
    assert entry["moves"] == "train_steps_per_s"
    assert set(entry["workloads"]) <= cells
    family = ("joyai" if name.startswith("lm_mla_") else
              "qwen3next" if name.startswith("lm_") else "qtopt")
    assert all(cell.startswith(family) for cell in entry["workloads"])
    assert callable(_reader(name))
    assert entry["unit"] == ("%" if name.endswith("_roofline") else "ms")
    assert entry["better"] == ("higher" if entry["unit"] == "%"
                               else "lower")


@pytest.fixture(scope="module")
def recorded_scopes():
  return tr.reduce_trace(RECORDED_SCOPES, 1, program="jit_scoped_step")


def test_op_metadata_of_the_recorded_scopes():
  """PR 40's recording (`tools/trace_probe.py <dir> scopes` on a TPU
  v5e): a scope stands in a `tf_op` inside the transforms that wrap it
  on the way forward and as a path component under the checkpoint on
  the way back; the kernel's four calls are `custom-call`s whose
  `tf_op` ends in `pallas_call`."""
  by_short = {tr.op_name(name): pair for name, pair in
              tr.op_metadata(RECORDED_SCOPES)["/device:TPU:0"].items()}
  assert by_short["flash_attention.4"] == (
      "jit(scoped_step)/jvp(mla/attend)/jit(flash_attention)/"
      "pallas_call:", "custom-call")
  assert by_short["flash_attention.5"] == (
      "jit(scoped_step)/transpose(jvp(jvp()))/checkpoint/"
      "rematted_computation/mla/attend/jit(flash_attention)/"
      "pallas_call:", "custom-call")
  assert by_short["fusion.5"] == (
      "jit(scoped_step)/transpose(jvp(jvp()))/checkpoint/dense_ffn/"
      "dot_general:", "convolution fusion")
  assert by_short["copy-done"] == ("", "copy-done")
  flash = {short: (tr.scope_of(tf_op), tr.pass_of(tf_op),
                   tr.primitive_of(tf_op))
           for short, (tf_op, _) in by_short.items()
           if short.startswith("flash_attention")}
  assert flash == {
      "flash_attention.4": ("mla/attend", "forward", "pallas_call"),
      "flash_attention.5": ("mla/attend", "recompute", "pallas_call"),
      "flash_attention.6": ("mla/attend", "backward", "pallas_call"),
      "flash_attention.7": ("mla/attend", "backward", "pallas_call")}


def test_recorded_scopes_by_scope_pass_and_kernel(recorded_scopes):
  """Three executions of the probe's gradient step, all whole: both
  scopes in all three passes, copies under `unnamed`, the whole summing
  to the program's self time; the flash kernel's forward program once a
  step forward and once as a recomputation, its two backward programs
  once each."""
  trace = recorded_scopes
  assert trace["program_runs"] == 3
  assert set(trace["scope_ns"]) == {"mla/attend", "dense_ffn", "unnamed"}
  for scope in ("mla/attend", "dense_ffn"):
    assert all(trace["scope_ns"][scope][which] > 0 for which in tr.PASSES)
  assert trace["scope_ns"]["unnamed"]["backward"] == 0
  assert sum(ns for passes in trace["scope_ns"].values()
             for ns in passes.values()) == pytest.approx(
                 1e9 * trace["program_self_s"])
  # Each operation at the median of its three occurrences.
  assert trace["program_self_s"] == pytest.approx(trace["program_busy_s"],
                                                  rel=0.02)
  calls = {k["pass"]: k["calls"] for k in trace["kernels"]}
  assert calls == {"forward": 3, "recompute": 3, "backward": 6}
  assert all(k["name"] == "flash_attention" and k["scope"] == "mla/attend"
             and k["primitive"] == "pallas_call" for k in trace["kernels"])
  assert trace["device_ops"][0][0] == "mla/attend:flash_attention"
  assert sum(s for _, s in trace["device_ops"]) == pytest.approx(
      trace["busy_s"])


def test_readers_on_the_recorded_scopes(recorded_scopes):
  """The readers on a recording of the chip: ms a step, and the flash
  kernel's roofline share at the probe's size (1 row of 256 positions,
  2 heads of 128: a few microseconds a call, far from any peak, but a
  share between 0 and 100)."""
  from benchmark.layer_metrics import device_scopes
  run = {"trace": recorded_scopes, "k": 1, "batch": 1, "chips": 1,
         "device_kind": "TPU v5 lite", "records": [], "config": {
             "model": dict(num_attention_heads=2, qk_nope_head_dim=64,
                           qk_rope_head_dim=64, v_head_dim=128,
                           sequence_length=256)}}
  ms = _reader("lm_mla_attention_device_ms")(run)
  assert ms == pytest.approx(
      sum(recorded_scopes["scope_ns"]["mla/attend"].values()) / 3e6)
  assert _reader("lm_mla_other_device_ms")(run) == pytest.approx(
      1e3 * recorded_scopes["program_self_s"] / 3 - ms)
  assert _reader("lm_mla_moe_device_ms")(run) is None
  share = _reader("lm_mla_attention_roofline")(run)
  forward, back, measured = device_scopes.family(run, "mla/attend",
                                                 "flash_attention")
  assert (forward, back) == (6, 6)
  assert measured == pytest.approx(25.16e-6, rel=1e-3)
  assert 1 < share < 100


def test_a_stall_of_the_device_moves_no_scope():
  """One occurrence of the grouped product 15 ms over its two others
  (the device stalled under it in one execution of three): the tables
  count an operation at the median of its occurrences, so neither the
  expert layer's row nor the rest moves; `busy_s` keeps the stall."""
  runs = ("tail", "whole", "whole", "whole", "head")
  planes, metadata = _scoped_recording(runs=runs)
  steady = tr.reduce_planes(planes, 1, program="jit_k_steps",
                            metadata=metadata)
  events = planes["/device:TPU:0"][tr.OPS_LINE]
  hit = [i for i, (name, _, _) in enumerate(events)
         if name.startswith("%ragged-dot-none.3")][2]
  stall = 15e6
  name, start, dur = events[hit]
  shifted = []
  for i, (n, s0, d) in enumerate(events):
    if i == hit:
      shifted.append((n, s0, d + stall))
    elif n.startswith("%while") and s0 <= start < s0 + d:
      shifted.append((n, s0, d + stall))  # its umbrella
    else:
      shifted.append((n, s0 + stall if s0 > start else s0, d))
  lines = planes["/device:TPU:0"]
  lines[tr.OPS_LINE] = shifted
  lines[tr.MODULES_LINE] = [
      (n, s0 + stall if s0 > start else s0,
       d + stall if s0 <= start < s0 + d else d)
      for n, s0, d in lines[tr.MODULES_LINE]]
  stalled = tr.reduce_planes(planes, 1, program="jit_k_steps",
                             metadata=metadata)
  assert stalled["program_runs"] == steady["program_runs"] == 3
  assert stalled["busy_s"] == pytest.approx(steady["busy_s"] + 15e-3)
  assert stalled["scope_ns"] == steady["scope_ns"]
  assert stalled["kernels"] == steady["kernels"]
  for name in ("lm_moe_device_ms", "lm_other_device_ms"):
    assert _reader(name)(_run(stalled)) == pytest.approx(
        _reader(name)(_run(steady)))
