"""The reduction of a trace by the program's own phases and spans
(``bench/phases.py``), on hand-built event lists and on a few recorded
rounds of a chip trace. Run on the CPU: ``python -m pytest -q
bench/tests``."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import phases, trace

HERE = Path(__file__).resolve().parent
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _ev(plane, line, name, start, dur):
    return (plane, line, name, float(start), float(dur))


def _op(scope, start, dur, plane=DEV):
    return _ev(plane, trace.OPS_LINE, scope, start, dur)


def _span(name, start, dur):
    return _ev(HOST, "python3", name, start, dur)


def test_op_scope_takes_the_innermost_fedcd_component():
    assert phases.op_scope(
        "jit(round_step)/fedcd.train/while/body/closed_call/conv") == \
        "fedcd.train"
    assert phases.op_scope(
        "jit(f)/fedcd.eval/fedcd.eval_val/while") == "fedcd.eval_val"
    assert phases.op_scope("jit(round_step)/copy") == phases.UNSCOPED
    assert phases.op_scope("") == phases.UNSCOPED


HLO = """HloModule jit_round_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="j/fedcd.train/add"}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %mul.2 = f32[8]{0} multiply(%param_0.1), metadata={op_name="j/fedcd.aggregate/m"}
  ROOT %bitcast.3 = f32[8]{0} bitcast(%mul.2)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="stacked"}
  %fusion.1 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %copy.4 = f32[8]{0} copy(%fusion.2)
  ROOT %convolution.5 = f32[8]{0} convolution(%copy.4), metadata={op_name="j/fedcd.eval_val/c"}
}
"""


def test_hlo_op_names_read_instructions_and_fusions():
    module, names = phases.hlo_op_names(HLO)
    assert module == "jit_round_step"
    scopes = {k: phases.op_scope(v) for k, v in names.items()}
    assert scopes["convolution.5"] == "fedcd.eval_val"
    assert scopes["fusion.1"] == "fedcd.train"       # its root's
    assert scopes["fusion.2"] == "fedcd.aggregate"   # a scoped member's
    assert scopes["copy.4"] == phases.UNSCOPED       # made by the compiler
    assert scopes["Arg_0.1"] == phases.UNSCOPED


def test_name_ops_looks_up_each_op_in_its_program():
    _, names = phases.hlo_op_names(HLO)
    events = [
        _span(trace.WINDOW, 0, 1000),
        _ev(DEV, phases.MODULES_LINE, "jit_round_step(123)", 100, 500),
        _op("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %Arg_0.1)", 100, 50),
        _op("%convolution.5 = f32[8]{0} convolution(...)", 400, 50),
        _op("%copy.4 = f32[8]{0} copy(...)", 300, 50),
        # outside any program of the trace, or in an unknown one
        _op("%fusion.1 = f32[8]{0} fusion(...)", 700, 50),
        _ev(DEV, phases.MODULES_LINE, "jit_other(7)", 800, 100),
        _op("%fusion.1 = f32[8]{0} fusion(...)", 800, 50),
    ]
    named = phases.name_ops(events, {"jit_round_step": names})
    assert [e[2] for e in named if e[1] == trace.OPS_LINE] == [
        "fedcd.train", "fedcd.eval_val", "unscoped", "unscoped", "unscoped"]
    # everything else passes through unchanged
    assert [e for e in named if e[1] != trace.OPS_LINE] == [
        e for e in events if e[1] != trace.OPS_LINE]


def test_device_by_scope_counts_self_time_of_nested_ops():
    events = [
        _span(trace.WINDOW, 0, 1000),
        # a loop of the train scope nests its body's ops: its own time
        # is what they leave uncovered
        _op("fedcd.train", 100, 400),
        _op("fedcd.train", 150, 100),
        _op("unscoped", 300, 50),       # an unscoped op inside the loop
        _op("fedcd.aggregate", 500, 20),
        _op("fedcd.eval_val", 600, 300),
        _op("fedcd.eval_test", 950, 100),   # clipped at the window's end
    ]
    by = phases.device_by_scope(events)
    assert by == {"fedcd.aggregate": pytest.approx(20e-9),
                  "fedcd.eval_test": pytest.approx(50e-9),
                  "fedcd.eval_val": pytest.approx(300e-9),
                  "fedcd.train": pytest.approx(350e-9),
                  "unscoped": pytest.approx(50e-9)}
    # the scopes add up to the busy time of trace.reduce
    assert sum(by.values()) == pytest.approx(trace.reduce(events)["busy_s"])


def test_device_by_scope_averages_over_devices():
    events = [_span(trace.WINDOW, 0, 100),
              _op("fedcd.train", 0, 100),
              _op("fedcd.train", 0, 50, plane="/device:TPU:1")]
    assert phases.device_by_scope(events) == {
        "fedcd.train": pytest.approx(75e-9)}


def test_idle_goes_to_the_innermost_covering_span():
    events = [
        _span(trace.WINDOW, 0, 1000),
        _op("fedcd.train", 0, 100),
        _op("fedcd.train", 700, 200),
        # the device idles over [100, 700] and [900, 1000]
        _span("fedcd.round", 50, 900),
        _span("fedcd.readback", 60, 140),
        _span("fedcd.readback.wait", 60, 90),        # idle 100-150
        _span("fedcd.readback.copy", 150, 40),       # idle 150-190
        # 190-200: readback's own time; 200-300: the round's own
        _span("fedcd.lifecycle", 300, 100),          # idle 300-400
        _span("fedcd.launch", 400, 250),
        _span("fedcd.launch.dispatch", 600, 120),    # idle 600-700
        # 650-700 outside launch but inside dispatch's own interval;
        # 950-1000: no program span at all
        # a span named as the harness's own is not the program's
        _span("readback", 0, 1000),
    ]
    by = phases.idle_by_span(events)
    assert by == {"fedcd.readback.wait": pytest.approx(50e-9),
                  "fedcd.readback.copy": pytest.approx(40e-9),
                  "fedcd.readback": pytest.approx(10e-9),
                  "fedcd.round": pytest.approx(100e-9 + 50e-9),
                  "fedcd.lifecycle": pytest.approx(100e-9),
                  "fedcd.launch": pytest.approx(200e-9),
                  "fedcd.launch.dispatch": pytest.approx(100e-9),
                  "other": pytest.approx(50e-9)}
    # the idle accounting closes on the reduce's idle share
    r = trace.reduce(events)
    assert sum(by.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_dispatch_lag_runs_to_the_next_device_op():
    events = [
        _span(trace.WINDOW, 0, 1000),
        _span("fedcd.launch.dispatch", 100, 30),
        _op("fedcd.train", 180, 300),
        _op("fedcd.train", 200, 10),
        _span("fedcd.launch.dispatch", 600, 30),
        _op("unscoped", 640, 100),
        _span("fedcd.launch.dispatch", 990, 5),     # nothing after it
    ]
    assert phases.dispatch_lags(events) == [pytest.approx(80e-9),
                                            pytest.approx(40e-9)]


def test_reductions_need_one_window_and_a_device_op():
    with pytest.raises(ValueError):
        phases.reduce_phases([_op("fedcd.train", 0, 1)])
    with pytest.raises(ValueError):
        phases.reduce_phases([_span(trace.WINDOW, 0, 10)])


def test_counter_runs_group_consecutive_equal_rounds():
    counts = {6: {"pairs": 10}, 7: {"pairs": 10}, 8: {"pairs": 12},
              10: {"pairs": 12}, None: {"pairs": 1}}
    assert phases.counter_runs(counts) == [
        [6, 7, {"pairs": 10}], [8, 8, {"pairs": 12}],
        [10, 10, {"pairs": 12}]]


def test_phases_on_a_recorded_v5e_trace():
    # three window rounds of c10x100_fedsgd on one TPU v5e, traced by
    # bench/phases.py with the recorder on: the host spans, the device
    # ops by HLO instruction name and the op_name of each, read from
    # the compiled round program
    recorded = json.loads((HERE / "data" / "trace_v5e_phases.json")
                          .read_text())
    events = [tuple(e) for e in recorded["events"]]
    r = phases.reduce_phases(phases.name_ops(events, recorded["op_names"]))
    whole = trace.reduce(events)
    by = r["device_by_scope"]
    assert set(by) == {"fedcd.train", "fedcd.aggregate", "fedcd.scatter",
                       "fedcd.eval_val", "fedcd.eval_test", "unscoped"}
    assert sum(by.values()) == pytest.approx(whole["busy_s"], rel=1e-9)
    assert by["unscoped"] < 0.05 * whole["busy_s"]
    # training leads, the two eval rows match, eq 1 is small
    assert by["fedcd.train"] > by["fedcd.eval_val"] + by["fedcd.eval_test"]
    assert by["fedcd.eval_val"] == pytest.approx(by["fedcd.eval_test"],
                                                 rel=0.05)
    assert by["fedcd.aggregate"] + by["fedcd.scatter"] < 0.01 * sum(
        by.values())
    idle = r["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(
        whole["window_s"] - whole["busy_s"], rel=1e-9)
    assert idle.get("other", 0.0) < 0.01 * sum(idle.values())
    # one dispatch a round, each followed by its program within 5 ms
    lags = r["dispatch_lag_s"]
    assert len(lags) == 3 and all(0 < x < 5e-3 for x in lags)
