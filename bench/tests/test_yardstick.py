"""The yardstick's arithmetic: the CNN's FLOP count and the trace
reduction. Run on the CPU: ``python -m pytest -q bench/tests``."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import flops, trace

HERE = Path(__file__).resolve().parent


def test_cnn_forward_flops_match_the_hand_count():
    # eight 3x3 SAME convolutions: 105,578,496; fc1 1024x256 and fc2
    # 256x10: 529,408 (a multiply-add counts two)
    assert flops.cnn_forward_flops() == 105_578_496 + 529_408
    assert round(flops.cnn_forward_flops() / 1e6, 1) == 106.1


def test_model_forward_flops_reads_the_configuration():
    config = json.loads((HERE.parent / "configs" /
                         "fedcd_cifar_c10x100_fedsgd.json").read_text())
    assert flops.model_forward_flops(config["model"]) == 106_107_904


def _ev(plane, line, name, start, dur):
    return (plane, line, name, float(start), float(dur))


def test_reduce_unions_ops_and_names_gaps_by_host_span():
    dev = "/device:TPU:0"
    events = [
        _ev("/host:CPU", "python", trace.WINDOW, 0, 1000),
        _ev("/host:CPU", "python", "launch", 0, 100),
        _ev("/host:CPU", "python", "readback", 100, 500),
        _ev("/host:CPU", "python", "collect", 700, 300),
        # a nested op counts once; the op past the window is clipped
        _ev(dev, trace.OPS_LINE, "%while.1 = (s32[]) while(...)", 100, 400),
        _ev(dev, trace.OPS_LINE, "%fusion.2 = f32[8] fusion(...)", 200, 100),
        _ev(dev, trace.OPS_LINE, "convolution.3", 900, 400),
        # not an op line, not counted
        _ev(dev, "XLA Modules", "jit_round", 100, 900),
    ]
    r = trace.reduce(events)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(500e-9)     # [100,500] + [900,1000]
    assert r["idle_share"] == pytest.approx(0.5)
    assert r["idle_gaps"][0] == ["collect", pytest.approx(400e-9)]
    assert r["idle_gaps"][1] == ["launch", pytest.approx(100e-9)]
    ops = dict((n, t) for n, t in r["device_ops"])
    assert ops == {"%while.1": pytest.approx(300e-9),
                   "%fusion.2": pytest.approx(100e-9),
                   "convolution.3": pytest.approx(100e-9)}


def test_reduce_averages_busy_over_devices():
    events = [_ev("/host:CPU", "python", trace.WINDOW, 0, 100),
              _ev("/device:TPU:0", trace.OPS_LINE, "a", 0, 100),
              _ev("/device:TPU:1", trace.OPS_LINE, "a", 0, 50)]
    r = trace.reduce(events)
    assert r["busy_s"] == pytest.approx(75e-9)
    assert r["n_devices"] == 2


def test_reduce_needs_one_window_and_a_device_op():
    with pytest.raises(ValueError):
        trace.reduce([_ev("/device:TPU:0", trace.OPS_LINE, "a", 0, 1)])
    with pytest.raises(ValueError):
        trace.reduce([_ev("/host:CPU", "python", trace.WINDOW, 0, 10)])


def test_self_times_subtract_nested_ops():
    iv = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"), (50, 60, "c")]
    got = dict(trace.self_times(iv))
    assert got == {"while": 30, "a": 20, "b": 40, "c": 10}


def test_reduce_on_a_recorded_v5e_trace():
    # 30 ms of a traced paper_one_model window on one TPU v5e (names cut
    # to the HLO instruction name): the round program's loop events
    # nest the ops of its body
    events = [tuple(e) for e in json.loads(
        (HERE / "data" / "trace_v5e_one_model.json").read_text())]
    r = trace.reduce(events)
    lo = next(e[3] for e in events if e[2] == trace.WINDOW)
    covered = set()
    for plane, line, _, s, d in events:
        if plane.startswith("/device:") and line == trace.OPS_LINE:
            a = max(int(s - lo) // 1000, 0)
            b = min(int(s + d - lo + 999) // 1000, 30_000)
            covered.update(range(a, b))
    assert r["window_s"] == pytest.approx(0.030)
    assert r["busy_s"] == pytest.approx(len(covered) * 1e-6, rel=2e-3)
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / 0.030)
    assert r["idle_gaps"][0][0] == "launch"
    # ops on a device line nest or follow one another, so their self
    # times add up to the busy time
    ops = [(s, s + d, n) for p, ln, n, s, d in events
           if ln == trace.OPS_LINE]
    busy = sum(e - s for s, e in trace.union((s, e) for s, e, _ in ops))
    assert sum(t for _, t in trace.self_times(ops)) == pytest.approx(busy)
