"""The control plane's span recorder and the round program's phase scopes.

* off (the default), a whole FedCD round records nothing, ``span``
  hands out the one shared null context, and entering it builds no
  span, annotation or counter and reads no clock;
* on, one round records the layer spans under the root ``fedcd.round``
  with the round's id, its counters agree with the plan's work batch,
  and ``wall_s`` is the root span's duration;
* ``drain`` hands the records out once;
* the fused round program carries the five phase scopes in its HLO.
"""
import dataclasses
import time

import jax
import numpy as np
import pytest

from repro import spans
from repro.configs.fedcd_cifar import HIERARCHICAL
from repro.core.fedcd import FedCDServer
from repro.data.partition import hierarchical_devices, stack_devices
from repro.federated.simulation import make_fused_round, train_bucket
from repro.models.mlp import init_mlp_classifier, mlp_accuracy, mlp_loss

# spans below the root, each with the parent it must have in a plain
# fused round (no churn events, no checkpoint, no extra eval)
CHILDREN = {
    "fedcd.churn": "fedcd.round",
    "fedcd.sample": "fedcd.round",
    "fedcd.plan": "fedcd.round",
    "fedcd.launch": "fedcd.round",
    "fedcd.launch.args": "fedcd.launch",
    "fedcd.launch.dispatch": "fedcd.launch",
    "fedcd.readback": "fedcd.round",
    "fedcd.readback.wait": "fedcd.readback",
    "fedcd.readback.copy": "fedcd.readback",
    "fedcd.readback.release": "fedcd.readback",
    "fedcd.lifecycle": "fedcd.round",
    "fedcd.collect": "fedcd.round",
}
SCOPES = ("fedcd.train", "fedcd.aggregate", "fedcd.scatter",
          "fedcd.eval_val", "fedcd.eval_test")


@pytest.fixture(autouse=True)
def recorder_off():
    spans.disable()
    spans.drain()
    yield
    spans.disable()
    spans.drain()


def _server(spec="fused"):
    devs = hierarchical_devices(seed=0, devices_per_archetype=1,
                                n_train=64, n_val=32, n_test=32,
                                noise=2.0)[:8]
    cfg = dataclasses.replace(HIERARCHICAL, n_devices=8,
                              devices_per_round=4, milestones=(),
                              max_models=4, seed=0)
    params = init_mlp_classifier(jax.random.PRNGKey(0), hidden=16)
    return FedCDServer(cfg, params, mlp_loss, mlp_accuracy,
                       stack_devices(devs), batch_size=16, spec=spec)


def test_off_records_nothing_and_hands_out_the_null_context():
    srv = _server()
    srv.run_round(1)
    srv.run_round(2)
    rec = spans.drain()
    assert rec.spans == [] and rec.counts == {}
    assert spans.span("fedcd.round") is spans.NULL
    assert spans.span("x", start_ns=5) is spans.NULL
    assert srv.metrics[-1].wall_s > 0


def test_off_span_allocates_nothing_and_reads_no_clock(monkeypatch):
    def forbidden(*a, **k):
        raise AssertionError("the recorder did work while off")

    # everything the recorder could allocate or read while recording
    monkeypatch.setattr(time, "perf_counter_ns", forbidden)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", forbidden)
    monkeypatch.setattr(spans, "_Span", forbidden)
    monkeypatch.setattr(spans, "Span", forbidden)
    monkeypatch.setattr(spans, "_counts", None)
    monkeypatch.setattr(spans, "_open", None)
    spans.round_scope(3)
    for _ in range(100):
        with spans.span("fedcd.launch") as s:
            spans.count("pairs", 1000)
        assert s is spans.NULL
    with spans.span("fedcd.round", start_ns=1) as s:
        s.end_ns = 2
    assert s is spans.NULL


def test_on_records_the_round_spans_counters_and_wall():
    srv = _server()
    srv.run_round(1)
    plans = []
    build = srv.planner.build
    srv.planner.build = lambda *a, **k: plans.append(build(*a, **k)) \
        or plans[-1]
    spans.enable()
    srv.run_round(2)
    spans.disable()
    rec = spans.drain()

    assert {s.round for s in rec.spans} == {2}
    roots = [s for s in rec.spans if s.name == "fedcd.round"]
    assert len(roots) == 1 and roots[0].parent is None
    root = roots[0]
    assert (root.end_ns - root.start_ns) * 1e-9 == pytest.approx(
        srv.metrics[-1].wall_s, rel=1e-12)
    names = {s.name for s in rec.spans}
    assert set(CHILDREN) <= names
    for s in rec.spans:
        if s.name in CHILDREN:
            assert s.parent == CHILDREN[s.name], s
            assert root.start_ns <= s.start_ns <= s.end_ns <= root.end_ns
    # the readback's parts lie inside it, in order
    parts = [s for s in rec.spans if s.parent == "fedcd.readback"]
    assert [s.name for s in parts] == ["fedcd.readback.wait",
                                       "fedcd.readback.copy",
                                       "fedcd.readback.release"]

    plan = plans[0]
    counts = rec.counts[2]
    assert counts["pairs"] == len(plan.pair_model) > 0
    assert counts["pairs"] + counts["pairs_padded"] == train_bucket(
        len(plan.pair_model))
    assert counts["val_rows"] == len(plan.val_stale) > 0
    assert counts["test_rows"] == len(plan.test_stale)
    assert counts["d2h_bytes"] == 4 * srv.executor.n_devices * (
        len(plan.val_stale) + len(plan.test_stale))
    assert "eval_rows_extra" not in counts


def test_pipelined_round_records_its_speculation():
    srv = _server("fused+pipeline")
    srv.run_round(1)
    spans.enable()
    srv.run_round(2)
    spans.disable()
    rec = spans.drain()
    parent = {s.name: s.parent for s in rec.spans}
    assert parent["fedcd.speculate"] == "fedcd.round"
    # round 3's training is dispatched inside round 2's speculation, so
    # its pairs count on round 2
    under = [s for s in rec.spans if s.parent == "fedcd.speculate"]
    assert {s.name for s in under} == {"fedcd.launch.args",
                                       "fedcd.launch.dispatch"}
    assert rec.counts[2]["pairs"] == len(srv._prefetch[1][0].nonzero()[0])


def test_collect_fallback_eval_is_spanned_and_counted():
    srv = _server()
    srv.run_round(1)
    # no predicted test rows: the round scores no test row, and collect
    # falls back to one eval of the preferred model's row
    srv.executor._pred_rows = []
    spans.enable()
    srv.run_round(2)
    spans.disable()
    rec = spans.drain()
    extra = [s for s in rec.spans if s.name == "fedcd.collect.eval"]
    assert len(extra) == 1 and extra[0].parent == "fedcd.collect"
    assert rec.counts[2]["eval_rows_extra"] == 1
    assert rec.counts[2]["test_rows"] == 0


def test_drain_hands_out_once():
    spans.enable()
    spans.round_scope(7)
    with spans.span("fedcd.plan"):
        spans.count("pairs", 3)
    spans.count("pairs", 2)
    rec = spans.drain()
    assert [(s.name, s.parent, s.round) for s in rec.spans] == [
        ("fedcd.plan", None, 7)]
    assert rec.counts == {7: {"pairs": 5}}
    again = spans.drain()
    assert again.spans == [] and again.counts == {}


def test_span_keeps_the_stamps_its_caller_read():
    spans.enable()
    with spans.span("fedcd.round", start_ns=10) as s:
        s.end_ns = 25
    (rec,) = spans.drain().spans
    assert (rec.start_ns, rec.end_ns) == (10, 25)


def test_fused_round_program_carries_the_phase_scopes():
    srv = _server()
    xs, ys = srv.databank.splits["train"]
    vx, vy = srv.databank.splits["val"]
    t = 4
    b = train_bucket(4)
    prog = make_fused_round(mlp_loss, mlp_accuracy, 0.1)
    i32 = np.int32
    hlo = prog.lower(
        srv.registry.params.tree, np.zeros(b, i32), np.zeros(b, i32),
        np.zeros((b, t, 16), i32), np.ones((1, b), np.float32),
        np.zeros(1, i32), np.zeros(1, i32), np.zeros(1, i32),
        xs, ys, vx, vy, vx, vy).compile().as_text()
    for scope in SCOPES:
        assert f"/{scope}/" in hlo, scope
