"""The phase reduction (``bench/harness/scopes.py``): the wire-format
reader against ``ProfileData`` on the recorded v5e trace, phase shares and
idle attribution on a scoped trace recorded on a v5e
(``bench/tools/record_scoped_trace.py``) and on a hand-made one."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench.harness.scopes import (
    Event,
    Line,
    Plane,
    phase_metrics,
    read_xspace,
    reduce_scopes,
    scope_of,
)
from bench.harness.xplane import reduce_planes, reduce_trace

DATA = Path(__file__).with_name("data")
TRACE = DATA / "tpu_v5e_small.xplane.pb"
SCOPED = DATA / "tpu_v5e_scoped.xplane.pb"


def _ops(planes):
    return [e for p in planes if p.name.startswith("/device:TPU:")
            for line in p.lines if line.name == "XLA Ops" for e in line.events]


def test_wire_reader_finds_the_op_metadata():
    fusions = [e for e in _ops(read_xspace(str(TRACE)))
               if e.name.startswith("%fusion = ")]
    assert fusions
    for e in fusions:
        assert e.stats["tf_op"] == "jit(<lambda>)/dot_general:"
        assert e.stats["flops"] == 537133056
        assert e.stats["hlo_category"] == "convolution fusion"


def test_wire_planes_reduce_as_profile_data_does():
    """``reduce_trace`` (``ProfileData``) keeps every key and value it had;
    the wire reader's planes give the same reduction to the nanosecond
    ``ProfileData`` rounds its times to."""
    before = reduce_trace(str(TRACE), {"round"})
    assert set(before) == {"window_s", "busy_s", "device_ops", "categories",
                           "idle_gaps", "n_devices"}
    wire = reduce_planes(read_xspace(str(TRACE)), {"round"})
    assert set(wire) == set(before)
    assert wire["n_devices"] == before["n_devices"]
    assert wire["window_s"] == pytest.approx(before["window_s"], abs=1e-9)
    assert wire["busy_s"] == pytest.approx(before["busy_s"], rel=1e-3)
    assert [n for n, _ in wire["device_ops"]] == [n for n, _ in before["device_ops"]]
    assert [n for n, _ in wire["idle_gaps"]] == [n for n, _ in before["idle_gaps"]]


def test_scoped_chip_trace_adds_up():
    planes = read_xspace(str(SCOPED))
    base = reduce_trace(str(SCOPED), {"round"})
    red = reduce_scopes(planes, {"round"})
    window, busy = base["window_s"], base["busy_s"]
    assert set(red["scopes"]) == {"mix", "local", "evolve"}
    assert all(v > 0 for v in red["scopes"].values())
    # the chip's compiler left the scan's loop op and the rank scatter
    # without a tf_op; they take the phase they run in, and only the
    # prefetch before the first phase stays unscoped
    bare = {e.stats.get("hlo_category") for e in _ops(planes)
            if "tf_op" not in e.stats}
    assert {"while", "custom fusion"} <= bare
    assert {n for n, _ in red["unscoped_ops"]} == {"jit_step: %copy-start",
                                                   "jit_step: %copy-done"}
    # the three phases are disjoint in time: with the rest of busy they
    # make up busy, and the step's program holds them all (busy from
    # ProfileData, whose times are cut to the nanosecond)
    scoped = sum(red["scopes"].values())
    assert scoped <= busy * (1 + 1e-6)
    step = next(v for k, v in red["modules"].items() if "step" in k)
    assert scoped <= step * (1 + 1e-6) and step <= busy * (1 + 1e-3)
    # idle: by span plus under none makes up the device's idle share
    idle_share = 100.0 * (1 - busy / window)
    assert 100.0 * red["idle_s"] / window == pytest.approx(idle_share, abs=0.1)
    by_span = red["idle_by_span"]
    assert set(by_span) == {"scale.inputs", "scale.dispatch", "scale.comm"}
    unattributed = red["idle_s"] - sum(by_span.values())
    assert unattributed >= -1e-12
    # three rounds of a 3 ms sleep under scale.inputs, 2 ms under no span
    assert by_span["scale.inputs"] >= 3 * 0.003 * 0.9
    assert unattributed >= 3 * 0.002 * 0.9
    m = phase_metrics(dict(base, **red), {"input_bytes": 3e6, "host_syncs": 6},
                      rounds=3)
    assert set(m) == {"mix_share.train", "local_share.train",
                      "evolve_share.train", "idle_inputs_share.train",
                      "idle_comm_share.train", "input_mb.train",
                      "host_syncs.train"}
    assert m["input_mb.train"] == pytest.approx(1.0)
    assert m["host_syncs.train"] == 2


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(round_step)/mix/dot_general:", "mix"),
    ("jit(round_step)/local/vmap()/while/body/closed_call/transpose(jvp())/conv", "local"),
    ("jit(round_step)/evolve/vmap(transpose(evolve))/add_any", "evolve"),
    ("jit(acc_stacked)/eval/vmap(acc_one)/argmax", "eval"),
    ("jit(<lambda>)/dot_general:", None),
    ("jit(f)/mix/local/add", None),
    (None, None),
])
def test_scope_of(tf_op, scope):
    assert scope_of(tf_op) == scope


def _ev(a, b, name, **stats):
    return Event(name, a, b - a, stats)


def test_hand_made_trace():
    step = {"program_id": 7}
    device = Plane("/device:TPU:0", [
        Line("XLA Modules", [_ev(0, 48, "jit_round_step(7)"),
                             _ev(80, 90, "jit_other(9)")]),
        Line("XLA Ops", [
            _ev(0, 10, "%a = add", tf_op="jit(round_step)/mix/add", **step),
            _ev(10, 12, "%copy.1 = copy", **step),
            # a loop op without metadata around its body's local ops
            _ev(20, 40, "%while = while", **step),
            _ev(25, 30, "%c = conv", tf_op="jit(round_step)/local/while/body/conv", **step),
            _ev(42, 44, "%s = sort", tf_op="jit(round_step)/evolve/jit(argsort)/sort", **step),
            # a rewritten scatter without metadata between two evolve ops
            _ev(44, 46, "%fusion.9 = fusion", **step),
            _ev(46, 48, "%w = select", tf_op="jit(round_step)/evolve/select_n", **step),
            _ev(80, 90, "%s = sort", tf_op="jit(other)/sort", program_id=9),
            _ev(120, 130, "%late = add", tf_op="jit(round_step)/evolve/add", **step),
        ])])
    host = Plane("/host:CPU", [Line("python", [
        _ev(0, 50, "round"), _ev(50, 100, "round"),
        _ev(0, 20, "scale.inputs"), _ev(12, 16, "scale.dispatch"),
        _ev(60, 95, "scale.comm"),
    ]), Line("other", [_ev(40, 80, "scale.eval")])])
    red = reduce_scopes([device, host], {"round"})
    assert red["scopes"] == {"mix": pytest.approx(10e-9),
                             "local": pytest.approx(20e-9),
                             "evolve": pytest.approx(6e-9)}   # "late" is out
    assert red["modules"] == {"jit_round_step": pytest.approx(38e-9),
                              "jit_other": pytest.approx(10e-9)}
    # between mix and local: no phase
    assert [n for n, _ in red["unscoped_ops"]] == ["jit_round_step: %copy.1"]
    # idle [12, 20] (inputs, dispatch innermost in [12, 16]), [40, 42],
    # [48, 80] (comm from 60; the other thread's span does not count),
    # [90, 100]
    assert red["idle_s"] == pytest.approx(52e-9)
    assert red["idle_by_span"] == {"scale.inputs": pytest.approx(4e-9),
                                   "scale.dispatch": pytest.approx(4e-9),
                                   "scale.comm": pytest.approx(25e-9)}
    # a program with no scopes, spans or counters gives no phase metrics
    bare = dict(red, scopes={}, idle_by_span={}, window_s=100e-9)
    assert phase_metrics(bare, {"step_calls": 2}, rounds=2) == {}
