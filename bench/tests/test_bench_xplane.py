"""The trace reduction: busy union, idle share, ops by self time and the
longest idle gaps, on a small trace recorded on a TPU v5e and on a
hand-made one."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench.harness.xplane import op_label, reduce_planes, reduce_trace

TRACE = Path(__file__).with_name("data") / "tpu_v5e_small.xplane.pb"


def _ev(a, b, name):
    return NS(name=name, start_ns=a, duration_ns=b - a, stats=[])


def test_recorded_tpu_trace():
    red = reduce_trace(str(TRACE), {"round"})
    assert red["n_devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    idle = red["window_s"] - red["busy_s"]
    gaps = [d for _, d in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and 0 < sum(gaps) <= idle * (1 + 1e-9)
    assert all(label.startswith("round") for label, _ in red["idle_gaps"])
    ops = dict(red["device_ops"])
    assert "%fusion (fusion:kOutput)" in ops
    assert sum(ops.values()) == pytest.approx(sum(red["categories"].values()))
    assert sum(ops.values()) >= red["busy_s"] * (1 - 1e-9)


def test_hand_made_trace():
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        _ev(0, 10, "%a.1 = f32[8]{0} add(f32[8]{0} %x, f32[8]{0} %y)"),
        _ev(5, 20, "%b.2 = f32[8]{0} multiply(f32[8]{0} %x, f32[8]{0} %y)"),
        _ev(30, 40, "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]) %t), condition=%c"),
        _ev(32, 35, "%sort.4 = (f32[4,8]{1,0}, s32[4,8]{1,0}) sort(f32[4,8]{1,0} %v), dimensions={1}"),
        _ev(60, 70, "%late.5 = f32[8]{0} add(f32[8]{0} %x, f32[8]{0} %y)"),
    ])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev(0, 25, "round"), _ev(25, 50, "round"), _ev(41, 49, "np.asarray(jax.Array)"),
    ])])
    red = reduce_planes([device, host], {"round"})
    assert red["window_s"] == pytest.approx(50e-9)
    assert red["busy_s"] == pytest.approx(30e-9)          # [0,20] + [30,40]
    ops = dict(red["device_ops"])
    assert ops["%while.3 (while)"] == pytest.approx(7e-9)  # less the nested sort
    assert ops["%sort.4 (sort)"] == pytest.approx(3e-9)
    assert "%late.5 (add)" not in ops                     # outside the window
    assert red["idle_gaps"] == [["round", pytest.approx(10e-9)],
                                ["round: np.asarray(jax.Array)", pytest.approx(10e-9)]]


def test_op_label():
    assert op_label("%fusion.37 = s32[37748736]{0:T(1024)} fusion(s32[37748736]{0} %g), "
                    "kind=kLoop, calls=%f") == ("%fusion.37", "fusion:kLoop")
    assert op_label("%sort.150 = (f32[16,2359296]{1,0:T(8,128)}, s32[16,2359296]{1,0}) "
                    "sort(f32[16,2359296]{1,0} %b), dimensions={1}") == ("%sort.150", "sort")


def test_no_device_plane_is_an_error():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[_ev(0, 5, "round")])])
    with pytest.raises(ValueError, match="no /device:TPU"):
        reduce_planes([host], {"round"})
