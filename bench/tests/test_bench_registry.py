"""Every cell, configuration, mix and metric of ``BENCHMARK.json`` is found
by name; an unknown workload fails; ``BENCHMARK.json`` keeps the contract's
shape."""
from __future__ import annotations

import json
import re

import pytest

from bench.harness.registry import BENCH_DIR, BenchError, load_cell, load_driver

SPEC = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_files_found_by_name(cell):
    c = load_cell(cell)
    assert c.chips in (1, 4)
    assert load_driver(c).ANNOTATION
    ref = c.reference()
    assert callable(ref.init) and callable(ref.apply) and ref.fwd_flops(c.config) > 0
    assert {"setup_s"} <= {m["name"] for m in c.end_to_end}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(c.metric_reader(m["name"]).read)


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_agrees_with_benchmark(metric):
    cell = load_cell(metric["workloads"][0])
    reader = cell.metric_reader(metric["name"])
    assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
        metric["layer"], metric["source"], metric["moves"])
    assert reader.read({}) is None          # nothing to read: no number


def test_unknown_workload_fails():
    with pytest.raises(BenchError, match="unknown workload"):
        load_cell("no_such.cell")
    with pytest.raises(BenchError, match="bad workload name"):
        load_cell("../escape")


def test_benchmark_json_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and SPEC["command"][1] == "bench/run.py"
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [w["name"] for w in SPEC["workloads"]]
    names += [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        cfg = json.loads((BENCH_DIR.parent / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
    for w in SPEC["workloads"]:
        assert len(w["why"]) <= 200
        rec = json.loads((BENCH_DIR / "workloads" / f"{w['name']}.json").read_text())
        assert rec["why"] == w["why"]
