"""Find the benchmark's files by the names ``BENCHMARK.json`` gives.

A cell (``workloads/<cell>.json``) names its configuration and its traffic
mix; the configuration is ``configs/<config>.json`` with its plain reference
``configs/<config>.py`` beside it; the mix is ``traffic/<traffic>.json``,
whose ``driver`` key picks ``drivers/<driver>.py``; a per-layer metric is
``metrics/<metric>.py``.  Adding any of them means adding files and entries,
never editing one that is there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parents[1]
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class BenchError(RuntimeError):
    """A cell, configuration, mix or metric that cannot be found or run."""


def _check_name(kind: str, name: str) -> str:
    if not NAME_RE.match(name):
        raise BenchError(f"bad {kind} name {name!r}")
    return name


def _read_json(path: Path, kind: str, name: str) -> dict:
    if not path.is_file():
        raise BenchError(f"unknown {kind} {name!r}: no file {path}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str) -> ModuleType:
    """Import a file of the benchmark by path (metric and reference file
    names carry dots, so they are not importable by package name)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    record: dict          # workloads/<cell>.json
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<traffic>.json
    end_to_end: list      # BENCHMARK.json entries reported by this cell
    per_layer: list
    root: Path
    _reference: ModuleType | None = dataclasses.field(default=None, repr=False)

    @property
    def chips(self) -> int:
        return int(self.record["chips"])

    @property
    def driver(self) -> str:
        return self.traffic["driver"]

    def reference(self) -> ModuleType:
        """The configuration's plain reference model (loaded once)."""
        if self._reference is None:
            name = self.record["config"]
            path = self.root / "configs" / f"{name}.py"
            if not path.is_file():
                raise BenchError(f"configuration {name!r} has no reference "
                                 f"{path}")
            self._reference = load_module(
                path, f"bench_ref_{name.replace('.', '_')}")
        return self._reference

    def metric_reader(self, metric: str) -> ModuleType:
        path = self.root / "metrics" / f"{_check_name('metric', metric)}.py"
        if not path.is_file():
            raise BenchError(f"per-layer metric {metric!r} has no reader {path}")
        return load_module(path, f"bench_metric_{metric.replace('.', '_')}")


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, root: Path = BENCH_DIR) -> Cell:
    """Everything one cell needs, from the files named after it."""
    _check_name("workload", name)
    root = Path(root)
    spec = _read_json(root.parent / "BENCHMARK.json", "benchmark", "BENCHMARK.json")
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise BenchError(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(entries)}")
    record = _read_json(root / "workloads" / f"{name}.json", "workload", name)
    for key in ("config", "traffic", "chips"):
        if record[key] != entries[name][key]:
            raise BenchError(f"workloads/{name}.json says {key}="
                             f"{record[key]!r}, BENCHMARK.json "
                             f"{entries[name][key]!r}")
    config = _read_json(root / "configs" / f"{_check_name('config', record['config'])}.json",
                        "config", record["config"])
    traffic = _read_json(root / "traffic" / f"{_check_name('traffic', record['traffic'])}.json",
                         "traffic", record["traffic"])
    e2e = [m for m in spec["end_to_end"]
           if ("workloads" not in m or name in m["workloads"])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _reports(m, name, e2e_names)]
    return Cell(name=name, record=record, config=config, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, root=root)


def load_driver(cell: Cell) -> ModuleType:
    path = cell.root / "drivers" / f"{_check_name('driver', cell.driver)}.py"
    if not path.is_file():
        raise BenchError(f"traffic {cell.record['traffic']!r} names driver "
                         f"{cell.driver!r}, which has no file {path}")
    return load_module(path, f"bench_driver_{cell.driver}")
