"""A copy of the benchmark with the CPU-size cells added by files alone:
a configuration (``tiny_cnn.json`` and its reference), a traffic mix, a
cell record and its ``BENCHMARK.json`` entry.  Nothing in the
copied harness is edited."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).with_name("fixtures")
TINY_CELLS = {"tiny_cnn.train_tiny": ("tiny_train", "train")}
CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# what each driver kind reports end to end, added for the tiny cells where
# BENCHMARK.json has no cell of that kind yet
E2E = {"train": [("client_rounds_per_s", "client-rounds/s", "higher")]}


def tiny_tree(tmp: Path) -> Path:
    """``<tmp>/bench`` holding the benchmark plus the tiny cells."""
    root = tmp / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(FIXTURES / "tiny_cnn.json", root / "configs")
    shutil.copy(FIXTURES / "tiny_cnn.py", root / "configs")
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for cell, (traffic, driver) in TINY_CELLS.items():
        shutil.copy(FIXTURES / f"{traffic}.json", root / "traffic")
        shutil.copy(FIXTURES / f"{cell}.json", root / "workloads")
        spec["workloads"].append({"name": cell, "config": "tiny_cnn",
                                  "traffic": traffic, "chips": 1,
                                  "why": "a CPU-test cell"})
        known = {m["name"]: m for m in spec["end_to_end"]}
        for name, unit, better in E2E[driver]:
            if name not in known:
                known[name] = {"name": name, "unit": unit, "better": better,
                               "bound": 0.1, "source": "host_clock",
                               "workloads": []}
                spec["end_to_end"].append(known[name])
            known[name]["workloads"].append(cell)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run_tiny(root: Path, cell: str, seed: int = 2**31 + 17, seconds: float = 1.0,
             trace: int = 0) -> dict:
    import jax

    from bench import run as bench_run

    jax.config.update("jax_enable_compilation_cache", False)
    args = bench_run.parse_args(["--workload", cell, "--seed", str(seed),
                                 "--seconds", str(seconds),
                                 "--trace", str(trace)])
    return bench_run.run(args, root=root, require_chip=False, peak=CPU_PEAK)
