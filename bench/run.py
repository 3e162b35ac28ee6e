"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``BENCHMARK.json``'s entry of that name; its files are found by
name (see ``bench/harness/registry.py``).  With ``--trace 0`` the result
carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window and the program's spans
and counters.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: every number that
decided ``correct`` beside its limit (also the last lines of standard
error).  A machine without a TPU, or with fewer chips than the cell asks
for, gets an error and no result.  JAX's persistent compilation cache lives
in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _metric(value, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def judge(checks: dict) -> bool:
    """``correct``: every compared number within its limit."""
    return all(v <= lim for v, lim in checks.values())


def run(args, root: Path = BENCH, require_chip: bool = True,
        peak: dict | None = None) -> dict:
    """One run; returns the result object.  ``require_chip=False`` skips the
    look for a TPU, and ``peak`` stands in for the chip's peaks (the CPU
    tests drive the rest of a run that way)."""
    from bench.harness.device import peaks, require_tpu
    from bench.harness.registry import load_cell, load_driver

    cell = load_cell(args.workload, root)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = (require_tpu(cell.chips) if require_chip
               else jax.devices()[:cell.chips])
    driver = load_driver(cell)
    profile = None
    if args.trace:
        from bench.harness.profile import Profile

        profile = Profile()
    out = driver.run(cell, args.seed, args.seconds, bool(args.trace),
                     devices, T_START, profile=profile)

    metrics, breakdown = {}, None
    device = dict(out["device"])
    if not args.trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = _metric(out["end_to_end"][m["name"]], m["unit"])
    else:
        red = profile.reduce({driver.ANNOTATION})
        ctx = dict(out["context"], trace=red,
                   peak=peak or peaks(devices[0].device_kind))
        for m in cell.per_layer:
            value = cell.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = _metric(value, m["unit"])
        top = sorted(red["categories"].items(), key=lambda kv: -kv[1])[:8]
        print("[bench] device self time by opcode: " + ", ".join(
            f"{k} {v:.3f}s" for k, v in top), file=sys.stderr, flush=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
    checks = out["checks"]
    correct = judge(checks)
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        from bench.harness.registry import BenchError
    except ImportError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        result = run(args)
    except (BenchError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
