"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only table1,...]

Prints ``name,us_per_call,derived`` CSV rows (derived = JSON payload with
the paper-comparable quantities).
"""
from __future__ import annotations

import argparse
import importlib
import sys
import traceback

from benchmarks.common import emit

MODULES = [
    "comm_flops",        # paper-exact Table 1/2 comm + FLOPs columns
    "table1_accuracy",   # Table 1 (accuracy, both partitions)
    "table2_topology",   # Table 2/8/9
    "table3_heterogeneous",  # Table 3 + Fig 4
    "table4_sparsity",   # Table 4
    "table5_convergence",  # Tables 5-7
    "fig5_masks",        # Fig 5
    "fig6_dropping",     # Fig 6
    "sim_async",         # §Sim: sync vs async wall-clock + busiest-node MB
    "sim_faults",        # §Sim v2: clean vs lossy vs shared-uplink physics
    "sparse_codec",      # §Sparse: packed payload throughput + bytes vs density
    "engine_vmap",       # §Perf: loop vs vmap local phase at K>=16
    "scale_engine",      # §Scale: one-program stacked round vs loop engine
    "serve_bench",       # §Serve: batched multi-tenant serving vs dense loop
    "roofline",          # dry-run roofline aggregation
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (slow on CPU)")
    ap.add_argument("--only", default="",
                    help="comma-separated module subset")
    ap.add_argument("--trace", default="",
                    help="export a Perfetto trace_event JSON of the whole "
                         "benchmark run (repro.obs) to this path")
    ap.add_argument("--history", default="",
                    help="append timestamped, git-sha-stamped rows per "
                         "module to this JSONL (the append-only perf "
                         "trajectory; BENCH_latest.json only holds the "
                         "newest run)")
    args = ap.parse_args()
    only = [m.strip() for m in args.only.split(",") if m.strip()]

    if args.trace:
        from repro.obs import get_tracer
        get_tracer().enable(mode="ring", capacity=1 << 18)

    rows = []
    by_module: dict[str, list] = {}
    failed = []
    for name in MODULES:
        if only and name not in only:
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            mod_rows = mod.run(fast=not args.full)
            rows.extend(mod_rows)
            by_module[name] = mod_rows
        except Exception:
            traceback.print_exc()
            failed.append(name)
            rows.append({"name": f"{name}/ERROR", "error": "see stderr"})
    emit(rows)
    if args.trace:
        from repro.obs import write_trace
        doc = write_trace(args.trace)
        print(f"# wrote trace ({doc['otherData']['spans']} spans) to "
              f"{args.trace}", file=sys.stderr)
    if args.history:
        from repro.obs import append_history, phase_summary, snapshot_counters
        n = append_history(
            args.history, by_module,
            phase_summary_doc=phase_summary() if args.trace else None,
            counters=snapshot_counters(),
            note="full" if args.full else "fast")
        print(f"# appended {n} lines to {args.history}", file=sys.stderr)
    if failed:
        print(f"# FAILED modules: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
