"""Readings that set a train cell's limits, on the chip at the cell's size.

    python -m bench.tools.control_train --workload <cell> --seeds 11,12,13

For each seed, in one process: the program's first checked rounds (as a
run's set-up drives them), then the float32 reference, and beside it the
bfloat16 control and the fault "half of the batch left out", each put in
the program's place and judged against the reference by a run's own
comparison.  One JSON line per seed and variant goes to standard output:
every compared number, and ``correct`` as a run would print it.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program,bf16,half_batch")
    ap.add_argument("--dump", default="",
                    help="save every variant's per-leaf readings to this .npz")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from bench.harness.device import require_tpu
    from bench.harness.registry import load_cell
    from bench.drivers import train
    from bench.run import judge

    cell = load_cell(args.workload)
    require_tpu(cell.chips)
    rounds = cell.traffic["checked_rounds"]
    variants = args.variants.split(",")
    dump = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        s = seed % (2**31 - 1)
        data = train.clients_for(cell, s)
        prog = None
        if "program" in variants:
            t0 = time.perf_counter()
            engine = train.build_engine(cell, s, data)
            p0, m0 = train.start_state(cell, s)
            train.inject(engine, p0, m0)
            it = engine.rounds()
            prog = train.checked_rounds(cell, engine, it, p0, t0)
            del engine, it, p0, m0
            gc.collect()
            print(f"[control] seed {seed}: program {time.perf_counter() - t0:.1f}s",
                  file=sys.stderr, flush=True)
        p0, m0 = train.start_state(cell, s)
        t0 = time.perf_counter()
        ref = train.reference_stats(cell, s, data, p0, m0, rounds)
        print(f"[control] seed {seed}: reference {time.perf_counter() - t0:.1f}s",
              file=sys.stderr, flush=True)
        runs = {}
        if prog is not None:
            runs["program"] = prog
        if "bf16" in variants:
            runs["bf16"] = train.reference_stats(
                cell, s, data, p0, m0, rounds, dtype=jnp.bfloat16,
                precision=lax.Precision.DEFAULT)
        if "half_batch" in variants:
            runs["half_batch"] = train.reference_stats(
                cell, s, data, p0, m0, rounds, batch_frac=0.5)
        for name, stats in dict(runs, reference=ref).items():
            for r, st in enumerate(stats):
                for p in st["delta"]:
                    dump[f"{seed}/{name}/r{r + 1}/delta/{p}"] = st["delta"][p]
                    diff = np.bitwise_xor(st["bits"][p], ref[r]["bits"][p])
                    dump[f"{seed}/{name}/r{r + 1}/maskdiff/{p}"] = (
                        np.unpackbits(diff, axis=1).sum(axis=1)
                        / ref[r]["numel"][p])
        for name, stats in runs.items():
            checks = train.compare(cell, stats, ref, stats[-1])
            print(json.dumps({"seed": seed, "variant": name,
                              "correct": judge(checks),
                              **{k: v for k, (v, _) in checks.items()}}),
                  flush=True)
        del p0, m0
        gc.collect()
    if args.dump:
        np.savez_compressed(args.dump, **dump)
    return 0


if __name__ == "__main__":
    sys.exit(main())
