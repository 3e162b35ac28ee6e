"""Thin CLI over the repro.serve serving plane.

Builds a ``ModelStore`` (synthetic per-user sparse personalizations, or a
trained engine checkpoint via ``--from-checkpoint``), replays a
seed-derived request stream through the micro-batcher, and streams p50/p99
latency, requests/s and cache counters as JSON lines.

    PYTHONPATH=src python -m repro.launch.serve \
        --users 64 --cache-size 16 --max-batch 8 --requests 256 \
        --backend ref --metrics-jsonl serve_metrics.jsonl

``--model`` picks the served family: ``mlp`` (matmul pipeline — supports
vmap/ref/pallas backends), ``smallcnn`` (FL task model, vmap only), or
any registered smoke arch name (one-step scorer, vmap only).
"""
from __future__ import annotations

import argparse


def build_model(name: str, rows: int):
    from repro.serve.model import ArchModel, MLPModel, TaskModel

    if name == "mlp":
        return MLPModel(d_in=64, widths=(128, 128), n_out=32, rows=rows)
    if name == "smallcnn":
        from repro.fl.base import make_cnn_task
        return TaskModel(make_cnn_task("smallcnn"), hw=16, rows=rows)
    from repro.configs import SMOKE_ARCHS
    if name in SMOKE_ARCHS:
        return ArchModel(SMOKE_ARCHS[name], rows=rows)
    raise SystemExit(
        f"unknown --model {name!r}: expected mlp, smallcnn, or one of "
        f"{sorted(SMOKE_ARCHS)}")


def build_store(args, model):
    import jax
    import numpy as np

    from repro.core.masks import apply_mask, init_mask
    from repro.serve.store import ModelStore

    if args.from_checkpoint:
        return ModelStore.from_checkpoint(
            args.from_checkpoint, cache_size=args.cache_size)
    base = model.init(jax.random.PRNGKey(args.seed))
    store = ModelStore(base, cache_size=args.cache_size)
    keys = jax.random.split(jax.random.PRNGKey(args.seed + 1), 2 * args.users)
    for u in range(args.users):
        p = model.init(keys[2 * u])
        m = init_mask(keys[2 * u + 1], p, args.density)
        store.put(u, apply_mask(p, m), m)
    return store


def parse_args(argv=None) -> argparse.Namespace:
    """Parse and validate the server's flags (``argv`` defaults to
    ``sys.argv[1:]``)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--users", type=int, default=64)
    ap.add_argument("--cache-size", type=int, default=16, dest="cache_size")
    ap.add_argument("--max-batch", type=int, default=8, dest="max_batch")
    ap.add_argument("--max-wait", type=float, default=0.005, dest="max_wait",
                    help="virtual seconds a request may wait before flush")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--backend", default="vmap",
                    choices=("vmap", "ref", "pallas"))
    ap.add_argument("--model", default="mlp",
                    help="mlp | smallcnn | <smoke arch name>")
    ap.add_argument("--rows", type=int, default=4,
                    help="input rows per request (matmul M)")
    ap.add_argument("--density", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rate", type=float, default=1000.0,
                    help="virtual arrivals per second")
    ap.add_argument("--from-checkpoint", default=None, dest="from_checkpoint",
                    help="load users from a trained engine archive instead "
                         "of synthesizing them")
    ap.add_argument("--metrics-every", type=int, default=8,
                    dest="metrics_every")
    ap.add_argument("--metrics-jsonl", default="-", dest="metrics_jsonl",
                    help="stream JSON lines here ('-': stdout)")
    ap.add_argument("--trace", default="",
                    help="export a Perfetto-loadable trace_event JSON of "
                         "the run (repro.obs) to this path")
    ap.add_argument("--trace-mode", default=None, dest="trace_mode",
                    choices=["ring", "full"],
                    help="span recorder: ring = bounded buffer (default), "
                         "full = keep every span")
    ap.add_argument("--run-dir", default="", dest="run_dir",
                    help="write a run archive (manifest, counters, series, "
                         "trace, health events) to this directory; implies "
                         "tracing.  Render with repro.launch.dash")
    args = ap.parse_args(argv)
    if args.trace_mode is not None and not (args.trace or args.run_dir):
        ap.error("--trace-mode requires --trace or --run-dir")
    return args


def run_serve(args):
    """Build the store and engine, replay the request stream and write
    the requested artifacts.  Returns ``(engine, result)``."""
    from repro.serve.batcher import RequestStream
    from repro.serve.engine import ServeEngine
    from repro.sim.report import MetricsStream

    if args.trace or args.run_dir:
        from repro.obs import get_tracer
        get_tracer().enable(mode=args.trace_mode or "ring")

    model = build_model(args.model, args.rows)
    store = build_store(args, model)
    n_users = len(store.users()) or args.users

    with MetricsStream(args.metrics_jsonl) as stream:
        stream.emit({"event": "store", **store.stats(),
                     "model": args.model, "backend": args.backend})
        engine = ServeEngine(store, model, backend=args.backend,
                             max_batch=args.max_batch, max_wait=args.max_wait,
                             metrics=stream, metrics_every=args.metrics_every)
        requests = RequestStream(n_users=n_users, n_requests=args.requests,
                                 seed=args.seed, rate=args.rate)
        result = engine.serve(requests)
    if args.trace:
        from repro.obs import write_trace
        doc = write_trace(args.trace)
        print(f"wrote trace ({doc['otherData']['spans']} spans) to "
              f"{args.trace} — open at https://ui.perfetto.dev")
    if args.run_dir:
        import os

        from repro.obs import (
            RunManifest,
            emit_health,
            fleet_health,
            get_tracer,
            save_run,
            snapshot_counters,
        )

        config = {k: v for k, v in vars(args).items()
                  if isinstance(v, (int, float, str, bool, type(None)))}
        manifest = RunManifest.build("serve", seed=args.seed, config=config)
        tracer = get_tracer()
        save_run(args.run_dir, manifest,
                 tracer=tracer if tracer.enabled else None,
                 report=result.summary)
        _, events = fleet_health(tracer, counters=snapshot_counters(),
                                 dropped_spans=tracer.dropped)
        with MetricsStream(os.path.join(args.run_dir, "health.jsonl"),
                           header=True) as hs:
            emit_health(hs, events)
        for ev in events:
            print(f"[health] {ev.severity}: {ev.kind} — {ev.message}")
        print(f"saved run archive {manifest.run_id} to {args.run_dir} "
              f"({len(events)} health events)")
    return engine, result


def main(argv=None) -> None:
    from repro.launch.compile_cache import enable_compile_cache

    args = parse_args(argv)
    enable_compile_cache()
    run_serve(args)


if __name__ == "__main__":
    main()
