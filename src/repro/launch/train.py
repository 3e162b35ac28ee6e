"""Training launcher.

Two modes:

1. ``simulate`` (default) — the paper's experiment: K clients, non-IID
   partitions, any strategy from the zoo, full comm/FLOP accounting and
   per-client personalized checkpoints.

       PYTHONPATH=src python -m repro.launch.train simulate \
           --strategy dispfl --clients 16 --rounds 30 --partition dirichlet

2. ``lm`` — end-to-end DisPFL on a transformer LM over synthetic Markov
   domains (one domain per client), demonstrating the technique on the
   assigned-architecture substrate (reduced configs on CPU).

       PYTHONPATH=src python -m repro.launch.train lm \
           --arch qwen3-8b --steps 100 --clients 4
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def run_simulate(args, callbacks=()) -> dict:
    """Run the ``simulate`` mode; ``callbacks`` (``repro.fl.Callback``)
    see the engine after every round, beside the ones the flags add."""
    from repro.checkpoint import save_clients
    from repro.data import build_federated_image_task
    from repro.fl import (
        Checkpointer,
        EarlyStopAtTarget,
        FLConfig,
        JsonlLogger,
        RoundEngine,
        make_cnn_task,
        make_strategy,
    )

    clients, _ = build_federated_image_task(
        args.seed, n_clients=args.clients, partition=args.partition,
        alpha=args.alpha, classes_per_client=args.classes_per_client,
        n_train_per_class=args.samples_per_class, hw=args.hw)
    task = make_cnn_task(args.model, n_classes=10, hw=args.hw,
                         width=args.width)
    capacities = None
    if args.heterogeneous:
        levels = [0.2, 0.4, 0.6, 0.8, 1.0]
        capacities = [levels[k % 5] for k in range(args.clients)]
    cfg = FLConfig(
        n_clients=args.clients, rounds=args.rounds,
        local_epochs=args.local_epochs, batch_size=args.batch_size,
        lr0=args.lr, topology=args.topology, degree=args.degree,
        density=args.density, capacities=capacities, seed=args.seed,
        drop_prob=args.drop_prob, eval_every=args.eval_every)

    callbacks = list(callbacks)
    if args.log_jsonl:
        callbacks.append(JsonlLogger(args.log_jsonl))
    if args.checkpoint:
        callbacks.append(Checkpointer(args.checkpoint,
                                      every=args.checkpoint_every))
    if args.target > 0:
        callbacks.append(EarlyStopAtTarget(args.target))
    if args.scale:
        from repro.scale import ScaleEngine

        mesh = None
        if args.mesh_shape:
            from repro.launch.mesh import make_test_mesh

            try:
                dims = [int(x) for x in args.mesh_shape.lower().split("x")]
            except ValueError:
                dims = []
            if len(dims) not in (2, 3):
                raise SystemExit(
                    f"--mesh-shape wants DATAxMODEL or PODSxDATAxMODEL, "
                    f"got {args.mesh_shape!r}")
            try:
                if len(dims) == 2:
                    mesh = make_test_mesh(data=dims[0], model=dims[1])
                else:
                    mesh = make_test_mesh(pods=dims[0], data=dims[1],
                                          model=dims[2])
            except ValueError as e:
                raise SystemExit(
                    f"cannot build mesh {args.mesh_shape}: {e}\n"
                    "(on CPU, export XLA_FLAGS=--xla_force_host_platform_"
                    "device_count=<n_devices> before launching)")
        engine = ScaleEngine(
            make_strategy(args.strategy), task, clients, cfg,
            callbacks=callbacks, mesh=mesh, reduction=args.scale_reduction)
    elif args.sim:
        from repro.sim import (
            AlwaysUp,
            BandwidthTrace,
            BernoulliAvailability,
            LinkModel,
            LossModel,
            SimEngine,
            hetero_speeds,
        )
        trace = (BandwidthTrace.from_json(args.bandwidth_trace)
                 if args.bandwidth_trace else None)
        links = (LinkModel.skewed(args.clients, args.bandwidth_mbps,
                                  args.bandwidth_skew,
                                  latency_ms=args.latency_ms, seed=args.seed,
                                  trace=trace)
                 if args.bandwidth_skew > 1.0 else
                 LinkModel.uniform(args.clients, args.bandwidth_mbps,
                                   args.latency_ms, trace=trace))
        avail = (BernoulliAvailability(args.clients, args.drop_prob, args.seed)
                 if args.drop_prob > 0 else AlwaysUp(args.clients))
        speeds = (hetero_speeds(args.clients, seed=args.seed)
                  if args.compute_hetero else None)
        loss = (LossModel(args.loss_prob, args.retransmit_timeout,
                          seed=args.seed)
                if args.loss_prob > 0 else None)
        if args.sim_checkpoint:
            callbacks.append(Checkpointer(args.sim_checkpoint,
                                          every=args.checkpoint_every))
        engine = SimEngine(
            make_strategy(args.strategy), task, clients, cfg,
            callbacks=callbacks, local_exec=args.local_exec,
            mode="async" if args.sim_async else "sync",
            staleness=args.staleness, links=links, availability=avail,
            round_s=args.round_s, compute_speeds=speeds,
            uplink=args.uplink_mode, loss=loss)
    else:
        engine = RoundEngine(make_strategy(args.strategy), task, clients, cfg,
                             callbacks=callbacks, local_exec=args.local_exec)
    if args.resume:
        engine.restore(args.resume)
        print(f"resumed from {args.resume} at round {engine._next_round}")
    if args.trace or args.run_dir:
        # --run-dir implies tracing: the archive's rollups/dashboard are
        # derived from spans, so an archive without them is near-empty
        from repro.obs import get_tracer
        get_tracer().enable(mode=args.trace_mode or "ring")

    t0 = time.time()
    for m in engine.rounds():
        if m.acc_mean is not None:
            sim_note = (f" t_sim={m.sim_time_s:.1f}s"
                        if hasattr(m, "sim_time_s") else "")
            print(f"[round {m.round + 1}/{cfg.rounds}] "
                  f"acc={m.acc_mean:.3f}±{m.acc_std:.3f} "
                  f"comm={m.comm_busiest_mb:.2f}MB lr={m.lr:.4f} "
                  f"({m.wall_s:.1f}s){sim_note}")
    res = engine.result()
    out = {
        "strategy": args.strategy, "partition": args.partition,
        "final_acc": res.final_acc, "acc_history": res.acc_history,
        "comm": res.comm_rows, "flops": res.flops_rows,
        "wall_s": round(time.time() - t0, 1),
    }
    if args.sim:
        targets = (args.target,) if args.target > 0 else ()
        out["sim"] = engine.report(targets=targets).row()
    print(json.dumps(out, indent=2))
    if args.trace:
        from repro.obs import write_trace
        doc = write_trace(args.trace)
        print(f"wrote trace ({doc['otherData']['spans']} spans) to "
              f"{args.trace} — open at https://ui.perfetto.dev")
    if args.run_dir:
        _save_run_archive(args, engine, out)
    if args.save:
        save_clients(args.save, [{"final_acc": np.asarray(a)}
                                 for a in res.final_accs])
        print(f"saved per-client results to {args.save}")
    return out


def _save_run_archive(args, engine, out: dict) -> None:
    """Write the run archive (manifest + counters + series + trace) and
    stream fleet-health events to ``<run_dir>/health.jsonl`` — the layout
    ``repro.launch.dash`` renders and ``RunRegistry`` lists."""
    import os

    from repro.obs import (
        RunManifest,
        fleet_health,
        get_tracer,
        save_run,
    )
    from repro.sim.report import MetricsStream

    kind = "scale" if args.scale else ("sim" if args.sim else "train")
    config = {k: v for k, v in vars(args).items()
              if isinstance(v, (int, float, str, bool, type(None)))}
    manifest = RunManifest.build(kind, seed=args.seed, config=config)
    tracer = get_tracer()
    save_run(args.run_dir, manifest,
             tracer=tracer if tracer.enabled else None, report=out)

    density = None
    dm = engine.series.series("density_measured")
    dt = engine.series.series("density_target")
    if dm.points() and dt.points():
        density = (dm, dt)
    from repro.obs import snapshot_counters
    _, events = fleet_health(
        tracer, counters=snapshot_counters(), density=density,
        dropped_spans=tracer.dropped)
    with MetricsStream(os.path.join(args.run_dir, "health.jsonl"),
                       header=True) as stream:
        from repro.obs import emit_health
        emit_health(stream, events)
    for ev in events:
        print(f"[health] {ev.severity}: {ev.kind} — {ev.message}")
    print(f"saved run archive {manifest.run_id} to {args.run_dir} "
          f"({len(events)} health events)")


def run_lm(args) -> dict:
    """DisPFL over a reduced assigned-arch LM on synthetic non-IID corpora."""
    import jax
    import jax.numpy as jnp

    from repro.configs import SMOKE_ARCHS, get_arch
    from repro.core.evolve import cosine_prune_rate, evolve_masks, layer_nnz_budgets
    from repro.core.gossip import masked_gossip_stacked
    from repro.core.masks import apply_mask, erk_densities_for_params, init_mask
    from repro.core.topology import make_adjacency
    from repro.data import make_lm_corpus
    from repro.models import bind
    from repro.utils.tree import tree_stack, tree_index, tree_size

    cfg = SMOKE_ARCHS[args.arch].replace(
        d_model=args.d_model, n_layers=max(SMOKE_ARCHS[args.arch].n_layers,
                                           args.layers),
        vocab=256)
    api = bind(cfg, remat=False)
    k_clients = args.clients
    seq, bs = args.seq, args.batch_size
    streams = make_lm_corpus(args.seed, vocab=256, n_domains=k_clients,
                             tokens_per_domain=args.tokens_per_client)

    keys = jax.random.split(jax.random.PRNGKey(args.seed), 2 * k_clients)
    params = [api.init(keys[i]) for i in range(k_clients)]
    masks = [init_mask(keys[k_clients + i], params[i], args.density)
             for i in range(k_clients)]
    densities = erk_densities_for_params(params[0], args.density)
    budgets = layer_nnz_budgets(params[0], densities)
    params = [apply_mask(p, m) for p, m in zip(params, masks)]
    print(f"[lm] arch={cfg.name} params/client={tree_size(params[0])/1e6:.2f}M "
          f"density={args.density}")

    rng = np.random.default_rng(args.seed)

    def batch_for(k):
        s = streams[k]
        starts = rng.integers(0, len(s) - seq - 1, size=bs)
        toks = np.stack([s[i: i + seq] for i in starts])
        labs = np.stack([s[i + 1: i + seq + 1] for i in starts])
        return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labs)}

    @jax.jit
    def step(stacked_params, stacked_masks, batch, adjacency, lr):
        mixed = masked_gossip_stacked(stacked_params, stacked_masks,
                                      adjacency, reduction="einsum")

        def total(ps):
            losses, _ = jax.vmap(lambda p, b: api.train_loss(p, b))(ps, batch)
            return jnp.sum(losses), losses

        (_, losses), grads = jax.value_and_grad(total, has_aux=True)(mixed)
        new = jax.tree.map(
            lambda w, g, m: (w - lr * g * m.astype(w.dtype)) * m.astype(w.dtype),
            mixed, grads, stacked_masks)
        return new, losses

    sp = tree_stack(params)
    sm = tree_stack(masks)
    hist = []
    steps_per_round = max(1, args.steps // args.rounds)
    t0 = time.time()
    it = 0
    for r in range(args.rounds):
        adj = jnp.asarray(make_adjacency("random", k_clients, r,
                                         degree=min(3, k_clients - 1),
                                         seed=args.seed))
        lr = args.lr * (0.998 ** r)
        for _ in range(steps_per_round):
            batch = tree_stack([batch_for(k) for k in range(k_clients)])
            sp, losses = step(sp, sm, batch, adj, lr)
            it += 1
        # mask evolution once per round
        alpha = cosine_prune_rate(0.5, r, args.rounds)
        ps = [tree_index(sp, i) for i in range(k_clients)]
        ms = [tree_index(sm, i) for i in range(k_clients)]
        for k in range(k_clients):
            g = jax.grad(lambda p: api.train_loss(p, batch_for(k))[0])(ps[k])
            ms[k], ps[k] = evolve_masks(ps[k], ms[k], g, alpha, budgets)
        sp, sm = tree_stack(ps), tree_stack(ms)
        mean_loss = float(jnp.mean(losses))
        hist.append(mean_loss)
        print(f"[lm] round {r+1}/{args.rounds} step {it} loss={mean_loss:.4f} "
              f"lr={lr:.4f} ({time.time()-t0:.0f}s)")
    out = {"arch": cfg.name, "loss_history": hist,
           "improved": hist[-1] < hist[0]}
    print(json.dumps({k: v for k, v in out.items() if k != "loss_history"}))
    return out


def parse_args(argv=None) -> argparse.Namespace:
    """Parse and validate the launcher's flags (``argv`` defaults to
    ``sys.argv[1:]``), resolving the simulator defaults."""
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)

    sim = sub.add_parser("simulate")
    sim.add_argument("--strategy", default="dispfl")
    sim.add_argument("--clients", type=int, default=16)
    sim.add_argument("--rounds", type=int, default=30)
    sim.add_argument("--local-epochs", type=int, default=5, dest="local_epochs")
    sim.add_argument("--batch-size", type=int, default=32, dest="batch_size")
    sim.add_argument("--lr", type=float, default=0.1)
    sim.add_argument("--partition", default="dirichlet",
                     choices=["dirichlet", "pathological"])
    sim.add_argument("--alpha", type=float, default=0.3)
    sim.add_argument("--classes-per-client", type=int, default=2,
                     dest="classes_per_client")
    sim.add_argument("--samples-per-class", type=int, default=100,
                     dest="samples_per_class")
    sim.add_argument("--topology", default="random",
                     choices=["random", "ring", "fc"])
    sim.add_argument("--degree", type=int, default=10)
    sim.add_argument("--density", type=float, default=0.5)
    sim.add_argument("--heterogeneous", action="store_true")
    sim.add_argument("--drop-prob", type=float, default=0.0, dest="drop_prob")
    sim.add_argument("--model", default="smallcnn",
                     choices=["smallcnn", "resnet18", "vgg11"])
    sim.add_argument("--width", type=int, default=16)
    sim.add_argument("--hw", type=int, default=16)
    sim.add_argument("--eval-every", type=int, default=1, dest="eval_every")
    sim.add_argument("--seed", type=int, default=0)
    sim.add_argument("--save", default="")
    sim.add_argument("--exec", default="auto", dest="local_exec",
                     choices=["auto", "loop", "vmap"],
                     help="local-phase execution: vmap = stacked fast path")
    sim.add_argument("--log-jsonl", default="", dest="log_jsonl",
                     help="stream per-round RoundMetrics to this JSONL file")
    sim.add_argument("--checkpoint", default="",
                     help="save engine state to this .npz after rounds")
    sim.add_argument("--checkpoint-every", type=int, default=1,
                     dest="checkpoint_every")
    sim.add_argument("--resume", default="",
                     help="restore engine state from this .npz and continue")
    sim.add_argument("--target", type=float, default=0.0,
                     help="early-stop once mean personalized acc >= target")
    sim.add_argument("--trace", default="",
                     help="export a Perfetto-loadable trace_event JSON of "
                          "the run (repro.obs) to this path")
    sim.add_argument("--trace-mode", default=None, dest="trace_mode",
                     choices=["ring", "full"],
                     help="span recorder: ring = bounded buffer (default), "
                          "full = keep every span")
    sim.add_argument("--run-dir", default="", dest="run_dir",
                     help="write a run archive (manifest, counters, series, "
                          "trace, health events) to this directory; implies "
                          "tracing.  Render with repro.launch.dash")
    # client-sharded SPMD execution (repro.scale)
    sim.add_argument("--scale", action="store_true",
                     help="run through ScaleEngine: the whole round "
                          "(mix + local phase + evolve) as one jitted "
                          "stacked program (dispfl / dispfl_anneal / dpsgd)")
    sim.add_argument("--mesh-shape", default="", dest="mesh_shape",
                     help="shard the stacked client dim over a device mesh "
                          "DATAxMODEL or PODSxDATAxMODEL (e.g. 8x1); on "
                          "CPU set XLA_FLAGS=--xla_force_host_platform_"
                          "device_count first")
    sim.add_argument("--scale-reduction", default="einsum",
                     dest="scale_reduction", choices=["einsum", "ordered"],
                     help="gossip fold: einsum = SPMD matmul (default), "
                          "ordered = bit-exact reference accumulation order")
    # event-driven network simulation (repro.sim)
    sim.add_argument("--sim", action="store_true",
                     help="run through the event-driven network simulator")
    sim.add_argument("--async", dest="sim_async", action="store_true",
                     help="asynchronous staleness-bounded gossip (default: "
                          "synchronous barrier, bit-identical to the engine)")
    sim.add_argument("--staleness", type=int, default=None,
                     help="max rounds any client may run ahead "
                          "(-1: unbounded; default 2)")
    sim.add_argument("--bandwidth-mbps", type=float, default=None,
                     dest="bandwidth_mbps", help="default 100")
    sim.add_argument("--bandwidth-skew", type=float, default=None,
                     dest="bandwidth_skew",
                     help=">1: half the clients sit behind skew-x slower links")
    sim.add_argument("--latency-ms", type=float, default=None,
                     dest="latency_ms", help="default 10")
    sim.add_argument("--compute-hetero", action="store_true",
                     dest="compute_hetero",
                     help="0.2x..1.0x per-client compute speed multipliers")
    sim.add_argument("--round-s", type=float, default=None, dest="round_s",
                     help="virtual seconds a full-speed client spends per "
                          "round (default 1.0)")
    # fault realism (sim v2)
    sim.add_argument("--loss-prob", type=float, default=None,
                     dest="loss_prob",
                     help="per-link Bernoulli message drop probability "
                          "(retransmitted after --retransmit-timeout; every "
                          "attempt's bytes are counted on the wire)")
    sim.add_argument("--retransmit-timeout", type=float, default=None,
                     dest="retransmit_timeout",
                     help="virtual seconds the sender waits before resending "
                          "a dropped message (default 0.5)")
    sim.add_argument("--uplink-mode", default=None, dest="uplink_mode",
                     choices=["parallel", "fifo", "fair"],
                     help="shared-uplink discipline: parallel = idealized "
                          "per-edge links (default), fifo/fair serialize a "
                          "sender's concurrent transfers on one uplink")
    sim.add_argument("--bandwidth-trace", default=None,
                     dest="bandwidth_trace",
                     help='JSON file {"times": [...], "scale": [...]} of '
                          "time-varying bandwidth multipliers (scale rows "
                          "scalar or per-client)")
    sim.add_argument("--sim-checkpoint", default="", dest="sim_checkpoint",
                     help="save the full simulator state (virtual clock, "
                          "event queue, link stats) to this .npz every "
                          "--checkpoint-every rounds; resume with --resume "
                          "(--checkpoint writes the same archive under "
                          "--sim; this alias just keeps sim runs explicit)")

    lm = sub.add_parser("lm")
    lm.add_argument("--arch", default="qwen3-8b")
    lm.add_argument("--clients", type=int, default=4)
    lm.add_argument("--steps", type=int, default=100)
    lm.add_argument("--rounds", type=int, default=10)
    lm.add_argument("--seq", type=int, default=128)
    lm.add_argument("--batch-size", type=int, default=8, dest="batch_size")
    lm.add_argument("--lr", type=float, default=0.05)
    lm.add_argument("--density", type=float, default=0.5)
    lm.add_argument("--d-model", type=int, default=256, dest="d_model")
    lm.add_argument("--layers", type=int, default=2)
    lm.add_argument("--tokens-per-client", type=int, default=32768,
                    dest="tokens_per_client")
    lm.add_argument("--seed", type=int, default=0)

    args = ap.parse_args(argv)
    if args.mode == "simulate":
        if args.scale and args.sim:
            ap.error("--scale and --sim are mutually exclusive engines")
        if args.trace_mode is not None and not (args.trace or args.run_dir):
            ap.error("--trace-mode requires --trace or --run-dir")
        if not args.scale:
            scale_only = {"--mesh-shape": bool(args.mesh_shape),
                          "--scale-reduction":
                              args.scale_reduction != "einsum"}
            used = [f for f, on in scale_only.items() if on]
            if used:
                ap.error(f"{', '.join(used)} require(s) --scale")
        if not args.sim:
            sim_only = {"--async": args.sim_async,
                        "--staleness": args.staleness is not None,
                        "--bandwidth-mbps": args.bandwidth_mbps is not None,
                        "--bandwidth-skew": args.bandwidth_skew is not None,
                        "--latency-ms": args.latency_ms is not None,
                        "--compute-hetero": args.compute_hetero,
                        "--round-s": args.round_s is not None,
                        "--loss-prob": args.loss_prob is not None,
                        "--retransmit-timeout":
                            args.retransmit_timeout is not None,
                        "--uplink-mode": args.uplink_mode is not None,
                        "--bandwidth-trace": args.bandwidth_trace is not None,
                        "--sim-checkpoint": bool(args.sim_checkpoint)}
            used = [f for f, on in sim_only.items() if on]
            if used:
                ap.error(f"{', '.join(used)} require(s) --sim")
        # resolve sim defaults after the guard above (`is None`, never `or`:
        # an explicit 0 must reach the models' own validation, not be
        # silently replaced by the default)
        args.staleness = 2 if args.staleness is None else args.staleness
        args.bandwidth_mbps = (100.0 if args.bandwidth_mbps is None
                               else args.bandwidth_mbps)
        args.bandwidth_skew = (1.0 if args.bandwidth_skew is None
                               else args.bandwidth_skew)
        args.latency_ms = 10.0 if args.latency_ms is None else args.latency_ms
        args.round_s = 1.0 if args.round_s is None else args.round_s
        args.loss_prob = 0.0 if args.loss_prob is None else args.loss_prob
        args.retransmit_timeout = (0.5 if args.retransmit_timeout is None
                                   else args.retransmit_timeout)
        args.uplink_mode = ("parallel" if args.uplink_mode is None
                            else args.uplink_mode)
        if args.sim and args.bandwidth_skew < 1.0:
            ap.error("--bandwidth-skew must be >= 1 (1 = uniform links)")
    return args


def main(argv=None) -> None:
    from repro.launch.compile_cache import enable_compile_cache

    args = parse_args(argv)
    enable_compile_cache()
    if args.mode == "simulate":
        run_simulate(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()
