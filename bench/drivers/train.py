"""Train driver: DisPFL rounds of ``repro.scale.ScaleEngine`` on one chip.

The engine is built as ``repro.launch.train.run_simulate`` builds it under
``--scale`` (``make_strategy``, ``make_cnn_task``, ``FLConfig``, no mesh),
over the benchmark's own seeded clients, and starts from the benchmark's
own seeded weights and masks.  Set-up drives the engine's ``rounds()``
iterator through the first ``checked_rounds`` rounds (the first compiles),
recording what each produced; the window then runs whole rounds through
the same iterator until ``--seconds`` have passed.  Evaluation never runs:
``eval_every`` lies beyond the horizon's last round.

``correct`` compares those first rounds with the plain reference
(``bench/reference/dispfl.py``) run from the same seed once the window has
closed and the program's state is freed, and checks every client's layer
budget after the window.
"""
from __future__ import annotations

import gc
import math
import sys
import time

ANNOTATION = "round"


def log(msg: str) -> None:
    print(f"[train] {msg}", file=sys.stderr, flush=True)


def fl_config(cfg: dict, seed: int):
    from repro.fl import FLConfig

    return FLConfig(
        n_clients=cfg["n_clients"], rounds=cfg["rounds"],
        local_epochs=cfg["local_epochs"], batch_size=cfg["batch_size"],
        lr0=cfg["lr"], lr_decay=cfg["lr_decay"],
        weight_decay=cfg["weight_decay"], momentum=cfg["momentum"],
        topology=cfg["topology"], degree=cfg["degree"], seed=seed,
        density=cfg["density"], alpha0=cfg["alpha0"],
        eval_every=10 * cfg["rounds"])


def clients_for(cell, seed: int) -> list[dict]:
    from bench.harness.traffic import image_clients

    cfg, tr = cell.config, cell.traffic
    return image_clients(
        seed, n_clients=cfg["n_clients"], n_classes=cfg["num_classes"],
        samples_per_class=tr["samples_per_class"],
        classes_per_client=tr["classes_per_client"], hw=cfg["image_hw"],
        channels=cfg["in_channels"], noise=tr["noise"],
        test_per_client=tr["test_per_client"])


def round_flops(cell, data: list[dict], model) -> float:
    """Dense model FLOPs of one round: forward and backward (3x forward)
    over every live local sample, and over each client's evolve batch."""
    cfg = cell.config
    bs = cfg["batch_size"]
    live = sum(cfg["local_epochs"] * math.ceil(len(d["train_y"]) / bs) * bs
               for d in data)
    return 3.0 * model.fwd_flops(cfg) * (live + len(data) * bs)


def build_engine(cell, seed: int, data: list[dict]):
    from repro.data import ClientData
    from repro.fl import make_cnn_task, make_strategy
    from repro.scale import ScaleEngine

    cfg = cell.config
    clients = [ClientData(**d) for d in data]
    task = make_cnn_task(cfg["model"], n_classes=cfg["num_classes"],
                         hw=cfg["image_hw"], width=cfg.get("width", 16))
    return ScaleEngine(make_strategy(cfg["strategy"]), task, clients,
                       fl_config(cfg, seed), mesh=None)


def start_state(cell, seed: int):
    from bench.reference.dispfl import make_state

    return make_state(cell.reference(), cell.config, seed,
                      cell.config["n_clients"])


def inject(engine, params, masks) -> None:
    """Hand the engine the benchmark's starting weights and masks."""
    import jax

    want = jax.tree.structure(engine.state["params"])
    for name, tree in (("params", params), ("masks", masks)):
        if jax.tree.structure(tree) != want or any(
                a.shape != b.shape or a.dtype != b.dtype
                for a, b in zip(jax.tree.leaves(tree),
                                jax.tree.leaves(engine.state[name]))):
            raise ValueError(f"the configuration's {name} tree does not match "
                             "the program's model")
    engine.state = {"params": params, "masks": masks}


def reference_stats(cell, seed: int, data: list[dict], params0, masks0,
                    rounds: int, **variant) -> list[dict]:
    """The plain reference's first ``rounds`` rounds, as the same leaf
    statistics the program's rounds are read with."""
    from bench.reference.dispfl import Reference, host_stats

    ref = Reference(cell.reference(), cell.config,
                    [(d["train_x"], d["train_y"]) for d in data], seed,
                    **variant)
    ps, ms = ref.start(params0, masks0)
    out = []
    for t in range(rounds):
        ps, ms = ref.round(ps, ms, t)
        out.append(host_stats(ps, ms, params0))
    return out


def compare(cell, prog: list[dict], ref: list[dict], final: dict) -> dict:
    """The numbers that decide ``correct``, each with its limit (those the
    cell gives no limit are not compared): every
    client's layer budgets after each checked round and after the window
    (exact); the worst leaf's change after the first and the last checked
    round; the worst unmasked leaf's change after the first, against its
    own norm; the median client's whole-model change after the first."""
    from bench.reference.dispfl import (
        budgets, leaf_change_gap, median_client_gap, nnz_off_budget,
        unmasked_leaf_gap)

    limits = cell.record["limits"]
    bud = budgets(_template(cell), cell.config["density"])
    n = len(ref)
    values = {
        "nnz_off_budget": sum(nnz_off_budget(s, bud) for s in prog + [final]),
        "leaf_change_gap_r1": leaf_change_gap(prog[0], ref[0]),
        f"leaf_change_gap_r{n}": leaf_change_gap(prog[n - 1], ref[n - 1]),
        "unmasked_leaf_gap_r1": unmasked_leaf_gap(prog[0], ref[0]),
        "median_client_gap_r1": median_client_gap(prog[0], ref[0]),
    }
    return {k: (v, limits[k]) for k, v in values.items() if k in limits}


def _template(cell):
    import jax

    model = cell.reference()
    return jax.eval_shape(lambda k: model.init(k, cell.config),
                          jax.random.key(0))


def checked_rounds(cell, engine, rounds, params0, t_start: float) -> list[dict]:
    """Drive the engine's own round iterator through the cell's checked
    rounds, reading each round's leaf statistics."""
    from bench.reference.dispfl import host_stats

    prog = []
    for r in range(cell.traffic["checked_rounds"]):
        m = next(rounds)
        prog.append(host_stats(engine.state["params"], engine.state["masks"],
                               params0))
        log(f"round {r + 1} wall {m.wall_s:.3f}s, at "
            f"{time.perf_counter() - t_start:.2f}s")
    return prog


def run(cell, seed: int, seconds: float, trace: bool, devices, t_start: float,
        profile=None) -> dict:
    """One run of a train cell; see the module docstring."""
    import jax

    from bench.harness.device import device_info
    from bench.reference.dispfl import host_stats
    from repro.obs import install_jax_hooks

    prog_seed = seed % (2**31 - 1)
    hooks = install_jax_hooks()
    compile_s = hooks.counter("backend_compile_s")
    data = clients_for(cell, prog_seed)
    log(f"data {time.perf_counter() - t_start:.2f}s")
    engine = build_engine(cell, prog_seed, data)
    params0, masks0 = start_state(cell, prog_seed)
    inject(engine, params0, masks0)
    log(f"engine and start state {time.perf_counter() - t_start:.2f}s")
    rounds = engine.rounds()
    prog = checked_rounds(cell, engine, rounds, params0, t_start)
    del params0, masks0
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.2f}s, backend compiles {compile_s.value:.2f}s, "
        f"step compiles {engine.step_compiles}")

    compiles0 = hooks.counter("backend_compiles").value
    if profile is not None:
        profile.start()
    t0 = time.perf_counter()
    n = 0
    while n == 0 or time.perf_counter() - t0 < seconds:
        with jax.profiler.StepTraceAnnotation(ANNOTATION, step_num=n):
            next(rounds)
            jax.block_until_ready(engine.state)
        n += 1
    window_s = time.perf_counter() - t0
    if profile is not None:
        profile.stop()
    compiles = hooks.counter("backend_compiles").value - compiles0

    device = device_info(devices)
    final = host_stats(engine.state["params"], engine.state["masks"],
                       engine.state["params"])
    del engine, rounds
    gc.collect()

    log(f"window {n} rounds in {window_s:.3f}s")
    model = cell.reference()
    t_ref = time.perf_counter()
    params0, masks0 = start_state(cell, prog_seed)
    ref = reference_stats(cell, prog_seed, data, params0, masks0,
                          cell.traffic["checked_rounds"])
    log(f"reference {time.perf_counter() - t_ref:.2f}s")
    checks = compare(cell, prog, ref, final)
    if compiles:
        log(f"warning: {compiles} backend compiles inside the window")
    k = cell.config["n_clients"]
    return {
        "attempted": n, "failed": 0,
        "end_to_end": {"client_rounds_per_s": k * n / window_s,
                       "setup_s": setup_s},
        "context": {"window_s": window_s, "chips": len(devices),
                    "model_flops": n * round_flops(cell, data, model),
                    "rounds": n, "compiles_in_window": compiles},
        "checks": checks, "device": device,
    }
