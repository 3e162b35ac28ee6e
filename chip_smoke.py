"""Chip smoke: drive the training and serving entry points once on a TPU and
check what comes out.

    python chip_smoke.py             # one chip: train phase, then serve phase
    python chip_smoke.py --chips 4   # four chips: the client-sharded round
                                     # against the same round on one device

* Train phase: ``repro.launch.train``'s ``simulate --scale`` path with
  dispfl over ResNet18-GN (widths 64-128-256-512) on CIFAR-shaped inputs,
  K=16, degree 10, d=0.5, three rounds from seeded random weights.  After
  every round: all stacked parameters are finite, every client's per-layer
  mask nnz equals its ERK budget, the accuracy lies in [0, 1], and the
  round step compiled at most once, never after round 1 (a persistent
  cache hit compiles nothing, so 0 is allowed).
* Serve phase: ``repro.launch.serve`` with the MLP served by the compiled
  Pallas batched masked matmul, more users than pool slots so that misses
  decode into slots; every output is checked against a float64 NumPy
  forward of the user's ``w ⊙ m`` within the bound of ``_oracle``.
* ``--chips 4``: the train configuration for two rounds, client-sharded
  over a 4x1 mesh (4 clients per chip) and again with no mesh.  The sharded
  state must sit on 4 distinct chips, and the two runs must agree within
  ``MASK_DIFF_BOUND`` and ``ACC_DIFF_BOUND``.

Progress lines come first; times in them are set-up timings that include
compiles, not benchmarks.  Only when every phase passed is the last line
``{"ok": true, "device": {...}}``.  Any failure raises and exits non-zero.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

TRAIN_ARGV = [
    "simulate", "--scale", "--strategy", "dispfl", "--model", "resnet18",
    "--hw", "32", "--clients", "16", "--degree", "10", "--density", "0.5",
    "--partition", "pathological", "--samples-per-class", "200",
    "--batch-size", "32", "--local-epochs", "1", "--rounds", "3",
    "--eval-every", "1",
]
SERVE_ARGV = [
    "--model", "mlp", "--backend", "pallas", "--users", "64",
    "--cache-size", "16", "--max-batch", "8", "--requests", "48",
    "--density", "0.5",
]
MESH_ROUNDS = 2
MESH_DEVICES = 4

# Sharded vs single-device round.  On the CPU the two runs agree bit for
# bit.  On a TPU, f32 convolutions and matmuls run at the default precision
# (bf16 operands), and the two partitionings fuse and round differently;
# five local SGD steps amplify that noise until evolve's magnitude and
# gradient top-k pick different coordinates near their thresholds (2.1e-2
# of all mask coordinates after round 1 at this configuration on four v5e
# chips, with identical accuracies).  The mask bound separates that noise
# from a sharding fault: a client whose state is misrouted or corrupted
# ends the round with a mask unrelated to its single-device twin, and two
# unrelated masks at density d differ on 2d(1-d) = 0.5 of their
# coordinates.  Every client must stay below a fifth of that.  The accuracy
# bound is 0.02, about 13 of the 640 test images over the 16 clients.
MASK_DIFF_BOUND = 0.1
ACC_DIFF_BOUND = 0.02

# bf16 unit roundoff (8-bit significand), f32 unit roundoff, and how many
# standard deviations of rounding error a served output may be off
_U_BF16 = 2.0 ** -8
_U_F32 = 2.0 ** -24
SERVE_SIGMAS = 6.0


class SmokeError(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def _compile_seconds() -> float:
    from repro.obs import install_jax_hooks

    return float(install_jax_hooks().counter("backend_compile_s").value)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


class RoundChecks:
    """``repro.fl`` callback: the per-round checks on a ScaleEngine run."""

    def __init__(self, n_devices: int = 0):
        self.n_devices = n_devices
        self.round1_masks = None
        self.accs: list[float] = []
        self._first_compiles = None

    def on_round_end(self, engine, m) -> None:
        import jax
        import jax.numpy as jnp

        from repro.scale import stacked_nnz_per_client
        from repro.utils.tree import tree_leaves_with_path

        r = m.round
        state = engine.state
        finite = all(bool(jnp.all(jnp.isfinite(x)))
                     for x in jax.tree.leaves(state["params"]))
        check(finite, f"round {r + 1}: non-finite stacked parameters")

        budgets = engine.strategy.budgets
        n_layers = 0
        for path, leaf in tree_leaves_with_path(state["masks"]):
            if path not in budgets[0]:
                continue
            nnz = stacked_nnz_per_client(leaf)
            want = [b[path] for b in budgets]
            check(nnz == want,
                  f"round {r + 1}: {path} nnz {nnz} != budgets {want}")
            n_layers += 1
        check(n_layers > 0, "no sparsified layer found in the masks")

        check(m.acc_mean is not None and 0.0 <= m.acc_mean <= 1.0,
              f"round {r + 1}: accuracy {m.acc_mean} outside [0, 1]")
        self.accs.append(float(m.acc_mean))

        calls = int(engine.scale_obs.counter("step_calls").value)
        compiles = int(engine.scale_obs.counter("step_compiles").value)
        check(calls == r + 1, f"round {r + 1}: {calls} step calls")
        check(compiles <= 1, f"round {r + 1}: step compiled {compiles} times")
        if self._first_compiles is None:
            self._first_compiles = compiles
        check(compiles == self._first_compiles,
              f"round {r + 1}: the round step recompiled")

        if self.n_devices:
            k = len(engine.clients)
            for path, leaf in tree_leaves_with_path(state):
                shards = leaf.addressable_shards
                devices = {s.device for s in shards}
                starts = {s.index[0].start for s in shards}
                check(len(devices) == self.n_devices
                      and len(starts) == self.n_devices
                      and all(s.data.shape[0] == k // self.n_devices
                              for s in shards),
                      f"{path}: shards {[(s.device, s.index[0]) for s in shards]}"
                      f" are not {self.n_devices} client slices on "
                      f"{self.n_devices} devices")
        if r == 0:
            self.round1_masks = [np.asarray(x) != 0
                                 for x in jax.tree.leaves(state["masks"])]

        log(f"[train] round {r + 1}: acc={m.acc_mean:.4f} "
            f"wall={m.wall_s:.2f}s (set-up timing, round 1 includes the "
            f"compile) step_compiles={compiles} params finite, nnz == "
            f"budget on {n_layers} layers x {len(budgets)} clients")

    def on_run_end(self, engine) -> None:
        pass


def train_phase(argv=TRAIN_ARGV, n_devices: int = 0) -> RoundChecks:
    """One ``simulate --scale`` run through the launcher, checked each
    round.  ``n_devices`` > 0 also requires the state to be sharded over
    that many devices."""
    from repro.launch import train

    args = train.parse_args(argv)
    check(args.scale and args.strategy == "dispfl",
          "the train phase checks a dispfl --scale run")
    checks = RoundChecks(n_devices)
    c0, t0 = _compile_seconds(), time.perf_counter()
    train.run_simulate(args, callbacks=[checks])
    check(len(checks.accs) == args.rounds,
          f"{len(checks.accs)} of {args.rounds} rounds checked")
    log(f"[train] {args.rounds} rounds of {args.model} K={args.clients} in "
        f"{time.perf_counter() - t0:.1f}s, backend compiles "
        f"{_compile_seconds() - c0:.1f}s (set-up timing, not a benchmark)")
    return checks


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def _oracle(weights: list[np.ndarray], x: np.ndarray):
    """float64 forward of one user's relu MLP over ``w ⊙ m``, and the
    elementwise standard deviation of the served f32 forward's rounding
    error.

    The served matmuls run in the Pallas kernel.  For an f32 ``jnp.dot``
    Mosaic contracts either in full f32 or, at its loosest, with each
    operand rounded to bf16 and the products summed in f32; the deviation
    is that of the looser one.  Round-to-nearest leaves each operand an
    unbiased relative error of at most u = 2^-8, so term i of
    ``y_j = sum_i h_i W_ij`` errs by ``h_i W_ij (d_i + e_ij)`` with variance
    at most ``2u^2/3 (h_i W_ij)^2`` (uniform d, e).  Earlier layers' errors
    carry through ``W`` and relu never enlarges them:
    ``V' = V @ W^2 + 2u^2/3 (h^2 @ W^2)``.
    """
    h = x.astype(np.float64)
    var = np.zeros_like(h)
    for i, w in enumerate(weights):
        w = w.astype(np.float64)
        w2 = w * w
        var = var @ w2 + (2 * _U_BF16 ** 2 / 3) * ((h * h) @ w2)
        h = h @ w
        if i < len(weights) - 1:
            h = np.maximum(h, 0.0)
    return h, np.sqrt(var)


def serve_phase(argv=SERVE_ARGV) -> dict:
    """One ``repro.launch.serve`` run; every output against ``_oracle``."""
    from repro.launch import serve
    from repro.serve.batcher import RequestStream

    args = serve.parse_args(argv)
    check(args.users > args.cache_size,
          "the serve phase needs more users than pool slots")
    t0 = time.perf_counter()
    engine, result = serve.run_serve(args)
    wall = time.perf_counter() - t0
    store, model = engine.store, engine.model
    check(len(result.outputs) == args.requests,
          f"{len(result.outputs)} of {args.requests} requests answered")
    hits, misses = store.hits, store.misses
    check(misses > 0, "no pool miss: the decode path did not run")

    worst_sigmas, worst_rel = 0.0, 0.0
    for req in RequestStream(n_users=args.users, n_requests=args.requests,
                             seed=args.seed, rate=args.rate):
        params, _ = store.get(req.user)
        weights = [np.asarray(params[f"layer{i}"]["w"])
                   for i in range(len(params))]
        want, sigma = _oracle(weights, model.make_input(req.input_seed))
        got = np.asarray(result.outputs[req.rid], np.float64)
        check(got.shape == want.shape and np.all(np.isfinite(got)),
              f"request {req.rid}: output shape {got.shape} or non-finite")
        diff = np.abs(got - want)
        bound = SERVE_SIGMAS * sigma + _U_F32 * np.abs(want)
        check(bool(np.all(diff <= bound)),
              f"request {req.rid} (user {req.user}): |err| up to "
              f"{diff.max():.3e} exceeds {SERVE_SIGMAS:g} sigma")
        worst_sigmas = max(worst_sigmas, float(np.max(diff / sigma)))
        worst_rel = max(worst_rel,
                        float(diff.max() / max(np.abs(want).max(), 1e-30)))
    log(f"[serve] {args.requests} requests, {hits} hits / "
        f"{misses} misses, backend {args.backend}: every output within "
        f"{SERVE_SIGMAS:g} sigma of the float64 oracle (max |err| = "
        f"{worst_sigmas:.3e} sigma of a bf16-operand dot, "
        f"max |err|/max|y| {worst_rel:.3e}); {wall:.1f}s including "
        f"compiles (set-up timing, not a benchmark)")
    return result.summary


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def mesh_phase(argv=TRAIN_ARGV) -> None:
    """The train configuration for ``MESH_ROUNDS`` rounds, sharded over a
    ``MESH_DEVICES``x1 mesh, then unsharded; the two runs must agree."""
    argv = list(argv) + ["--rounds", str(MESH_ROUNDS)]
    sharded = train_phase(argv + ["--mesh-shape", f"{MESH_DEVICES}x1"],
                          n_devices=MESH_DEVICES)
    gc.collect()
    single = train_phase(argv)
    k = len(single.round1_masks[0])
    n_diff = np.zeros(k)
    n_all = 0
    for a, b in zip(sharded.round1_masks, single.round1_masks):
        n_diff += np.sum((a != b).reshape(k, -1), axis=1)
        n_all += a[0].size
    share = n_diff / n_all
    acc_gap = max(abs(a - b) for a, b in zip(sharded.accs, single.accs))
    log(f"[mesh] mask coordinates differing after round 1: "
        f"{int(n_diff.sum())} of {n_all * k} ({n_diff.sum() / (n_all * k):.3e}"
        f"); per client {np.array2string(share, precision=4)}, max "
        f"{share.max():.4f} (bound {MASK_DIFF_BOUND:g}); per-round mean acc "
        f"sharded {sharded.accs} vs single {single.accs}, max gap "
        f"{acc_gap:.4f} (bound {ACC_DIFF_BOUND:g})")
    check(share.max() <= MASK_DIFF_BOUND, "sharded and single masks disagree")
    check(acc_gap <= ACC_DIFF_BOUND, "sharded and single accuracy disagree")


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    choices=(1, MESH_DEVICES),
                    help=f"1: train + serve phases; {MESH_DEVICES}: "
                         "sharded-vs-single round only")
    opts = ap.parse_args()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform {platform!r}")
    if len(devices) < opts.chips:
        sys.exit(f"chip_smoke: --chips {opts.chips} needs {opts.chips} "
                 f"devices, found {len(devices)}")

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    log(f"[chip_smoke] {len(devices)} x {devices[0].device_kind}; compile "
        f"cache {cache_dir}")
    t0 = time.perf_counter()
    if opts.chips == MESH_DEVICES:
        mesh_phase()
    else:
        train_phase()
        gc.collect()
        serve_phase()
    n_entries = (len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
                 else 0)
    log(f"[chip_smoke] all phases passed in {time.perf_counter() - t0:.1f}s "
        f"(set-up timing); compile cache {cache_dir} holds {n_entries} "
        f"entries")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
