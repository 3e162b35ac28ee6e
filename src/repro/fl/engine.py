"""Composable round engine: Strategy lifecycle hooks + streaming rounds.

Every federated strategy in the zoo shares the same skeleton — sample a
topology, mix with neighbors, run local SGD, (maybe) evolve masks, evaluate
on a cadence, account comm/FLOPs.  The seed code repeated that skeleton in
seven monolithic ``run_*`` loops; here it lives once, in ``RoundEngine``,
and a strategy is just the five-ish hooks that differ:

    class MyStrategy(StrategyBase):
        def init_state(self, task, clients, cfg) -> dict: ...
        def mix(self, state, ctx): ...                 # communication phase
        def local_update(self, state, k, ctx): ...     # client k's local phase
        def evolve(self, state, k, ctx): ...           # optional mask search
        def finalize_eval_params(self, state): ...     # what to evaluate

plus per-round accounting (``round_comm`` / ``round_flops``) so the paper's
tables come from the *actual* per-round adjacency and mask nnz rather than a
round-0 snapshot.

The engine *streams*: ``engine.rounds()`` is an iterator of ``RoundMetrics``
(mean/std personalized acc, this round's busiest-node comm, cumulative
FLOPs, lr, prune rate), which makes live dashboards, early stopping and
mid-run checkpointing natural.  ``engine.run()`` drains the iterator and
returns the familiar ``FLResult``.

Determinism: all randomness is derived from ``(cfg.seed, round, client)``
via ``np.random.SeedSequence`` — no shared generator threads through the
loop — so results are independent of client iteration order and a resumed
run is bit-identical to an uninterrupted one.

Fast path: for homogeneous-density clients, the local phase is executed as
one jitted ``fl.base.stacked_local_phase`` (a ``jax.vmap``-over-clients
``lax.scan``) instead of a Python loop over K clients (``local_exec="vmap"``
or ``"auto"``); ``fl.base.stacked_schedule`` draws the batch orders from the
same per-client generators and pads ragged step counts, which the phase
masks, and momentum travels as stacked per-client optimizer state — so the
schedule and update rule match the per-client loop exactly.
"""
from __future__ import annotations

import dataclasses
import json
import time
from functools import partial
from typing import Any, Callable, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.accounting import CommReport, FlopsReport, centralized_comm
from repro.core.evolve import cosine_prune_rate
from repro.core.topology import make_adjacency
from repro.fl.base import (
    FLConfig,
    FLResult,
    Task,
    evaluate_clients,
    rounds_to_targets,
    stacked_local_phase,
    stacked_schedule,
)
from repro.obs import CounterSet, SeriesSet, span
from repro.optim import SGDConfig
from repro.sparse import pack_tree, unpack_mask_tree, unpack_tree
from repro.utils.tree import tree_index, tree_nnz, tree_size, tree_stack

PyTree = Any

# rng sub-streams (the last SeedSequence word); disjoint per use so adding a
# draw to one phase never perturbs another
STREAM_CLIENT = 0       # per-(round, client) training randomness
STREAM_ROUND = 1        # per-round strategy randomness (client selection)
STREAM_EVAL = 2         # per-(round, client) eval-time fine-tuning


def derive_rng(seed: int, round_idx: int, k: int = 0,
               stream: int = STREAM_CLIENT) -> np.random.Generator:
    """Order-independent generator for (seed, round, client, stream)."""
    return np.random.default_rng(
        np.random.SeedSequence([seed, round_idx, k, stream]))


# ---------------------------------------------------------------------------
# Round context
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RoundCtx:
    """Everything a hook may need about the current round.

    Generators returned by ``client_rng``/``round_rng``/``eval_rng`` are
    cached for the round, so successive hook calls for the same client
    continue one deterministic stream (mix draws, then local-phase draws,
    then evolve draws).
    """
    t: int
    cfg: FLConfig
    task: Task
    clients: Sequence[Any]
    lr: float
    prune_rate: float
    adjacency: np.ndarray
    _rngs: dict = dataclasses.field(default_factory=dict, repr=False)

    def _rng(self, k: int, stream: int) -> np.random.Generator:
        key = (k, stream)
        if key not in self._rngs:
            self._rngs[key] = derive_rng(self.cfg.seed, self.t, k, stream)
        return self._rngs[key]

    def client_rng(self, k: int) -> np.random.Generator:
        return self._rng(k, STREAM_CLIENT)

    def round_rng(self) -> np.random.Generator:
        return self._rng(0, STREAM_ROUND)

    def eval_rng(self, k: int) -> np.random.Generator:
        return self._rng(k, STREAM_EVAL)


# ---------------------------------------------------------------------------
# Strategy protocol
# ---------------------------------------------------------------------------


class StrategyBase:
    """Default hook implementations; subclass and override what differs.

    ``init_state`` must return the *mutable, checkpointable* state: a pytree
    of arrays (nested dicts / lists).  Static derived quantities (ERK
    budgets, fixed masks, client sizes) belong on ``self`` — they are
    re-derived by ``init_state`` on resume, so checkpoints stay small and
    list/dict round-tripping stays trivial.
    """

    name: str = "strategy"
    #: engine may execute the local phase as vmap-over-clients when True
    vmap_capable: bool = False
    #: True iff ``mix`` communicates peer-to-peer over ``ctx.adjacency`` —
    #: the contract the network simulator (repro.sim) measures; server-based
    #: and local-only strategies leave this False
    decentralized: bool = False

    # -- lifecycle ---------------------------------------------------------
    def init_state(self, task: Task, clients, cfg: FLConfig) -> dict:
        self.task, self.clients, self.cfg = task, clients, cfg
        self.opt = SGDConfig(momentum=cfg.momentum,
                             weight_decay=cfg.weight_decay)
        self.n_samples = int(np.mean([c.n_train for c in clients]))
        return {}

    def mix(self, state: dict, ctx: RoundCtx) -> None:
        """Communication phase: gossip / server aggregation / selection."""

    def active_clients(self, state: dict, ctx: RoundCtx) -> Sequence[int]:
        """Clients that run a local phase this round (default: all)."""
        return range(len(self.clients))

    def local_update(self, state: dict, k: int, ctx: RoundCtx) -> None:
        raise NotImplementedError

    def evolve(self, state: dict, k: int, ctx: RoundCtx) -> None:
        """Optional per-client mask search after the local phase."""

    def post_round(self, state: dict, ctx: RoundCtx) -> None:
        """Optional aggregation after all clients finished (e.g. FedAvg)."""

    # -- evaluation --------------------------------------------------------
    def eval_params(self, state: dict, ctx: RoundCtx) -> list[PyTree]:
        return state["params"]

    def finalize_eval_params(self, state: dict) -> list[PyTree]:
        return state["params"]

    # -- accounting --------------------------------------------------------
    def round_comm(self, state: dict, ctx: RoundCtx) -> CommReport:
        return centralized_comm(0, [0], 1)

    def round_flops(self, state: dict, ctx: RoundCtx) -> FlopsReport:
        raise NotImplementedError

    # -- density telemetry (obs layer 2: measured vs scheduled sparsity) ---
    def measured_density(self, state: dict) -> Optional[float]:
        """Fleet-mean *measured* mask density (nnz / size over every
        client's mask), or None for strategies without masks."""
        masks = state.get("masks") if isinstance(state, dict) else None
        if not masks or masks[0] is None:
            return None
        nnz = sum(tree_nnz(m) for m in masks)
        size = sum(tree_size(m) for m in masks)
        return float(nnz) / float(size) if size else None

    def target_density(self, t: int) -> Optional[float]:
        """Fleet-mean *scheduled* density at round ``t``: the anneal
        schedule when the strategy has one (``density_at``), the static
        per-client config densities otherwise.  The gap between this and
        ``measured_density`` is the drift ``repro.obs.health`` watches."""
        cfg = getattr(self, "cfg", None)
        if cfg is None:
            return None
        if hasattr(self, "density_at"):
            return float(np.mean([self.density_at(t, k)
                                  for k in range(cfg.n_clients)]))
        return float(np.mean([cfg.client_density(k)
                              for k in range(cfg.n_clients)]))

    # -- vmap fast-path adapters ------------------------------------------
    def local_epochs(self, state: dict, ctx: RoundCtx) -> int:
        return ctx.cfg.local_epochs

    def local_params(self, state: dict, k: int) -> PyTree:
        return state["params"][k]

    def local_mask(self, state: dict, k: int) -> Optional[PyTree]:
        return None

    def set_local(self, state: dict, k: int, params: PyTree) -> None:
        state["params"][k] = params

    def set_local_mask(self, state: dict, k: int, mask: PyTree) -> None:
        if mask is not None and "masks" in state:
            state["masks"][k] = mask

    # -- per-message payload (used by repro.sim for bytes-on-wire) ---------
    def message_nnz(self, state: dict, k: int) -> int:
        """Values client k actually puts on the wire: its mask's nnz, or the
        full coordinate count for dense strategies."""
        mask = self.local_mask(state, k)
        if mask is not None:
            return tree_nnz(mask)
        return tree_size(self.local_params(state, k))

    def message_coords(self, state: dict, k: int) -> int:
        return tree_size(self.local_params(state, k))

    def snapshot_message(self, state: dict, k: int) -> dict:
        """What k transmits right now: a ``repro.sparse`` packed tree —
        bitmap + nnz values, never the dense pytree.  Dense strategies pack
        against an all-ones bitmap, so one wire format serves the whole zoo
        (``sim.links.measure_payload`` sizes it via the codec)."""
        return {"packed": pack_tree(self.local_params(state, k),
                                    self.local_mask(state, k))}

    def install_message(self, state: dict, k: int, msg: dict) -> None:
        """Write a received message into slot k (the simulator swaps these in
        temporarily so ``mix`` sees arrived — possibly stale — models)."""
        if "packed" in msg:
            self.set_local(state, k, unpack_tree(msg["packed"]))
            self.set_local_mask(state, k, unpack_mask_tree(msg["packed"]))
        else:
            self.set_local(state, k, msg["params"])
            self.set_local_mask(state, k, msg["mask"])

    def mix_one(self, state: dict, k: int, senders: dict[int, dict],
                ctx: RoundCtx) -> None:
        """Mix client k against the payloads that have *arrived* (the async
        simulator's per-activation communication hook).

        Generic fallback: swap the payloads into their slots, run the full
        ``mix`` on an adjacency whose only non-identity row is k's, keep
        only k's mixed model — correct for any strategy, but O(K) tree work
        per activation.  Decentralized strategies override it with packed
        O(degree)-fold implementations (``repro.sparse.ops``) whose cost
        tracks node degree, never K."""
        if not senders:
            # gossip self-mix is the identity (dispfl: re-masking an
            # already-masked model; dpsgd: W[k,k]=1) — skip the O(K) mix
            return
        saved_params = list(state["params"])
        saved_masks = list(state["masks"]) if "masks" in state else None
        for j, payload in senders.items():
            self.install_message(state, j, payload)
        self.mix(state, ctx)
        mixed_k = state["params"][k]
        state["params"] = saved_params
        state["params"][k] = mixed_k
        if saved_masks is not None:
            saved_masks[k] = state["masks"][k]
            state["masks"] = saved_masks


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, tuple[type, dict]] = {}


def register(name: str, **defaults):
    """Class decorator: ``@register("dpsgd_ft", finetune=True)``.

    One class may be registered under several names with different
    constructor defaults (the ``*_ft`` variants).
    """

    def deco(cls):
        _REGISTRY[name] = (cls, dict(defaults))
        return cls

    return deco


def strategy_names() -> list[str]:
    _ensure_zoo()
    return sorted(_REGISTRY)


def make_strategy(name: str, **overrides) -> StrategyBase:
    _ensure_zoo()
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown strategy '{name}'; available: {sorted(_REGISTRY)}")
    cls, defaults = _REGISTRY[name]
    strat = cls(**{**defaults, **overrides})
    strat.name = name
    return strat


def _ensure_zoo() -> None:
    """Import the built-in strategy modules so their @register calls run."""
    import repro.fl.centralized  # noqa: F401
    import repro.fl.decentralized  # noqa: F401
    import repro.fl.dispfl  # noqa: F401
    import repro.fl.partial  # noqa: F401


# ---------------------------------------------------------------------------
# Streaming metrics
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RoundMetrics:
    round: int                       # 0-based round index
    lr: float
    prune_rate: float
    comm_busiest_mb: float           # this round, from the current adjacency
    comm_rows: dict
    flops_round: float               # per client, this round
    cum_flops: float                 # per client, cumulative
    acc_mean: Optional[float]        # None on non-eval rounds
    acc_std: Optional[float]
    wall_s: float

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# Callbacks
# ---------------------------------------------------------------------------


class Callback:
    def on_round_end(self, engine: "RoundEngine", metrics: RoundMetrics) -> None:
        pass

    def on_run_end(self, engine: "RoundEngine") -> None:
        pass


class JsonlLogger(Callback):
    """Append one JSON object per round to ``path``.

    The file is truncated only when a run starts from round 0, so a resumed
    run keeps the rounds streamed before the checkpoint."""

    def __init__(self, path: str):
        self.path = path
        import os
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)

    def on_round_end(self, engine, metrics):
        mode = "w" if metrics.round == 0 else "a"
        with open(self.path, mode) as f:
            f.write(json.dumps(metrics.to_dict()) + "\n")


class Checkpointer(Callback):
    """Save the full engine state every ``every`` rounds (and at run end)."""

    def __init__(self, path: str, every: int = 1):
        self.path = path
        self.every = max(1, every)

    def on_round_end(self, engine, metrics):
        if (metrics.round + 1) % self.every == 0:
            engine.save(self.path)

    def on_run_end(self, engine):
        engine.save(self.path)


class EarlyStopAtTarget(Callback):
    """Stop the run once mean personalized accuracy reaches ``target``."""

    def __init__(self, target: float):
        self.target = target

    def on_round_end(self, engine, metrics):
        if metrics.acc_mean is not None and metrics.acc_mean >= self.target:
            engine.request_stop()


# ---------------------------------------------------------------------------
# Checkpoint packing: lists <-> marked dicts so np.savez paths round-trip
# ---------------------------------------------------------------------------

_LIST_KEY = "__list__"


def _pack(tree):
    if isinstance(tree, dict):
        return {k: _pack(v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return {_LIST_KEY: {f"{i:06d}": _pack(v) for i, v in enumerate(tree)}}
    return tree


def _unpack(tree):
    if isinstance(tree, dict):
        if set(tree.keys()) == {_LIST_KEY}:
            inner = tree[_LIST_KEY]
            return [_unpack(inner[k]) for k in sorted(inner)]
        return {k: _unpack(v) for k, v in tree.items()}
    return tree


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class RoundEngine:
    """Owns the round loop for any ``Strategy``.

    Usage::

        engine = RoundEngine(make_strategy("dispfl"), task, clients, cfg)
        for m in engine.rounds():         # streams RoundMetrics
            print(m.round, m.acc_mean)
        result = engine.result()          # FLResult (paper tables)

    or simply ``engine.run()``.  ``local_exec``:

    * ``"loop"`` — per-client Python loop (the reference semantics),
    * ``"vmap"`` — force the stacked jax.vmap local phase (errors if the
      strategy/config cannot take it),
    * ``"auto"`` — vmap when the strategy is vmap-capable, densities are
      homogeneous and all active clients agree on an effective batch size
      (momentum rides along as stacked optimizer state); loop otherwise.
    """

    def __init__(self, strategy: StrategyBase, task: Task, clients,
                 cfg: FLConfig, callbacks: Sequence[Callback] = (),
                 local_exec: str = "auto"):
        if local_exec not in ("auto", "loop", "vmap"):
            raise ValueError(f"local_exec must be auto|loop|vmap, got {local_exec}")
        self.strategy = strategy
        self.task = task
        self.clients = clients
        self.cfg = cfg
        self.callbacks = list(callbacks)
        self.local_exec = local_exec
        self.state = strategy.init_state(task, clients, cfg)
        # metric accumulators (restored by `restore`)
        self._next_round = 0
        self._stop = False
        self._acc_history: list[float] = []
        self._acc_stds: list[float] = []
        self._eval_rounds: list[int] = []
        self._comm: dict[str, list[float]] = {
            "busiest_mb": [], "avg_per_node_mb": [], "total_mb": [],
            "busiest_mb_with_bitmap": []}
        self._flops: dict[str, list[float]] = {
            "per_round_flops": [], "dense_per_round_flops": [],
            "fwd_flops_per_sample": []}
        self._local_fn: Optional[Callable] = None
        self.obs = CounterSet("fl.engine")
        self.obs.gauge("rounds_completed", fn=lambda: self._next_round)
        self.obs.gauge("cum_flops", fn=lambda: float(
            np.sum(self._flops["per_round_flops"])))
        self.obs.gauge("comm_total_mb", fn=lambda: float(
            np.sum(self._comm["total_mb"])))
        # obs layer 2: per-round wall-clock time series (not checkpointed —
        # a resumed run restarts its series; the counters above stay the
        # reconciliation source of truth)
        self.series = SeriesSet("fl.engine")
        self._series_epoch = time.perf_counter()

    # -- control -----------------------------------------------------------
    def request_stop(self) -> None:
        self._stop = True

    # -- checkpointing -----------------------------------------------------
    def _checkpoint_payload(self) -> dict:
        """The full serializable engine state (subclasses extend it — the
        network simulator adds its virtual timeline under a "sim" key)."""
        return {
            "engine": {
                "next_round": np.asarray(self._next_round, np.int64),
                "acc_history": np.asarray(self._acc_history, np.float64),
                "acc_stds": np.asarray(self._acc_stds, np.float64),
                "eval_rounds": np.asarray(self._eval_rounds, np.int64),
                "comm": {k: np.asarray(v, np.float64)
                         for k, v in self._comm.items()},
                "flops": {k: np.asarray(v, np.float64)
                          for k, v in self._flops.items()},
            },
            "state": _pack(self.state),
        }

    def save(self, path: str) -> None:
        from repro.checkpoint import save_pytree
        save_pytree(path, self._checkpoint_payload())

    def _restore_payload(self, payload: dict) -> None:
        eng = payload["engine"]
        self._next_round = int(eng["next_round"])
        self._acc_history = [float(a) for a in np.asarray(eng["acc_history"])]
        self._acc_stds = [float(a) for a in np.asarray(eng["acc_stds"])]
        self._eval_rounds = [int(r) for r in np.asarray(eng["eval_rounds"])]
        self._comm = {k: [float(x) for x in np.asarray(v)]
                      for k, v in eng["comm"].items()}
        self._flops = {k: [float(x) for x in np.asarray(v)]
                       for k, v in eng["flops"].items()}
        self.state = jax.tree.map(jnp.asarray, _unpack(payload["state"]))

    def restore(self, path: str) -> "RoundEngine":
        """Load a checkpoint written by ``save``; resumes bit-identically
        (all rng is derived from (seed, round, client), never carried).

        The archive is loaded as numpy (float64 metric histories and the
        simulator's virtual timeline must round-trip exactly; a jnp detour
        would truncate them to float32 under the x32 default) and only the
        strategy state is moved to device arrays."""
        from repro.checkpoint import load_pytree
        self._restore_payload(load_pytree(path, as_jnp=False))
        return self

    # -- the round loop ----------------------------------------------------
    def _make_ctx(self, t: int, alive: Optional[np.ndarray] = None) -> RoundCtx:
        cfg = self.cfg
        return RoundCtx(
            t=t, cfg=cfg, task=self.task, clients=self.clients,
            lr=cfg.lr_at(t),
            prune_rate=cosine_prune_rate(cfg.alpha0, t, cfg.rounds),
            adjacency=make_adjacency(cfg.topology, len(self.clients), t,
                                     cfg.degree, cfg.seed, cfg.drop_prob,
                                     alive=alive))

    # hooks for subclasses (the event simulator times each round without
    # perturbing the reference semantics below)
    def _pre_round(self, ctx: RoundCtx) -> None:
        """Called after the ctx is built, before any hook runs."""

    def _finish_metrics(self, ctx: RoundCtx, metrics: RoundMetrics) -> RoundMetrics:
        """Last chance to decorate the round's metrics before callbacks."""
        return metrics

    def _sample_series(self, metrics: RoundMetrics) -> None:
        """Sample the wall-clock engine series after one round.  Counter-kind
        series record the *cumulative* accumulator values, so their
        telescoping delta sums reconcile exactly with the ``fl.engine``
        gauges in ``snapshot_counters()``."""
        tw = time.perf_counter() - self._series_epoch
        ss = self.series
        ss.series("round_wall_s").observe(tw, metrics.wall_s)
        ss.series("comm_total_mb", kind="counter").observe(
            tw, float(np.sum(self._comm["total_mb"])))
        ss.series("cum_flops", kind="counter").observe(tw, metrics.cum_flops)
        if metrics.acc_mean is not None:
            ss.series("acc_mean").observe(tw, metrics.acc_mean)
        dm = self.strategy.measured_density(self.state)
        if dm is not None:
            ss.series("density_measured").observe(tw, dm)
            dt_ = self.strategy.target_density(metrics.round)
            if dt_ is not None:
                ss.series("density_target").observe(tw, dt_)

    def run_local_phase(self, ctx: RoundCtx, active: Sequence[int]) -> None:
        """Execute the local phase for ``active`` clients — the reusable unit
        the simulator invokes per client (``active=[k]``) or per round."""
        active = list(active)
        if self._use_vmap(ctx, active):
            self._vmap_local_phase(ctx, active)
        else:
            for k in active:
                self.strategy.local_update(self.state, k, ctx)

    def _run_one_round(self, t: int) -> RoundMetrics:
        cfg = self.cfg
        strat = self.strategy
        t0 = time.perf_counter()
        ctx = self._make_ctx(t)
        self._pre_round(ctx)
        with span("round.mix", track="engine", round=t):
            strat.mix(self.state, ctx)
        active = list(strat.active_clients(self.state, ctx))
        with span("round.local", track="engine", round=t,
                  active=len(active)):
            self.run_local_phase(ctx, active)
        with span("round.evolve", track="engine", round=t):
            for k in active:
                strat.evolve(self.state, k, ctx)
            strat.post_round(self.state, ctx)

        comm = strat.round_comm(self.state, ctx)
        flops = strat.round_flops(self.state, ctx)
        for key in self._comm:
            self._comm[key].append(float(getattr(comm, key)))
        for key in self._flops:
            self._flops[key].append(float(getattr(flops, key)))

        acc_mean = acc_std = None
        if (t + 1) % cfg.eval_every == 0 or t == cfg.rounds - 1:
            with span("round.eval", track="engine", round=t):
                accs = evaluate_clients(
                    self.task, strat.eval_params(self.state, ctx),
                    self.clients)
            acc_mean = float(np.mean(accs))
            acc_std = float(np.std(accs))
            self._acc_history.append(acc_mean)
            self._acc_stds.append(acc_std)
            self._eval_rounds.append(t)

        self._next_round = t + 1
        metrics = RoundMetrics(
            round=t, lr=ctx.lr, prune_rate=ctx.prune_rate,
            comm_busiest_mb=comm.busiest_mb, comm_rows=comm.row(),
            flops_round=flops.per_round_flops,
            cum_flops=float(np.sum(self._flops["per_round_flops"])),
            acc_mean=acc_mean, acc_std=acc_std,
            wall_s=time.perf_counter() - t0)
        metrics = self._finish_metrics(ctx, metrics)
        self._sample_series(metrics)
        return metrics

    def rounds(self) -> Iterator[RoundMetrics]:
        for t in range(self._next_round, self.cfg.rounds):
            metrics = self._run_one_round(t)
            for cb in self.callbacks:
                cb.on_round_end(self, metrics)
            yield metrics
            if self._stop:
                break
        for cb in self.callbacks:
            cb.on_run_end(self)

    # -- results -----------------------------------------------------------
    def result(self, targets: Sequence[float] = (0.5,)) -> FLResult:
        """Aggregate streamed metrics into the paper-table ``FLResult``.

        Comm / FLOP columns are the *mean over executed rounds* — the
        topology is time-varying and masks evolve, so a single-round
        snapshot (the seed behaviour) misreports both.
        """
        final = evaluate_clients(
            self.task, self.strategy.finalize_eval_params(self.state),
            self.clients)
        comm = CommReport(**{k: float(np.mean(v)) if v else 0.0
                             for k, v in self._comm.items()})
        flops = FlopsReport(**{k: float(np.mean(v)) if v else 0.0
                               for k, v in self._flops.items()})
        return FLResult(
            acc_history=list(self._acc_history),
            final_accs=final,
            comm_busiest_mb=comm.busiest_mb, comm_rows=comm.row(),
            flops_per_round=flops.per_round_flops, flops_rows=flops.row(),
            rounds_to=rounds_to_targets(self._acc_history, list(targets)))

    def run(self, targets: Sequence[float] = (0.5,)) -> FLResult:
        for _ in self.rounds():
            pass
        return self.result(targets)

    # -- vmap fast path ----------------------------------------------------
    def _use_vmap(self, ctx: RoundCtx, active: list[int]) -> bool:
        if self.local_exec == "loop" or not active:
            return False
        ok, why = self._vmap_supported(ctx, active)
        if self.local_exec == "vmap" and not ok:
            raise ValueError(f"local_exec='vmap' requested but {why}")
        return ok

    def _vmap_supported(self, ctx: RoundCtx, active: list[int]):
        cfg = self.cfg
        if not self.strategy.vmap_capable:
            return False, f"strategy '{self.strategy.name}' is not vmap-capable"
        if cfg.capacities is not None:
            return False, "heterogeneous capacities use the per-client loop"
        ns = [self.clients[k].n_train for k in active]
        bss = {min(cfg.batch_size, n) for n in ns}
        if len(bss) != 1:
            return False, "clients disagree on effective batch size"
        # ragged step counts are fine: the stacked phase pads every client to
        # the max step count and masks the padded updates (no-op steps)
        return True, ""

    def _vmap_local_phase(self, ctx: RoundCtx, active: list[int]) -> None:
        strat = self.strategy
        state = self.state
        epochs = strat.local_epochs(state, ctx)
        clients = [self.clients[k] for k in active]
        bs = min(self.cfg.batch_size, min(c.n_train for c in clients))
        # identical draws to the per-client loop: one permutation per epoch
        # from the client's (seed, round, k) generator
        idx, live = stacked_schedule(
            clients, [ctx.client_rng(k) for k in active], epochs, bs)
        bx = np.stack([c.train_x[i] for c, i in zip(clients, idx)])
        by = np.stack([c.train_y[i] for c, i in zip(clients, idx)])
        stacked = tree_stack([strat.local_params(state, k) for k in active])
        masks = [strat.local_mask(state, k) for k in active]
        masks = None if masks[0] is None else tree_stack(masks)
        if self._local_fn is None:
            # same update rule as the per-client loop (repro.optim)
            opt = SGDConfig(momentum=self.cfg.momentum,
                            weight_decay=self.cfg.weight_decay)
            self._local_fn = jax.jit(
                partial(stacked_local_phase, self.task.apply_fn, opt))
        new = self._local_fn(stacked, masks, jnp.asarray(bx),
                             jnp.asarray(by), jnp.asarray(live),
                             jnp.float32(ctx.lr))
        for i, k in enumerate(active):
            strat.set_local(state, k, tree_index(new, i))


# ---------------------------------------------------------------------------
# Convenience entry point (back-compat with the seed `run_strategy`)
# ---------------------------------------------------------------------------


def run_strategy(name: str, task: Task, clients, cfg: FLConfig,
                 targets: Sequence[float] = (0.5,),
                 callbacks: Sequence[Callback] = (),
                 local_exec: str = "auto", **strategy_kw) -> FLResult:
    """Build the named strategy, run it through the engine, return FLResult."""
    strat = make_strategy(name, **strategy_kw)
    engine = RoundEngine(strat, task, clients, cfg, callbacks=callbacks,
                         local_exec=local_exec)
    return engine.run(targets)
