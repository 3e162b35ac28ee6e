"""``ScaleEngine`` — the client-sharded SPMD round engine.

One ``RoundEngine`` subclass whose entire round — gossip mix, local SGD
phase, mask evolution — is a single jitted program over client-stacked
state.  The Python-per-client work of the reference engine (its loop *and*
its vmap fast path still mix/evolve eagerly per client) collapses into one
XLA dispatch per round, and under a device mesh the leading K dim is
sharded over the client axes (``sharding.rules.tree_stacked_shardings``) so
GSPMD emits the gossip collectives — the K=256-clients-per-round regime.

Semantics contract (the golden suite in tests/test_scale_engine.py):

* round-0 state is bit-identical to ``RoundEngine`` (the adapter inits
  through the base strategy's own ``init_state``);
* all randomness (batch orders, evolve batches, topology) derives from the
  same ``(seed, round, client)`` streams in the same draw order, so a
  ``ScaleEngine`` checkpoint resumes bit-identically — and interchangeably
  with ``RoundEngine`` (checkpoints are written in the engine's per-client
  list layout);
* with ``reduction="ordered"`` the gossip fold reproduces the reference
  accumulation order and the whole trajectory — params, masks, metrics —
  is bit-identical to ``RoundEngine(local_exec="loop")``;
* with ``reduction="einsum"`` (the default: the SPMD matmul form) values
  agree to fp reduction-order tolerance (~1e-6 relative per round) and the
  documented golden criterion is: masks identical, per-round metrics within
  tolerance.

Constraints (checked at construction, with pointers back to RoundEngine):
homogeneous client densities, all clients sharing one effective batch size
(ragged step counts are fine — padded and live-masked exactly like the
vmap fast path), and a strategy with a registered ``StackedStrategy``
adapter (``dispfl``, ``dispfl_anneal``, ``dpsgd``).

The clients' training images go to the device once, stacked; a round sends
only its batch indices and labels, and the step gathers its batches there.
"""
from __future__ import annotations

import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.base import (
    FLResult,
    Task,
    evaluate_clients_stacked,
    rounds_to_targets,
    stack_eval_arrays,
    stacked_local_phase,
    stacked_schedule,
)
from repro.fl.engine import Callback, RoundCtx, RoundEngine, RoundMetrics, StrategyBase
from repro.core.accounting import CommReport, FlopsReport
from repro.models.common import softmax_xent
from repro.obs import (
    CounterSet,
    SeriesSet,
    install_jax_hooks,
    jax_compile_count,
    span,
)
from repro.optim import SGDConfig
from repro.scale.stacked import evolve_topk_work, pack_stacked, split_stacked
from repro.scale.strategy import make_stacked

PyTree = Any


class ScaleEngine(RoundEngine):
    """Runs a Strategy-zoo member as one compiled stacked round program.

    Usage::

        engine = ScaleEngine(make_strategy("dispfl"), task, clients, cfg,
                             mesh=make_test_mesh(4, 1))   # or mesh=None
        for m in engine.rounds():
            ...
        result = engine.result()

    ``mesh=None`` runs the same single program on one device (still one
    dispatch per round); with a mesh the stacked state and batches are
    sharded over the client axes.  ``reduction`` picks the gossip fold:
    ``"einsum"`` (SPMD matmul, default) or ``"ordered"`` (bit-exact
    reference accumulation order).
    """

    def __init__(self, strategy: StrategyBase, task: Task, clients,
                 cfg, callbacks: Sequence[Callback] = (),
                 mesh=None, reduction: str = "einsum"):
        # the base class wires strategy/task/clients/cfg and builds the
        # per-client list state via the strategy's own init_state — the
        # adapter then stacks it, so round-0 state matches RoundEngine
        # bit for bit
        super().__init__(strategy, task, clients, cfg, callbacks=callbacks,
                         local_exec="loop")
        self.adapter = make_stacked(strategy, reduction=reduction)
        self.adapter.validate(cfg)
        self.mesh = mesh
        self._validate_clients()
        self.state = self.adapter.stack_state(self.state)
        self._opt = SGDConfig(momentum=cfg.momentum,
                              weight_decay=cfg.weight_decay)
        self._round_step = None
        self._eval_arrays = None
        self._img_shape = tuple(self.clients[0].train_x.shape[1:])
        self._images = None
        # compile-vs-execute observability: the jax.monitoring bridge makes
        # "traced scalars never recompile" an assertable counter — one
        # backend compile on the first step, zero after, whatever the
        # lr/prune schedule does (tests/test_obs.py pins this)
        install_jax_hooks()
        self.scale_obs = CounterSet("scale.engine")
        self._c_step_calls = self.scale_obs.counter("step_calls")
        self._c_step_compiles = self.scale_obs.counter("step_compiles")
        # bytes of a round's inputs copied host to device, and blocking
        # device-to-host reads, counted where each is made
        self._c_input_bytes = self.scale_obs.counter("input_bytes")
        self._c_host_syncs = self.scale_obs.counter("host_syncs")
        # the evolve's exact top-k work, from shapes on the host: (client,
        # leaf) selections and the count passes they make over their rows
        self._c_topk_selects = self.scale_obs.counter("topk_selects")
        self._c_topk_passes = self.scale_obs.counter("topk_passes")
        # cumulative step/compile series on the wall clock (counter-kind:
        # the deltas reconcile against the counters above); not
        # checkpointed — a resumed run restarts its series
        self.scale_series = SeriesSet("scale.engine")

    # ------------------------------------------------------------------
    # construction-time checks
    # ------------------------------------------------------------------
    def _validate_clients(self) -> None:
        cfg = self.cfg
        bss = {min(cfg.batch_size, c.n_train) for c in self.clients}
        if len(bss) != 1:
            raise ValueError(
                "ScaleEngine requires all clients to share one effective "
                f"batch size (min(batch_size, n_train)); got {sorted(bss)} "
                "— ragged *step counts* are fine (padded + masked), ragged "
                "batch shapes are not; use RoundEngine")

    # ------------------------------------------------------------------
    # the compiled round step
    # ------------------------------------------------------------------
    def _build_round_step(self):
        adapter = self.adapter
        apply_fn = self.task.apply_fn
        opt = self._opt
        evolves = adapter.evolves

        def loss(p, x, y):
            return softmax_xent(apply_fn(p, x), y)

        grad = jax.grad(loss)
        img_shape = self._img_shape

        def gather(images, idx):
            # each client's rows of its own images: (K, n, F) by (K, ...)
            x = jax.vmap(lambda d, i: d[i])(images, idx)
            return x.reshape(idx.shape + img_shape)

        # the phase scopes name every op of the program in its metadata
        # (``op_name``, the profiler's ``tf_op``), so a device trace splits
        # the round into the RoundEngine's phases; they change no op
        def round_step(state, images, mix, bi, by, live, ev_i, ev_y, lr,
                       counts):
            with jax.named_scope("mix"):
                state = adapter.stacked_mix(state, mix)
            with jax.named_scope("local"):
                params = stacked_local_phase(
                    apply_fn, opt, state["params"],
                    adapter.stacked_masks(state), gather(images, bi), by,
                    live, lr)
            state = {**state, "params": params}
            if evolves:
                with jax.named_scope("evolve"):
                    grads = jax.vmap(grad)(params, gather(images, ev_i), ev_y)
                    state = adapter.stacked_evolve(state, grads, counts)
            return state

        if self.mesh is None:
            return jax.jit(round_step)

        from jax.sharding import NamedSharding

        from repro.sharding import use_mesh_rules
        from repro.sharding.rules import stacked_spec, tree_stacked_shardings

        mesh = self.mesh
        state_sh = self._state_sh = tree_stacked_shardings(self.state, mesh)

        def shard_stacked(x):
            # batches/live carry the same leading K dim as the state; pin
            # them to the client axes so GSPMD keeps the whole round local
            # to each client shard (modulo the gossip collectives)
            if x is None:
                return None
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, stacked_spec(tuple(x.shape), mesh)))

        def sharded_step(state, images, mix, bi, by, live, ev_i, ev_y, lr,
                         counts):
            return round_step(state, shard_stacked(images), mix,
                              shard_stacked(bi), shard_stacked(by),
                              shard_stacked(live), shard_stacked(ev_i),
                              shard_stacked(ev_y), lr, counts)

        with use_mesh_rules(mesh):
            return jax.jit(
                sharded_step,
                in_shardings=(state_sh,) + (None,) * 9,
                out_shardings=state_sh,
            )

    def _step_fn(self):
        if self._round_step is None:
            self._round_step = self._build_round_step()
        return self._round_step

    @property
    def step_compiles(self) -> int:
        """Rounds whose step dispatch triggered a backend compile — the
        "traced scalars never recompile" invariant says this stays at 1,
        or at 0 when the persistent compile cache already holds the step."""
        return int(self._c_step_compiles.value)

    # ------------------------------------------------------------------
    # host-side per-round inputs (identical draws to the reference engine)
    # ------------------------------------------------------------------
    def _device_images(self):
        """Every client's training images as flat rows, (K, n_max, F) zero
        padded past each client's ``n_train``, copied to the device on the
        first call and kept: the round then ships indices, not images.
        Flat rows keep the minor dim wide (h x w x c), whatever c is."""
        if self._images is None:
            feat = int(np.prod(self._img_shape))
            n_max = max(c.n_train for c in self.clients)
            host = np.zeros((len(self.clients), n_max, feat),
                            self.clients[0].train_x.dtype)
            for k, c in enumerate(self.clients):
                host[k, :c.n_train] = c.train_x.reshape(c.n_train, feat)
            if self.mesh is None:
                self._images = jnp.asarray(host)
            else:
                from jax.sharding import NamedSharding

                from repro.sharding.rules import stacked_spec

                self._images = jax.device_put(host, NamedSharding(
                    self.mesh, stacked_spec(host.shape, self.mesh)))
            self._c_input_bytes.inc(host.nbytes)
        return self._images

    def _batch_schedule(self, ctx: RoundCtx):
        """``stacked_schedule``'s padded batch schedule — the same draws as
        the reference engine — as each step's rows of ``_device_images``,
        (K, steps, bs), its labels and its live flags."""
        clients = self.clients
        bs = min(self.cfg.batch_size, min(c.n_train for c in clients))
        idx, live = stacked_schedule(
            clients, [ctx.client_rng(k) for k in range(len(clients))],
            self.strategy.local_epochs({}, ctx), bs)
        labels = np.stack([c.train_y[i] for c, i in zip(clients, idx)])
        return idx, labels, live

    def _evolve_batches(self, ctx: RoundCtx):
        """The mask-search batches' rows of ``_device_images`` and their
        labels, drawn from the *same* per-client rng stream right after the
        local-phase orders — exactly the draw order of ``Strategy.evolve``
        in the reference engine."""
        bs = self.cfg.batch_size
        idx = np.stack([c.sample_indices(ctx.client_rng(k), bs)
                        for k, c in enumerate(self.clients)]).astype(np.int32)
        labels = np.stack([c.train_y[i] for c, i in zip(self.clients, idx)])
        return idx, labels

    # ------------------------------------------------------------------
    # the round
    # ------------------------------------------------------------------
    def _run_one_round(self, t: int) -> RoundMetrics:
        # the round's host phases are spans that also enter the profiler's
        # trace (``annotate``), so a device trace can put each idle gap down
        # to the phase the host was in; only ``scale.comm`` (its first mask
        # read waits for the step to finish) and ``scale.eval`` block
        cfg = self.cfg
        t0 = time.perf_counter()
        with span("scale.inputs", track="engine", annotate=True, round=t):
            ctx = self._make_ctx(t)
            self._pre_round(ctx)
            images = self._device_images()
            bi, by, live = self._batch_schedule(ctx)
            if self.adapter.evolves:
                ev_i, ev_y = self._evolve_batches(ctx)
            else:
                ev_i = ev_y = None
            counts = self.adapter.evolve_counts(ctx)
            # numpy all through, and one copy to the device for the lot
            inputs = jax.device_put((
                self.adapter.mix_matrix(ctx), bi, by, live, ev_i, ev_y,
                np.float32(ctx.lr), counts))
            self._c_input_bytes.inc(
                sum(x.nbytes for x in jax.tree.leaves(inputs)))
        with span("scale.dispatch", track="engine", annotate=True,
                  round=t) as sp:
            step = self._step_fn()
            if self.mesh is not None:
                # the step's outputs carry the mesh in their types: give the
                # state that placement up front (a no-op from round 2 on),
                # or round 2 sees new input types and compiles the step again
                self.state = jax.device_put(self.state, self._state_sh)
            # snapshot the compile counter around the step dispatch only —
            # _stacked_eval below jit-compiles separately and must not
            # pollute the "the round step compiled" signal
            n_compiles = jax_compile_count()
            self.state = step(self.state, images, *inputs)
            delta = jax_compile_count() - n_compiles
            sp.attrs["compiles"] = delta
        self._c_step_calls.inc()
        selects, passes = evolve_topk_work(self.state["params"], counts)
        self._c_topk_selects.inc(selects)
        self._c_topk_passes.inc(passes)
        if delta > 0:
            self._c_step_compiles.inc()
        tw = time.perf_counter() - self._series_epoch
        self.scale_series.series("step_calls", kind="counter").observe(
            tw, float(self._c_step_calls.value))
        self.scale_series.series("step_compiles", kind="counter").observe(
            tw, float(self._c_step_compiles.value))

        with span("scale.comm", track="engine", annotate=True, round=t):
            comm = self.adapter.round_comm(self.state, ctx,
                                           syncs=self._c_host_syncs)
            flops = self.adapter.round_flops(ctx)
        for key in self._comm:
            self._comm[key].append(float(getattr(comm, key)))
        for key in self._flops:
            self._flops[key].append(float(getattr(flops, key)))

        acc_mean = acc_std = None
        if (t + 1) % cfg.eval_every == 0 or t == cfg.rounds - 1:
            with span("scale.eval", track="engine", annotate=True, round=t):
                accs = self._stacked_eval()
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
        return self._finish_metrics(ctx, metrics)

    def _stacked_eval(self) -> list[float]:
        """Personalized eval without leaving the device: one vmapped
        launch over the client-stacked params (golden-equal to the
        per-client ``evaluate_clients`` loop)."""
        if self._eval_arrays is None:
            self._eval_arrays = stack_eval_arrays(self.clients)
        return evaluate_clients_stacked(
            self.task, self.adapter.stacked_eval_params(self.state),
            self.clients, arrays=self._eval_arrays)

    # ------------------------------------------------------------------
    # results / messages / checkpoints
    # ------------------------------------------------------------------
    def result(self, targets: Sequence[float] = (0.5,)) -> FLResult:
        final = self._stacked_eval()
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

    def snapshot_messages(self) -> list[dict]:
        """Per-client packed payloads of the current stacked state — what
        each client would put on the wire right now (codec-framable; dense
        strategies ride all-ones bitmaps), via the stacked packed
        container."""
        masks = self.adapter.stacked_masks(self.state)
        stacked = pack_stacked(self.state["params"], masks)
        return [{"packed": p} for p in split_stacked(stacked)]

    def _checkpoint_payload(self) -> dict:
        # write checkpoints in the engine's per-client list layout, so
        # ScaleEngine and RoundEngine archives are interchangeable
        stacked = self.state
        self.state = self.adapter.unstack_state(stacked)
        try:
            return super()._checkpoint_payload()
        finally:
            self.state = stacked

    def _restore_payload(self, payload: dict) -> None:
        super()._restore_payload(payload)
        self.state = self.adapter.stack_state(self.state)
