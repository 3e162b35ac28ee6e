"""StackedStrategy — the adapter protocol that runs a registered Strategy
as one client-stacked SPMD program.

An adapter wraps an *existing* ``StrategyBase`` instance (the hook class the
loop engine, the vmap fast path and the network simulator all drive) and
re-expresses its round phases over stacked (K-leading) state:

    stacked_init(task, clients, cfg)   -> stacked state (via the base's own
                                          init_state, then tree_stack — so
                                          round-0 state is bit-identical)
    mix_matrix(ctx)                    -> (K, K) host matrix for the fold
                                          (adjacency gate / Metropolis W)
    stacked_mix(state, mix)            -> traced communication phase
    stacked_evolve(state, grads, counts) -> traced mask search (optional)
    evolve_counts(ctx)                 -> host per-round traced count inputs
                                          (so schedules never recompile)

plus ``round_comm``/``round_flops`` (delegating to the base strategy's
accounting) and ``eval_params``/``unstack_state`` for evaluation and
checkpoint interop.  ``ScaleEngine`` composes these into a single jitted
round step: mix -> local phase -> evolve.

Adapters are looked up by the *registered* strategy name
(``@register_stacked("dispfl")``); ``make_stacked(strategy)`` raises with
the supported list for strategies that have no stacked form yet.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np

from repro.core.accounting import decentralized_comm
from repro.core.gossip import masked_gossip_stacked, plain_mix_stacked
from repro.fl.engine import RoundCtx, StrategyBase
from repro.obs import Counter
from repro.scale.stacked import (
    evolve_counts_for,
    stacked_evolve_exact,
    stacked_nnz_per_client,
)
from repro.utils.tree import tree_stack, tree_unstack

PyTree = Any

_STACKED_REGISTRY: dict[str, type] = {}


def register_stacked(*names: str):
    """Class decorator: map registered strategy names to their adapter."""

    def deco(cls):
        for name in names:
            _STACKED_REGISTRY[name] = cls
        return cls

    return deco


def stacked_strategy_names() -> list[str]:
    return sorted(_STACKED_REGISTRY)


def make_stacked(strategy: StrategyBase,
                 reduction: str = "einsum") -> "StackedStrategyBase":
    """Adapter for an already-constructed strategy instance."""
    cls = _STACKED_REGISTRY.get(strategy.name)
    if cls is None:
        raise KeyError(
            f"strategy '{strategy.name}' has no stacked adapter; "
            f"supported: {stacked_strategy_names()}")
    return cls(strategy, reduction=reduction)


class StackedStrategyBase:
    """Default adapter plumbing; subclasses fill in the traced phases."""

    #: state keys that carry per-client lists in the base strategy's state
    state_keys: tuple[str, ...] = ("params",)
    #: whether the strategy runs a post-local mask search
    evolves: bool = False

    def __init__(self, base: StrategyBase, reduction: str = "einsum"):
        self.base = base
        self.reduction = reduction

    @property
    def name(self) -> str:
        return self.base.name

    # -- lifecycle ---------------------------------------------------------
    def validate(self, cfg) -> None:
        """Reject configurations the stacked program cannot express."""
        if cfg.capacities is not None:
            raise ValueError(
                "ScaleEngine requires homogeneous client densities "
                "(cfg.capacities=None); heterogeneous capacities imply "
                "per-client layer budgets, which the stacked evolve cannot "
                "batch — use RoundEngine")

    def stacked_init(self, task, clients, cfg) -> dict:
        """Init through the base strategy (bit-identical round-0 state),
        then stack the per-client lists."""
        state = self.base.init_state(task, clients, cfg)
        return self.stack_state(state)

    def stack_state(self, state: dict) -> dict:
        """Per-client lists (``state_keys``) -> stacked trees; any other
        state entries pass through untouched."""
        return {k: tree_stack(v) if k in self.state_keys else v
                for k, v in state.items()}

    def unstack_state(self, state: dict) -> dict:
        kdim = len(self.base.clients)
        return {k: tree_unstack(v, kdim) if k in self.state_keys else v
                for k, v in state.items()}

    # -- traced phases -----------------------------------------------------
    def mix_matrix(self, ctx: RoundCtx) -> np.ndarray:
        raise NotImplementedError

    def stacked_mix(self, state: dict, mix: jax.Array) -> dict:
        raise NotImplementedError

    def stacked_masks(self, state: dict) -> Optional[PyTree]:
        """Stacked masks for the local phase (None = unmasked SGD)."""
        return None

    def stacked_evolve(self, state: dict, grads: PyTree,
                       counts: dict) -> dict:
        return state

    def evolve_counts(self, ctx: RoundCtx) -> dict:
        return {}

    # -- evaluation / accounting ------------------------------------------
    def eval_params(self, state: dict) -> list[PyTree]:
        return tree_unstack(state["params"], len(self.base.clients))

    def stacked_eval_params(self, state: dict) -> PyTree:
        """Client-stacked personalized params for the vmapped eval path —
        same models as ``eval_params``, without the host-side unstack."""
        return state["params"]

    def round_comm(self, state: dict, ctx: RoundCtx,
                   syncs: Optional[Counter] = None):
        """The round's ``CommReport``; ``syncs`` counts the blocking device
        reads it makes."""
        raise NotImplementedError

    def round_flops(self, ctx: RoundCtx):
        # the zoo's round_flops are pure functions of (cfg, task, round)
        return self.base.round_flops({}, ctx)


@register_stacked("dispfl", "dispfl_anneal")
class StackedDisPFL(StackedStrategyBase):
    """DisPFL (and its sparse-to-sparser anneal variant) in stacked form:
    intersection gossip as the adjacency-weighted masked fold, masked local
    SGD, exact batched prune/regrow with per-round traced counts (the
    anneal schedule changes only the counts, never the program)."""

    state_keys = ("params", "masks")
    evolves = True

    def validate(self, cfg) -> None:
        super().validate(cfg)
        if getattr(self.base, "payload_dtype", "fp32") != "fp32":
            raise ValueError(
                "ScaleEngine's stacked mix computes on dense fp32 state and "
                "never crosses a message boundary, so payload_dtype='fp16' "
                "would silently have no effect — use RoundEngine/SimEngine "
                "for half-precision wire payloads")

    def mix_matrix(self, ctx: RoundCtx) -> np.ndarray:
        return np.asarray(ctx.adjacency, dtype=np.float32)

    def stacked_mix(self, state: dict, mix: jax.Array) -> dict:
        params = masked_gossip_stacked(state["params"], state["masks"], mix,
                                       reduction=self.reduction)
        return {**state, "params": params}

    def stacked_masks(self, state: dict) -> PyTree:
        return state["masks"]

    def stacked_evolve(self, state: dict, grads: PyTree,
                       counts: dict) -> dict:
        masks, params = stacked_evolve_exact(state["params"], state["masks"],
                                             grads, counts)
        return {"params": params, "masks": masks}

    def evolve_counts(self, ctx: RoundCtx) -> dict:
        base = self.base
        if hasattr(base, "_budgets_at"):          # dispfl_anneal
            budgets = base._budgets_at(ctx.t, 0)
        else:
            budgets = base.budgets[0]
        return evolve_counts_for(budgets, ctx.prune_rate)

    def round_comm(self, state: dict, ctx: RoundCtx,
                   syncs: Optional[Counter] = None):
        nnz = stacked_nnz_per_client(state["masks"], syncs=syncs)
        return decentralized_comm(ctx.adjacency, nnz, self.base.n_coords)


@register_stacked("dpsgd", "dpsgd_ft")
class StackedDPSGD(StackedStrategyBase):
    """D-PSGD in stacked form: Metropolis mixing as the row-stochastic fold
    over K, unmasked local SGD, no mask search.  (``dpsgd_ft`` maps here so
    it fails with a precise unsupported-variant error rather than a generic
    registry miss.)"""

    def validate(self, cfg) -> None:
        super().validate(cfg)
        if getattr(self.base, "param_fraction", 1.0) < 1.0:
            raise ValueError(
                "stacked dpsgd supports param_fraction=1.0 only (the shared "
                "static-mask baseline stays on RoundEngine)")
        if getattr(self.base, "finetune", False):
            raise ValueError(
                "stacked dpsgd does not implement the -FT eval variant; "
                "use RoundEngine for dpsgd_ft")

    def mix_matrix(self, ctx: RoundCtx) -> np.ndarray:
        from repro.fl.decentralized import metropolis_weights

        return metropolis_weights(ctx.adjacency).astype(np.float32)

    def stacked_mix(self, state: dict, mix: jax.Array) -> dict:
        return {**state,
                "params": plain_mix_stacked(state["params"], mix,
                                            reduction=self.reduction)}

    def round_comm(self, state: dict, ctx: RoundCtx,
                   syncs: Optional[Counter] = None):
        n = len(self.base.clients)
        return decentralized_comm(ctx.adjacency,
                                  [self.base.n_coords] * n,
                                  self.base.n_coords)
