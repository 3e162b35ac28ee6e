"""repro.scale — the client-sharded SPMD round engine.

DisPFL's pitch is that decentralized sparse training stays cheap as the
client count grows; this package is the execution layer that makes the
*simulation* scale the same way.  Where ``repro.fl.engine.RoundEngine``
walks clients in Python (vmap covers only the local phase), ``ScaleEngine``
compiles the entire round — gossip mix, local SGD, mask evolution — into
ONE jitted program over client-stacked state, and shards the leading K dim
over a device mesh's client axes (hundreds–thousands of clients per round;
GSPMD emits the gossip collectives).

The StackedStrategy contract
----------------------------
A strategy joins the scale path by registering an adapter
(``scale.strategy.register_stacked``) that wraps its ordinary
``StrategyBase`` hooks:

    class MyStacked(StackedStrategyBase):
        state_keys = ("params", ...)        # per-client lists to stack
        evolves = True/False                # post-local mask search?

        def mix_matrix(self, ctx): ...      # host: (K, K) fold matrix
        def stacked_mix(self, state, mix):  # traced: communication phase
        def stacked_masks(self, state):     # masks for the local phase
        def stacked_evolve(self, state, grads, counts):  # traced search
        def evolve_counts(self, ctx): ...   # host: per-round traced counts
        def round_comm(self, state, ctx):   # accounting on stacked state

``stacked_init`` (inherited) builds round-0 state through the base
strategy's own ``init_state`` and stacks it, so the stacked program starts
from bit-identical state; ``evolve_counts`` routes *schedule* changes
(cosine prune rate, dispfl_anneal's shrinking ERK budgets) through traced
scalars, so the program compiles once for a whole run.  Built-in adapters:
``dispfl``, ``dispfl_anneal``, ``dpsgd``.

Fidelity
--------
``reduction="ordered"`` reproduces the reference engine's accumulation
order — the trajectory (params, masks, metrics) is bit-identical to
``RoundEngine(local_exec="loop")``, pinned by tests/test_scale_engine.py.
``reduction="einsum"`` (default) is the SPMD matmul fold: values agree to
fp-reduction-order tolerance, masks and rng draws stay identical-by-
construction round for round only as long as value drift never crosses a
top-k tie (asserted at the golden suite's scale).  Checkpoints are written
in the engine's per-client list layout, so ScaleEngine and RoundEngine
archives are interchangeable.

Entry points: ``ScaleEngine``; ``launch/train.py --scale [--mesh-shape]``;
``benchmarks/scale_engine.py`` (rounds/s + bytes vs K, gated);
``examples/scale_mesh.py`` (K=256 on forced host devices).
"""
from repro.scale.engine import ScaleEngine  # noqa: F401
from repro.scale.stacked import (  # noqa: F401
    StackedPacked,
    fold_stacked,
    pack_stacked,
    split_stacked,
    stack_payloads,
    stacked_evolve_exact,
    stacked_nnz_per_client,
    stacked_prune_regrow_threshold,
    unpack_stacked,
)
from repro.scale.strategy import (  # noqa: F401
    StackedStrategyBase,
    make_stacked,
    register_stacked,
    stacked_strategy_names,
)
