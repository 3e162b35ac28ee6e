"""repro.scale: K=8 golden equivalence vs RoundEngine, stacked primitive
parity, stacked packed payload round-trips, sharding spec resolution,
checkpoint interop, and the sharded subprocess smoke."""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.evolve import (
    _exact_topk_mask,
    evolve_mask_layer,
    evolve_masks,
    layer_nnz_budgets,
)
from repro.core.gossip import plain_mix_stacked
from repro.core.masks import erk_densities_for_params
from repro.core.topology import make_adjacency
from repro.data import build_federated_image_task
from repro.fl import (
    Checkpointer,
    FLConfig,
    RoundEngine,
    make_cnn_task,
    make_strategy,
)
from repro.fl.decentralized import metropolis_weights
from repro.scale import (
    ScaleEngine,
    fold_stacked,
    make_stacked,
    pack_stacked,
    split_stacked,
    stack_payloads,
    stacked_evolve_exact,
    stacked_nnz_per_client,
    unpack_stacked,
)
from repro.scale.stacked import (_RADIX_BITS, _count_pass, _topk_rows,
                                 evolve_counts_for, topk_row_passes)
from repro.sparse import encoded_nbytes, pack_tree
from repro.utils.tree import (
    tree_index,
    tree_leaves_with_path,
    tree_stack,
    tree_unstack,
)

pytestmark = pytest.mark.tier1

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def setup():
    clients, _ = build_federated_image_task(
        0, n_clients=8, partition="pathological", classes_per_client=2,
        n_train_per_class=24, n_test_per_client=16, hw=8, noise=0.7)
    task = make_cnn_task("smallcnn", 10, 8, width=4)
    cfg = FLConfig(n_clients=8, rounds=3, local_epochs=2, batch_size=16,
                   degree=2, eval_every=1)
    return task, clients, cfg


def _trees_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        bool(jnp.array_equal(x, y)) for x, y in zip(la, lb))


def _stacked_allclose(stacked, lists, atol):
    ref = tree_stack(lists)
    for x, y in zip(jax.tree.leaves(stacked), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol,
                                   rtol=0)


# ---------------------------------------------------------------------------
# Golden equivalence at K=8: ScaleEngine vs RoundEngine(local_exec="loop")
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def golden_refs(setup):
    """Reference trajectories, computed once per strategy on demand."""
    task, clients, cfg = setup
    cache = {}

    def get(name):
        if name not in cache:
            eng = RoundEngine(make_strategy(name), task, clients, cfg,
                              local_exec="loop")
            res = eng.run()
            cache[name] = (eng, res)
        return cache[name]

    return get


@pytest.mark.parametrize("reduction", ["ordered", "einsum"])
def test_dispfl_golden_k8(setup, golden_refs, reduction):
    """The tentpole contract: masks bit-identical for both reductions;
    with the ordered fold the *whole trajectory* (params, metrics) is
    bit-identical; the einsum fold agrees to fp-reduction-order tolerance
    (documented in repro/scale/__init__.py)."""
    task, clients, cfg = setup
    ref, rres = golden_refs("dispfl")
    eng = ScaleEngine(make_strategy("dispfl"), task, clients, cfg,
                      reduction=reduction)
    eres = eng.run()
    assert _trees_equal(eng.state["masks"], tree_stack(ref.state["masks"]))
    if reduction == "ordered":
        assert _trees_equal(eng.state["params"],
                            tree_stack(ref.state["params"]))
        assert eres.acc_history == rres.acc_history
    else:
        _stacked_allclose(eng.state["params"], ref.state["params"],
                          atol=1e-5)
        np.testing.assert_allclose(eres.acc_history, rres.acc_history,
                                   atol=1e-5)
    assert eres.comm_busiest_mb == pytest.approx(rres.comm_busiest_mb)
    assert eres.flops_per_round == pytest.approx(rres.flops_per_round)


@pytest.mark.slow
def test_dispfl_anneal_golden_k8(setup, golden_refs):
    task, clients, cfg = setup
    ref, rres = golden_refs("dispfl_anneal")
    eng = ScaleEngine(make_strategy("dispfl_anneal"), task, clients, cfg,
                      reduction="ordered")
    eres = eng.run()
    assert _trees_equal(eng.state["masks"], tree_stack(ref.state["masks"]))
    assert _trees_equal(eng.state["params"], tree_stack(ref.state["params"]))
    assert eres.acc_history == rres.acc_history
    assert eres.comm_busiest_mb == pytest.approx(rres.comm_busiest_mb)
    # the annealed budgets flow through traced counts: payload nnz shrinks
    nnz = stacked_nnz_per_client(eng.state["masks"])
    init_nnz = stacked_nnz_per_client(
        tree_stack(make_strategy("dispfl_anneal").init_state(
            task, clients, cfg)["masks"]))
    assert all(a < b for a, b in zip(nnz, init_nnz))


@pytest.mark.parametrize("reduction", ["ordered", "einsum"])
def test_dpsgd_golden_k8(setup, golden_refs, reduction):
    """dpsgd has no masks; its documented golden contract is metric
    equality + params at fp-contraction tolerance (the fused stacked
    program FMA-contracts the SGD update — even the engine's own vmap path
    differs from the loop by ~1e-8 here)."""
    task, clients, cfg = setup
    ref, rres = golden_refs("dpsgd")
    eng = ScaleEngine(make_strategy("dpsgd"), task, clients, cfg,
                      reduction=reduction)
    eres = eng.run()
    _stacked_allclose(eng.state["params"], ref.state["params"], atol=1e-5)
    np.testing.assert_allclose(eres.acc_history, rres.acc_history, atol=1e-5)
    assert eres.comm_busiest_mb == pytest.approx(rres.comm_busiest_mb)


def test_scale_checkpoint_interop_with_round_engine(setup, tmp_path):
    """ScaleEngine checkpoints are written in the engine's per-client list
    layout: a run checkpointed under ScaleEngine resumes bit-identically
    under RoundEngine, and vice versa (ordered fold)."""
    task, clients, cfg = setup
    path = str(tmp_path / "scale.npz")
    eng_a = ScaleEngine(make_strategy("dispfl"), task, clients, cfg,
                        reduction="ordered", callbacks=[Checkpointer(path)])
    it = eng_a.rounds()
    next(it)
    next(it)
    # finish under RoundEngine from the ScaleEngine checkpoint
    eng_b = RoundEngine(make_strategy("dispfl"), task, clients, cfg,
                        local_exec="loop").restore(path)
    res_b = eng_b.run()
    # uninterrupted loop reference
    eng_c = RoundEngine(make_strategy("dispfl"), task, clients, cfg,
                        local_exec="loop")
    res_c = eng_c.run()
    assert res_b.acc_history == res_c.acc_history
    assert _trees_equal(eng_b.state, eng_c.state)
    # and back: resume the RoundEngine-written archive under ScaleEngine
    eng_b.save(path)
    eng_d = ScaleEngine(make_strategy("dispfl"), task, clients, cfg,
                        reduction="ordered").restore(path)
    assert eng_d._next_round == cfg.rounds
    assert _trees_equal(eng_d.state["params"],
                        tree_stack(eng_c.state["params"]))


def test_scale_engine_rejects_unsupported_configs(setup):
    task, clients, cfg = setup
    import dataclasses as dc

    with pytest.raises(KeyError, match="no stacked adapter"):
        ScaleEngine(make_strategy("fedavg"), task, clients, cfg)
    with pytest.raises(ValueError, match="homogeneous"):
        ScaleEngine(make_strategy("dispfl"), task, clients,
                    dc.replace(cfg, capacities=[0.2] * 4 + [0.8] * 4))
    with pytest.raises(ValueError, match="-FT"):
        ScaleEngine(make_strategy("dpsgd_ft"), task, clients, cfg)
    with pytest.raises(ValueError, match="param_fraction"):
        ScaleEngine(make_strategy("dpsgd", param_fraction=0.5),
                    task, clients, cfg)
    # fp16 wire payloads are a message-boundary feature; the stacked mix
    # never crosses one, so the config must refuse rather than silently
    # run (and report) the fp32 trajectory
    with pytest.raises(ValueError, match="payload_dtype"):
        ScaleEngine(make_strategy("dispfl", payload_dtype="fp16"),
                    task, clients, cfg)
    ragged = [dc.replace(clients[0], train_x=clients[0].train_x[:8],
                         train_y=clients[0].train_y[:8])] + list(clients[1:])
    with pytest.raises(ValueError, match="effective batch size"):
        ScaleEngine(make_strategy("dispfl"), task, ragged, cfg)


def test_round_inputs_are_rows_of_device_resident_images(setup):
    """The clients' images go to the device once, as flat rows zero padded
    to the largest client; a round's local and evolve batches are indices
    into them, drawn as the reference engine draws its host batches."""
    task, clients, cfg = setup
    eng = ScaleEngine(make_strategy("dispfl"), task, clients, cfg)
    images = eng._device_images()
    assert eng._device_images() is images
    images = np.asarray(images)
    feat = int(np.prod(clients[0].train_x.shape[1:]))
    assert images.shape == (len(clients), max(c.n_train for c in clients),
                            feat)
    assert len({c.n_train for c in clients}) > 1      # padding exercised
    for k, c in enumerate(clients):
        np.testing.assert_array_equal(images[k, :c.n_train],
                                      c.train_x.reshape(c.n_train, feat))
        assert not images[k, c.n_train:].any()

    ctx = eng._make_ctx(1)
    bi, by, live = (np.asarray(a) for a in eng._batch_schedule(ctx))
    ev_i, ev_y = (np.asarray(a) for a in eng._evolve_batches(ctx))
    assert bi.dtype == ev_i.dtype == np.int32
    ref = eng._make_ctx(1)
    eng._batch_schedule(ref)             # the local phase's draws come first
    for k, c in enumerate(clients):
        assert bi[k].max() < c.n_train
        np.testing.assert_array_equal(by[k], c.train_y[bi[k]])
        assert live[k].sum() == cfg.local_epochs * -(-c.n_train
                                                     // cfg.batch_size)
        x, y = c.sample_batch(ref.client_rng(k), cfg.batch_size)
        np.testing.assert_array_equal(images[k][ev_i[k]],
                                      x.reshape(len(x), feat))
        np.testing.assert_array_equal(ev_y[k], y)


def test_stacked_eval_golden_equal_to_loop(setup):
    """The vmapped personalized eval replacing the per-client host loop is
    bit-equal to it — on round-0 state and on a trained trajectory, with
    ragged per-client test sets."""
    import dataclasses as dc

    from repro.fl.base import evaluate_clients, evaluate_clients_stacked

    task, clients, cfg = setup
    # make the test sets ragged so the padding + live-mask path is exercised
    ragged = [dc.replace(c, test_x=c.test_x[: len(c.test_y) - k],
                         test_y=c.test_y[: len(c.test_y) - k])
              for k, c in enumerate(clients)]
    eng = ScaleEngine(make_strategy("dispfl"), task, ragged,
                      dc.replace(cfg, rounds=2))
    loop = evaluate_clients(task, eng.adapter.eval_params(eng.state), ragged)
    stacked = evaluate_clients_stacked(
        task, eng.adapter.stacked_eval_params(eng.state), ragged)
    assert loop == stacked
    for _ in eng.rounds():
        pass
    loop = evaluate_clients(task, eng.adapter.eval_params(eng.state), ragged)
    assert eng._stacked_eval() == loop
    assert eng.result().final_accs == loop


# ---------------------------------------------------------------------------
# Stacked primitive parity (unit level)
# ---------------------------------------------------------------------------


def _random_world(k=6, density=0.5, seed=0):
    rng = np.random.default_rng(seed)
    shapes = {"conv/w": (3, 3, 2, 4), "fc": {"w": (17, 10), "b": (10,)}}

    def tree(fn):
        return {"conv/w": fn((k,) + shapes["conv/w"]),
                "fc": {"w": fn((k,) + shapes["fc"]["w"]),
                       "b": fn((k,) + shapes["fc"]["b"])}}

    w = tree(lambda s: jnp.asarray(rng.normal(size=s).astype(np.float32)))
    m = tree(lambda s: jnp.asarray((rng.random(s) < density)
                                   .astype(np.float32)))
    m["fc"]["b"] = jnp.ones_like(m["fc"]["b"])  # biases dense
    w = jax.tree.map(lambda a, b: a * b, w, m)
    return w, m


def test_plain_mix_stacked_matches_metropolis_reference():
    w, _ = _random_world(seed=3)
    k = 6
    wm = metropolis_weights(make_adjacency("random", k, 1, 2, 0))
    ref = []
    for i in range(k):
        acc = None
        for j in range(k):
            if wm[i, j] == 0.0:
                continue
            contrib = jax.tree.map(lambda x: wm[i, j] * x, tree_index(w, j))
            acc = contrib if acc is None else jax.tree.map(
                lambda u, v: u + v, acc, contrib)
        ref.append(acc)
    ref = tree_stack(ref)
    mix = jnp.asarray(wm, jnp.float32)
    # both reductions sit at fp tolerance of the eager reference: XLA
    # FMA-contracts the jitted multiply-accumulate (same reason the dpsgd
    # golden contract is tolerance-based, see test_dpsgd_golden_k8)
    for reduction in ("ordered", "einsum"):
        got = jax.jit(
            lambda p: plain_mix_stacked(p, mix, reduction))(w)
        for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-6, rtol=0)


def _topk_rows_by_argsort(scores, k):
    """The argsort + rank-scatter form ``_topk_rows`` replaced: each row's
    ranks under a stable descending argsort, selected where rank < k."""
    n = scores.shape[1]
    order = jnp.argsort(-scores, axis=1)
    rows = jnp.arange(scores.shape[0])[:, None]
    ranks = jnp.zeros(scores.shape, jnp.int32).at[rows, order].set(
        jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), scores.shape))
    return (ranks < k).astype(jnp.float32)


def _topk_block(n, bf16):
    rng = np.random.default_rng(n)
    s = np.stack([
        np.round(np.abs(rng.normal(size=n)) * 2) / 2,   # heavy ties
        np.full(n, 0.75),                               # all equal
        np.full(n, -np.inf),                            # all excluded
        np.zeros(n),                                    # all +0.0
        np.where(rng.random(n) < 0.4, -np.inf,          # some excluded
                 np.abs(rng.normal(size=n))),
        np.where(rng.random(n) < 0.2, np.nan,           # NaN ranks last
                 np.round(np.abs(rng.normal(size=n)))),
        np.abs(rng.normal(size=n)),
    ]).astype(np.float32)
    if bf16:
        s = np.asarray(jnp.asarray(s).astype(jnp.bfloat16).astype(jnp.float32))
    return jnp.asarray(s)


@pytest.mark.parametrize("bf16", [False, True], ids=["f32", "bf16"])
@pytest.mark.parametrize("n", [1, 2, 17, 31, 33, 1025, 4097])
def test_topk_rows_matches_argsort_rank_scatter(n, bf16):
    """The threshold search selects exactly what the stable argsort ranks
    select, ties and excluded entries included, for every k under one
    trace (k is a traced scalar)."""
    scores = _topk_block(n, bf16)
    traces = []

    @jax.jit
    def select(s, k):
        traces.append(1)
        return _topk_rows(s, k)

    finite = np.isfinite(np.asarray(scores)).sum(axis=1).tolist()
    for k in sorted({0, 1, n // 2, n, *finite}):
        got = np.asarray(select(scores, jnp.int32(k)))
        want = np.asarray(_topk_rows_by_argsort(scores, k))
        np.testing.assert_array_equal(got, want, err_msg=f"k={k}")
        assert (got.sum(axis=1) == k).all()
    assert len(traces) == 1
    digits = lambda bits: -(-bits // _RADIX_BITS)  # noqa: E731
    assert topk_row_passes(n) == (digits(32) + 1
                                  + digits((n - 1).bit_length()))


def _count_pass_block(rows, n):
    """A key block with runs of equal keys, the extreme keys 0 and
    0xFFFFFFFF, and candidates at both ends and inside the runs."""
    rng = np.random.default_rng(rows * 1000 + n)
    key = rng.integers(0, 2**32, size=(rows, n), dtype=np.uint64)
    key[:, ::3] = 0x80000000                    # a run of equal keys
    key[:, 1::7] = 0xFFFFFFFF
    key[:, 2::11] = 0
    key = key.astype(np.uint32)
    c = (1 << _RADIX_BITS) - 1
    cands = rng.integers(0, 2**32, size=(rows, c), dtype=np.uint64)
    cands[:, 0] = 0
    cands[:, 1 % c] = 0xFFFFFFFF
    cands[:, 2 % c] = 0x80000000                # inside the run
    cands[:, 3 % c] = 0x80000001                # just past it
    return key, cands.astype(np.uint32)


@pytest.mark.parametrize("mode", ["threshold", "tie_cut"])
@pytest.mark.parametrize("rows,n", [(1, 1), (7, 1000), (25, 4097)])
def test_count_pass_matches_one_candidate_sums(rows, n, mode):
    """The multi-candidate count pass counts what one plain row-sum per
    candidate counts: keys ``>= c`` (threshold pass), or keys tied at a
    threshold with an index below ``c`` (tie-cut pass)."""
    key, cands = _count_pass_block(rows, n)
    if mode == "threshold":
        block = jnp.asarray(key)
        select = lambda c: block >= c  # noqa: E731
        want = [np.sum(key >= cands[:, j, None], axis=1)
                for j in range(cands.shape[1])]
    else:
        tied = key == np.uint32(0x80000000)
        idx = np.broadcast_to(np.arange(n, dtype=np.uint32), key.shape)
        cands = cands % np.uint32(n + 2)        # cuts inside the row and past it
        block, jidx = jnp.asarray(tied), jnp.asarray(idx)
        select = lambda c: block & (jidx < c)  # noqa: E731
        want = [np.sum(tied & (idx < cands[:, j, None]), axis=1)
                for j in range(cands.shape[1])]
    got = jax.jit(lambda c: _count_pass(select, c))(jnp.asarray(cands))
    assert got.dtype == jnp.int32 and got.shape == cands.shape
    np.testing.assert_array_equal(np.asarray(got), np.stack(want, axis=1))


def _leaf(tree, path):
    return dict(tree_leaves_with_path(tree))[path]


def _cut_in_tie(scores, k) -> bool:
    """The k-th and (k+1)-th largest scores are equal and finite."""
    s = np.sort(np.asarray(scores).ravel())[::-1]
    return 0 < k < s.size and s[k - 1] == s[k] and np.isfinite(s[k])


@pytest.mark.parametrize("grid", [None, 0.5, 0.25],
                         ids=["random", "grid0.5", "grid0.25"])
def test_stacked_evolve_exact_matches_core_evolve(grid):
    """Batched prune/regrow with traced counts == the per-client reference
    (same argsort tie-breaks, exact counts), across several prune rates.
    On a coarse grid the weights and grads tie in groups that straddle
    both the keep and the grow cut."""
    w, m = _random_world(seed=5)
    rng = np.random.default_rng(7)
    g = jax.tree.map(
        lambda x: jnp.asarray(rng.normal(size=x.shape).astype(np.float32)), w)
    if grid is not None:
        w, g = (jax.tree.map(lambda x: jnp.round(x / grid) * grid, t)
                for t in (w, g))
        w = jax.tree.map(lambda a, b: a * b, w, m)
    k = 6
    dens = erk_densities_for_params(tree_index(w, 0), 0.5)
    budgets = layer_nnz_budgets(tree_index(w, 0), dens)
    straddled = {"keep": False, "grow": False}
    for rate in (0.0, 0.3, 0.77, 1.0):
        ref_m, ref_w = [], []
        for i in range(k):
            nm, nw = evolve_masks(tree_index(w, i), tree_index(m, i),
                                  tree_index(g, i), rate, budgets)
            ref_m.append(nm)
            ref_w.append(nw)
        counts = evolve_counts_for(budgets, rate)
        for path, (n_keep, n_prune) in counts.items():
            wl, ml, gl = (_leaf(t, path) for t in (w, m, g))
            for i in range(k):
                keep = jnp.where(ml[i] > 0, jnp.abs(wl[i]), -jnp.inf)
                half = _exact_topk_mask(keep, int(n_keep))
                grow = jnp.where(half > 0, -jnp.inf,
                                 jnp.abs(gl[i]).reshape(-1))
                straddled["keep"] |= _cut_in_tie(keep, int(n_keep))
                straddled["grow"] |= _cut_in_tie(grow, int(n_prune))
        got_m, got_w = jax.jit(
            lambda p, q, r, c: stacked_evolve_exact(p, q, r, c))(
                w, m, g, counts)
        assert _trees_equal(got_m, tree_stack(ref_m)), rate
        assert _trees_equal(got_w, tree_stack(ref_w)), rate
    if grid is not None:
        assert all(straddled.values()), straddled


@pytest.mark.parametrize("n", [256, 1000, 4096])
@pytest.mark.parametrize("rate", [0.1, 0.5])
def test_stacked_evolve_exact_one_leaf_matches_core_evolve(n, rate):
    """One (K, n) leaf, each client's mask at its budget of n // 2: the
    batched prune/regrow equals ``evolve_mask_layer`` per client bit for
    bit, keeps the budget exactly and zeroes every pruned weight."""
    k, budget = 4, n // 2
    ks = jax.random.split(jax.random.PRNGKey(n), k + 2)
    m = jnp.stack([jnp.zeros(n).at[jax.random.permutation(ks[i], n)[:budget]]
                   .set(1.0) for i in range(k)])
    w = jax.random.normal(ks[k], (k, n)) * m
    g = jax.random.normal(ks[k + 1], (k, n))
    counts = evolve_counts_for({"w": budget}, rate)
    got_m, got_w = jax.jit(stacked_evolve_exact)(
        {"w": w}, {"w": m}, {"w": g}, counts)
    got_m, got_w = np.asarray(got_m["w"]), np.asarray(got_w["w"])
    for i in range(k):
        nm, nw = evolve_mask_layer(w[i], m[i], g[i], rate, budget)
        np.testing.assert_array_equal(got_m[i], np.asarray(nm))
        np.testing.assert_array_equal(got_w[i], np.asarray(nw))
    np.testing.assert_array_equal(got_m.sum(axis=1), budget)
    assert not np.any(got_w[got_m == 0])


def test_stacked_evolve_exact_lowers_without_sort_or_scatter():
    """The exact top-k is a threshold search: with traced counts, neither
    the lowered nor the compiled program holds a sort or a scatter."""
    w, m = _random_world(seed=3)
    dens = erk_densities_for_params(tree_index(w, 0), 0.5)
    counts = evolve_counts_for(
        layer_nnz_budgets(tree_index(w, 0), dens), 0.3)
    lowered = jax.jit(stacked_evolve_exact).lower(w, m, w, counts)
    assert not re.search(r"stablehlo\.(sort|scatter)\b", lowered.as_text())
    assert not re.search(r"\s(sort|scatter)\(", lowered.compile().as_text())


def test_stacked_evolve_exact_on_a_client_mesh_subprocess():
    """With K sharded over a 4x1 client mesh, the evolve selects the same
    masks and weights, bit for bit, as on one device, and no key block is
    gathered across the mesh (the forced device count must precede jax
    init, hence the subprocess)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.core.evolve import layer_nnz_budgets
from repro.core.masks import erk_densities_for_params
from repro.launch.mesh import make_test_mesh
from repro.scale.stacked import evolve_counts_for, stacked_evolve_exact
from repro.sharding.rules import tree_stacked_shardings
from repro.utils.tree import tree_index

rng = np.random.default_rng(11)
shapes = {"conv/w": (8, 3, 3, 8, 16), "fc": {"w": (8, 64, 10), "b": (8, 10)}}
# weights and grads on a coarse grid, so that ties straddle the cuts
grid = lambda s: jnp.asarray(np.round(rng.normal(size=s) * 2) / 2, jnp.float32)
m = jax.tree.map(lambda s: jnp.asarray(rng.random(s) < 0.5, jnp.float32),
                 shapes, is_leaf=lambda x: isinstance(x, tuple))
m["fc"]["b"] = jnp.ones_like(m["fc"]["b"])
w = jax.tree.map(lambda s, mm: grid(s) * mm, shapes, m,
                 is_leaf=lambda x: isinstance(x, tuple))
g = jax.tree.map(lambda s: grid(s), shapes,
                 is_leaf=lambda x: isinstance(x, tuple))
counts = evolve_counts_for(layer_nnz_budgets(
    tree_index(w, 0), erk_densities_for_params(tree_index(w, 0), 0.5)), 0.3)
single = jax.jit(stacked_evolve_exact)(w, m, g, counts)
sh = tree_stacked_shardings(w, make_test_mesh(data=4, model=1))
args = [jax.device_put(t, sh) for t in (w, m, g)]
step = jax.jit(stacked_evolve_exact, out_shardings=(sh, sh))
meshed = step(*args, counts)
hlo = step.lower(*args, counts).compile().as_text()
assert "all-gather" not in hlo, "a key block is gathered"
for a, b in zip(jax.tree.leaves(meshed), jax.tree.leaves(single)):
    assert len(a.sharding.device_set) == 4
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("ok")
"""
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "ok"


# ---------------------------------------------------------------------------
# Stacked packed payloads
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [None, np.float16])
def test_pack_stacked_roundtrip(dtype):
    w, m = _random_world(seed=11)
    sp = pack_stacked(w, m, dtype=dtype)
    dense = unpack_stacked(sp)
    ref = jax.tree.map(lambda a, b: (a * b).astype(dtype or a.dtype), w, m)
    assert _trees_equal(dense, ref)
    # dense packing: all-ones bitmaps, full nnz
    sp_dense = pack_stacked(w, None)
    assert _trees_equal(unpack_stacked(sp_dense), w)


def test_split_stack_payloads_roundtrip_and_codec():
    w, m = _random_world(seed=13)
    sp = pack_stacked(w, m)
    parts = split_stacked(sp)
    assert len(parts) == 6
    # each split payload is codec-framable and equals the direct pack
    for i, part in enumerate(parts):
        direct = pack_tree(tree_index(w, i), tree_index(m, i))
        assert encoded_nbytes(part) == encoded_nbytes(direct)
        assert _trees_equal(
            jax.tree.leaves(part), jax.tree.leaves(direct))
    sp2 = stack_payloads(parts)
    assert _trees_equal(jax.tree.leaves(sp), jax.tree.leaves(sp2))


@pytest.mark.parametrize("backend", ["ref", "pallas", "pallas_rows"])
def test_fold_stacked_backends_agree(backend):
    w, m = _random_world(seed=17)
    sp = pack_stacked(w, m)
    num = jax.tree.map(jnp.zeros_like, w)
    den = jax.tree.map(jnp.zeros_like, w)
    n2, d2 = fold_stacked(num, den, sp, 1.0, backend=backend)
    assert _trees_equal(n2, jax.tree.map(lambda a, b: a * b, w, m))
    assert _trees_equal(d2, m)


# ---------------------------------------------------------------------------
# Sharding specs resolve on the test meshes
# ---------------------------------------------------------------------------


class _FakeMesh:
    def __init__(self, shape, axes):
        self.shape = dict(zip(axes, shape))
        self.axis_names = tuple(axes)


MESH_2X2 = _FakeMesh((2, 2), ("data", "model"))
MESH_PODS = _FakeMesh((2, 2, 2), ("pod", "data", "model"))


def test_stacked_spec_resolves_on_test_meshes():
    from repro.sharding.rules import stacked_spec

    # K=8 divides both client-axis products (a one-axis entry is stored
    # as the bare axis name)
    assert stacked_spec((8, 3, 3, 2, 4), MESH_2X2)[0] == "data"
    assert stacked_spec((8, 10), MESH_PODS)[0] == ("pod", "data")
    # K=2 on the pods mesh: ('pod','data') product 4 doesn't divide 2 ->
    # trimmed to ('pod',)
    assert stacked_spec((2, 10), MESH_PODS)[0] == "pod"
    # K=1 stays unsharded
    assert stacked_spec((1, 10), MESH_2X2)[0] is None
    # body dims never shard in the stacked layout
    for spec in (stacked_spec((8, 64, 64), MESH_2X2),
                 stacked_spec((8, 64, 64), MESH_PODS)):
        assert all(s is None for s in spec[1:])


def test_param_and_batch_specs_resolve_on_test_meshes():
    from repro.sharding.rules import batch_spec, param_spec

    # a stacked matmul weight: client axes lead, 'model' on the out dim
    spec = param_spec("blocks/attn/wq/w", (8, 4, 128, 128), MESH_2X2,
                      fsdp2d=False)
    assert spec[0] == "data"
    assert spec[-1] == "model"
    spec = param_spec("blocks/attn/wq/w", (8, 4, 128, 128), MESH_PODS,
                      fsdp2d=False)
    assert spec[0] == ("pod", "data")
    # replicated leaves stay replicated
    spec = param_spec("blocks/norm/scale", (8, 4, 128), MESH_2X2, False)
    assert all(s is None for s in spec[1:])
    b = batch_spec("tokens", (8, 2, 32), MESH_PODS)
    assert b[0] == ("pod", "data")


def test_scale_engine_sharded_subprocess():
    """K=8 over a 4-host-device mesh through the launcher (the forced
    device count must precede jax init, hence the subprocess), checked
    against the unsharded ScaleEngine run for identical accuracy."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    code = """
import json
from repro.data import build_federated_image_task
from repro.fl import FLConfig, make_cnn_task, make_strategy
from repro.launch.mesh import make_test_mesh
from repro.scale import ScaleEngine

clients, _ = build_federated_image_task(
    0, n_clients=8, partition="pathological", classes_per_client=2,
    n_train_per_class=24, n_test_per_client=16, hw=8, noise=0.7)
task = make_cnn_task("smallcnn", 10, 8, width=4)
cfg = FLConfig(n_clients=8, rounds=2, local_epochs=1, batch_size=16,
               degree=2, eval_every=1)
accs = {}
for label, mesh in (("meshed", make_test_mesh(data=4, model=1)),
                    ("single", None)):
    eng = ScaleEngine(make_strategy("dispfl"), task, clients, cfg, mesh=mesh)
    accs[label] = eng.run().acc_history
print(json.dumps(accs))
"""
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    import json
    accs = json.loads(r.stdout.strip().splitlines()[-1])
    np.testing.assert_allclose(accs["meshed"], accs["single"], atol=1e-5)
