"""Plain reference of DisPFL's round (Dai et al., ICML 2022, Alg. 1 and 2),
written from the paper's description and the documented seed streams alone.

Per round t, for every client k (a Python loop, one client at a time):

1. gossip: ``w_k <- m_k * sum_j A[k,j] m_j w_j / max(sum_j A[k,j] m_j, 1)``
   over the round's time-varying random topology (``degree`` random cyclic
   permutations, a client always hears itself);
2. local SGD: the client's permuted, padded batch schedule, each live step
   ``w <- m * (w - lr * m * (g + wd * w))``;
3. evolve: a dense gradient on one sampled batch, then per sparsified layer
   keep the ``n_active - ceil(rate * n_active)`` largest ``|w|`` among active
   coordinates and regrow the ``ceil(rate * n_active)`` largest ``|g|`` among
   the rest; pruned weights become 0, regrown ones enter at 0.

``lr = lr0 * decay**t``, ``rate = alpha0 / 2 * (1 + cos(pi * t / rounds))``.
Layer budgets are the ERK allocation (Evci et al. 2020) of the global
density.  ``dtype``/``precision`` give the float32 reference (HIGHEST) or
the bfloat16 control; ``batch_frac`` < 1 plants the fault "half of the batch
left out" (the mean over the rest).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# ---------------------------------------------------------------------------
# masks and budgets
# ---------------------------------------------------------------------------


def leaves(tree) -> list[tuple[str, jax.Array]]:
    """(path "a/b/c", leaf) in the tree's flattening order."""
    out = []
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.append(("/".join(str(getattr(p, "key", p)) for p in kp), leaf))
    return out


def sparsified(shape) -> bool:
    """Convolution and linear weights are masked; norms and biases not."""
    return len(shape) >= 2


def erk_densities(shapes: dict[str, tuple], density: float) -> dict[str, float]:
    """Erdos-Renyi-Kernel layer densities whose total is ``density``: raw
    score sum(shape)/prod(shape), one global scale, saturated layers dense."""
    numel = {k: int(np.prod(s)) for k, s in shapes.items()}
    raw = {k: float(np.sum(s)) / float(np.prod(s)) for k, s in shapes.items()}
    target = density * sum(numel.values())
    dense: set[str] = set()
    while True:
        free = [k for k in shapes if k not in dense]
        denom = sum(raw[k] * numel[k] for k in free)
        eps = (target - sum(numel[k] for k in dense)) / denom if denom else 0.0
        grow = [k for k in free if raw[k] * eps > 1.0]
        if not grow:
            break
        dense.update(grow)
    return {k: 1.0 if k in dense else float(np.clip(raw[k] * eps, 0.0, 1.0))
            for k in shapes}


def budgets(template, density: float) -> dict[str, int]:
    """Active-coordinate count of every sparsified layer."""
    shapes = {p: tuple(x.shape) for p, x in leaves(template)
              if sparsified(x.shape)}
    dens = erk_densities(shapes, density)
    return {p: int(round(d * int(np.prod(shapes[p])))) for p, d in dens.items()}


_BUILDERS: dict = {}


def make_state(model, cfg: dict, seed, n_clients: int):
    """Seeded start of a run, in one jitted call on the device: one shared
    initialization, a Bernoulli ERK mask per client, client-stacked
    ``(w * m_k, m_k)``.  ``seed`` is an int or a PRNG key."""
    key = jax.random.key(seed) if isinstance(seed, int) else seed
    tag = (id(model), id(cfg), n_clients)
    if tag not in _BUILDERS:
        template = jax.eval_shape(lambda k: model.init(k, cfg), key)
        shapes = {p: tuple(x.shape) for p, x in leaves(template)
                  if sparsified(x.shape)}
        dens = erk_densities(shapes, cfg["density"])

        def build(key):
            k_init, k_mask = jax.random.split(key)
            w0 = model.init(k_init, cfg)
            paths = [p for p, _ in leaves(w0)]
            flat, treedef = jax.tree.flatten(w0)

            def client_masks(k):
                ks = jax.random.split(k, len(flat))
                return [jax.random.bernoulli(kk, dens[p], x.shape).astype(jnp.float32)
                        if p in dens else jnp.ones(x.shape, jnp.float32)
                        for kk, p, x in zip(ks, paths, flat)]

            ms = jax.vmap(client_masks)(jax.random.split(k_mask, n_clients))
            params = [x[None] * m for x, m in zip(flat, ms)]
            return (jax.tree.unflatten(treedef, params),
                    jax.tree.unflatten(treedef, ms))

        _BUILDERS[tag] = (model, cfg, jax.jit(build))
    return _BUILDERS[tag][2](key)


# ---------------------------------------------------------------------------
# the round's inputs from the seed (the documented streams)
# ---------------------------------------------------------------------------


def adjacency(n_clients: int, degree: int, t: int, seed: int) -> np.ndarray:
    """A[k, j] = 1 iff k receives j in round t; the diagonal is 1."""
    a = np.eye(n_clients)
    if degree >= n_clients:
        return np.ones((n_clients, n_clients))
    rng = np.random.default_rng(np.random.SeedSequence([seed, t]))
    for _ in range(degree):
        perm = rng.permutation(n_clients)
        a[np.arange(n_clients), perm[(np.argsort(perm) + 1) % n_clients]] = 1.0
    return a


def schedule(sizes: list[int], bs: int, epochs: int, seed: int, t: int):
    """Per client: the padded local batch indices (s_max, bs), the live
    steps (s_max,), and the evolve batch indices (bs,), all drawn from the
    client's (seed, t, k, 0) stream in that order."""
    orders, evolve = [], []
    for k, n in enumerate(sizes):
        rng = np.random.default_rng(np.random.SeedSequence([seed, t, k, 0]))
        parts = []
        for _ in range(epochs):
            o = rng.permutation(n)
            pad = (-n) % bs
            parts.append(np.concatenate([o, o[:pad]]) if pad else o)
        orders.append(np.concatenate(parts))
        evolve.append(rng.integers(0, n, size=min(bs, n)))
    s_max = max(len(o) // bs for o in orders)
    idx = np.stack([np.resize(o, s_max * bs).reshape(s_max, bs) for o in orders])
    live = np.stack([np.arange(s_max) < len(o) // bs for o in orders])
    return idx, live, np.stack(evolve)


def lr_at(cfg: dict, t: int) -> float:
    return cfg["lr"] * cfg["lr_decay"] ** t


def prune_rate(cfg: dict, t: int) -> float:
    tt = min(t, cfg["rounds"])
    return cfg["alpha0"] / 2.0 * (1.0 + math.cos(tt * math.pi / max(cfg["rounds"], 1)))


# ---------------------------------------------------------------------------
# the round
# ---------------------------------------------------------------------------


class Reference:
    """The clients' rounds in ``dtype`` at ``precision``: the gossip over the
    clients' stacked copies of each layer, then local SGD and evolve one
    client at a time.  ``data`` is one ``(train_x, train_y)`` per client."""

    def __init__(self, model, cfg: dict, data, seed: int,
                 dtype=jnp.float32, precision=lax.Precision.HIGHEST,
                 batch_frac: float = 1.0):
        self.cfg, self.data, self.seed = cfg, data, seed
        self.dtype, self.precision = dtype, precision
        self.bs = cfg["batch_size"]
        used = max(1, int(round(self.bs * batch_frac)))
        wd = cfg["weight_decay"]

        def loss(w, x, y):
            logits = model.apply(w, x, cfg, dtype=dtype, precision=precision)
            logits = logits.astype(jnp.float32)
            ll = jnp.take_along_axis(logits, y[:, None], axis=1)[:, 0]
            return jnp.mean(jax.nn.logsumexp(logits, axis=1) - ll)

        grad = jax.grad(loss)

        def local(w, m, bx, by, live, lr):
            def step(w, xyl):
                x, y, alive = xyl
                g = grad(w, x[:used], y[:used])
                new = jax.tree.map(
                    lambda p, gp, mp: (p - lr * mp * (gp + wd * p)) * mp,
                    w, g, m)
                return jax.tree.map(lambda a, b: jnp.where(alive, b, a),
                                    w, new), None
            return lax.scan(step, w, (bx, by, live))[0]

        def evolve(w, m, x, y, n_keep, n_prune):
            g = grad(w, x[:used], y[:used])
            pairs = [_evolve_leaf(wp, mp, gp, n_keep.get(p), n_prune.get(p))
                     for (p, wp), (_, mp), (_, gp)
                     in zip(leaves(w), leaves(m), leaves(g))]
            treedef = jax.tree.structure(w)
            return (jax.tree.unflatten(treedef, [a for a, _ in pairs]),
                    jax.tree.unflatten(treedef, [b for _, b in pairs]))

        def mix(a, ws, ms):
            def one(w, m):
                num = jnp.tensordot(a, w * m, axes=1, precision=precision)
                den = jnp.tensordot(a, m, axes=1, precision=precision)
                return num / jnp.maximum(den, 1) * m
            return jax.tree.map(one, ws, ms)

        self._local = jax.jit(local)
        self._evolve = jax.jit(evolve)
        self._mix = jax.jit(mix)
        self._take = jax.jit(lambda tree, k: jax.tree.map(lambda x: x[k], tree))
        self._stack = jax.jit(stacked)

    def start(self, params, masks):
        """The reference's copy of client-stacked starting trees."""
        cast = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(self.dtype), t))
        return cast(params), cast(masks)

    def round(self, params, masks, t: int):
        """One round over client-stacked ``params`` and ``masks``."""
        cfg, k_clients = self.cfg, self.cfg["n_clients"]
        a = adjacency(k_clients, cfg["degree"], t, self.seed)
        params = self._mix(jnp.asarray(a, self.dtype), params, masks)
        sizes = [len(y) for _, y in self.data]
        idx, live, ev = schedule(sizes, self.bs, cfg["local_epochs"],
                                 self.seed, t)
        lr = jnp.asarray(lr_at(cfg, t), self.dtype)
        rate = prune_rate(cfg, t)
        bud = budgets(self._take(params, 0), cfg["density"])
        n_prune = {p: int(math.ceil(rate * n)) for p, n in bud.items()}
        n_keep = {p: jnp.int32(bud[p] - n_prune[p]) for p in bud}
        n_prune = {p: jnp.int32(v) for p, v in n_prune.items()}
        out_p, out_m = [], []
        for k, (x, y) in enumerate(self.data):
            w, m = self._take(params, k), self._take(masks, k)
            w = self._local(w, m, jnp.asarray(x[idx[k]]),
                            jnp.asarray(y[idx[k]]), jnp.asarray(live[k]), lr)
            m, w = self._evolve(w, m, jnp.asarray(x[ev[k]]),
                                jnp.asarray(y[ev[k]]), n_keep, n_prune)
            out_p.append(w)
            out_m.append(m)
        return self._stack(*out_p), self._stack(*out_m)


def _topk(scores: jax.Array, k) -> jax.Array:
    """{0,1} of the k largest scores (ties to the lower index)."""
    order = jnp.argsort(-scores, stable=True)
    rank = jnp.zeros(scores.shape, jnp.int32).at[order].set(
        jnp.arange(scores.size, dtype=jnp.int32))
    return (rank < k).astype(jnp.float32)


def _evolve_leaf(w, m, g, n_keep, n_prune):
    if n_keep is None:
        return m, w
    neg = jnp.float32(-jnp.inf)
    mf, wf, gf = (x.reshape(-1).astype(jnp.float32) for x in (m, w, g))
    half = _topk(jnp.where(mf > 0, jnp.abs(wf), neg), n_keep)
    grown = _topk(jnp.where(half > 0, neg, jnp.abs(gf)), n_prune)
    new_m = (half + grown).reshape(w.shape)
    return new_m.astype(m.dtype), w * new_m.astype(w.dtype)


# ---------------------------------------------------------------------------
# what is compared
# ---------------------------------------------------------------------------


def stacked(*trees):
    """One client-stacked tree from per-client ones."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


@jax.jit
def leaf_stats(params, masks, params0):
    """Per leaf and client: the norm of the change since the start, the
    mask's nnz, and the mask as packed bits."""
    delta, nnz, bits = {}, {}, {}
    for (p, w), (_, m), (_, w0) in zip(leaves(params), leaves(masks),
                                       leaves(params0)):
        k = w.shape[0]
        d = (w.astype(jnp.float32) - w0.astype(jnp.float32)).reshape(k, -1)
        delta[p] = jnp.sqrt(jnp.sum(d * d, axis=1))
        on = (m != 0).reshape(k, -1)
        nnz[p] = jnp.sum(on, axis=1, dtype=jnp.int32)
        bits[p] = jnp.packbits(on, axis=1)
    return delta, nnz, bits


def host_stats(params, masks, params0) -> dict:
    delta, nnz, bits = jax.device_get(leaf_stats(params, masks, params0))
    numel = {p: int(np.prod(m.shape[1:])) for p, m in leaves(masks)}
    masked = {p: sparsified(m.shape[1:]) for p, m in leaves(masks)}
    return {"delta": delta, "nnz": nnz, "bits": bits, "numel": numel,
            "masked": masked}


def _leaf_norms(prog: dict, ref: dict):
    """Per leaf (one parameter tensor over all clients, as the program
    stacks it): the program's and the reference's norm of the change since
    the start, whether a mask covers the leaf, and the median leaf's
    reference norm.  Leaves whose change the reference leaves at rounding
    level (under a thousandth of the median leaf's) are left out."""
    paths = sorted(ref["delta"])
    norm = lambda st: np.array([np.sqrt(np.sum(np.square(np.asarray(  # noqa: E731
        st["delta"][p], np.float64)))) for p in paths])
    p, r = norm(prog), norm(ref)
    masked = np.array([ref["masked"][q] for q in paths])
    med = float(np.median(r))
    keep = r >= 1e-3 * med
    return p[keep], r[keep], masked[keep], med


def leaf_change_gap(prog: dict, ref: dict) -> float:
    """Worst leaf's gap between the program's and the reference's norm of
    the change, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    p, r, _, med = _leaf_norms(prog, ref)
    return float(np.max(np.abs(p - r) / np.maximum(r, med)))


def unmasked_leaf_gap(prog: dict, ref: dict) -> float:
    """Worst gap, over the leaves no mask covers (the norms' scales and
    biases), between the program's and the reference's norm of the change,
    against that leaf's own reference norm.  Every client updates these
    leaves in full, by steps far below their size (scales start at 1), so
    this is where storage in a lower precision shows first."""
    p, r, masked, _ = _leaf_norms(prog, ref)
    return float(np.max(np.abs(p[~masked] - r[~masked]) / r[~masked]))


def median_client_gap(prog: dict, ref: dict) -> float:
    """Median over clients of the gap between the program's and the
    reference's norm of the client's whole-model change, against the
    reference's."""
    norm = lambda st: np.sqrt(sum(np.square(np.asarray(st["delta"][p], np.float64))  # noqa: E731
                                  for p in ref["delta"]))
    p, r = norm(prog), norm(ref)
    return float(np.median(np.abs(p - r) / r))


def nnz_off_budget(stats: dict, bud: dict[str, int]) -> int:
    """(client, layer) pairs whose mask does not hold its budget."""
    return int(sum(np.sum(np.asarray(stats["nnz"][p]) != n)
                   for p, n in bud.items()))
