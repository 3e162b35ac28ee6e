"""Stacked-client state containers and the stacked compute primitives.

Everything in this module operates on *client-stacked* pytrees: every leaf
carries a leading ``K`` (client) dimension, so one jitted program expresses
what the reference engine does with a Python loop over clients.  Under a
mesh (``sharding.rules.tree_stacked_shardings``) the K dim is sharded over
the client axes and GSPMD emits the collectives for the gossip fold.

Primitives
----------
``stacked_evolve_exact``    Alg. 2 prune/regrow batched over clients with
                            *traced* per-layer (n_keep, n_prune) counts, and
                            no recompilation when the cosine schedule or an
                            annealed density changes the counts per round.
                            Each top-k is a per-row threshold search with no
                            sort and no scatter: a radix search for the
                            k-th largest score's key, then an index cut
                            among the keys tied there, ties going to the
                            lower index as a stable descending argsort
                            breaks them (bit-identical to
                            ``core.evolve.evolve_mask_layer``).
``stacked_prune_regrow_threshold``
                            the threshold-based variant for giant archs
                            (sampled-sort thresholds, tie drift tolerated)
                            — previously a private body inside
                            ``launch/steps.make_mask_update_step``; it now
                            lives here so there is exactly one stacked
                            mask-search implementation.

The round's other two phases live below this package, where both engines
reach them: the stacked gossip in ``repro.core.gossip``
(``masked_gossip_stacked``, ``plain_mix_stacked``) and the local SGD phase
in ``repro.fl.base`` (``stacked_local_phase``).

Stacked packed payloads
-----------------------
``StackedPacked`` is the K-client form of ``repro.sparse.PackedSparse``:
bitmaps stacked ``(K, n_words)``, values right-padded to the max nnz with a
``(K,)`` nnz vector.  ``pack_stacked``/``unpack_stacked`` round-trip a
stacked state bit-exactly; ``split_stacked`` yields the K individual
``PackedSparse`` trees (what actually crosses a link, codec-sized), and
``fold_stacked`` accumulates a stacked payload into stacked (num, den)
accumulators through ``repro.kernels.packed_accum`` (ref or Pallas
backend).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import Counter
from repro.sparse.packed import (
    PackedSparse,
    _is_packed,
    _pack_bits,
    _unpack_bits,
    n_words,
)
from repro.utils.tree import tree_leaves_with_path, tree_map_with_path

PyTree = Any

# ---------------------------------------------------------------------------
# Stacked mask evolution — exact (golden) and threshold (giant-arch) forms
# ---------------------------------------------------------------------------


_KEY_BITS = 32
# Bits of a row's threshold (or tie cut) fixed per pass: a pass tests the
# 2**_RADIX_BITS - 1 candidates of the next digit in one read of the block.
# On a TPU v5e, one selection over a (25, 2359296) block took 18.9, 12.6,
# 11.8, 16.3 and 25.9 ms at 1 to 5 bits: from 4 bits on, a pass is bound
# by the vector unit's compares (0.92 ms, against 0.47 ms at 3 bits).
_RADIX_BITS = 3


def _digits(bits: int) -> int:
    return -(-bits // _RADIX_BITS)


def topk_row_passes(n: int) -> int:
    """Count passes ``_topk_rows`` makes over a row block of width ``n``:
    the threshold search, the count above the threshold, the tie cut."""
    return _digits(_KEY_BITS) + 1 + _digits((n - 1).bit_length())


def _order_keys(scores: jax.Array) -> jax.Array:
    """uint32 keys that order like ``-scores`` sorts them: for scores >= +0.0
    the float bits order like the value; ``-inf`` ranks below every finite
    score and NaN below ``-inf``, as ``jnp.argsort`` places them."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    key = jnp.where(scores == -jnp.inf, -1, bits)
    key = jnp.where(jnp.isnan(scores), -2, key)
    # flipping the sign bit maps int32 order onto uint32 order
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)


def _count_pass(select: Callable[[jax.Array], jax.Array],
                cands: jax.Array) -> jax.Array:
    """``(rows, C)`` int32: column j counts, per row of the block,
    ``select(cands[:, j:j+1])``. The C row-sums share one operand, so XLA
    fuses them into one multi-output reduction over a single read."""
    return jnp.stack([jnp.sum(select(cands[:, j, None]), axis=1,
                              dtype=jnp.int32)
                      for j in range(cands.shape[1])], axis=1)


def _radix_search(bits: int, rows: int,
                  fits: Callable[[jax.Array], jax.Array]) -> jax.Array:
    """Per row, the largest uint32 ``v < 2**bits`` that ``fits`` (0 if none
    does), where ``fits`` maps ``(rows, C)`` candidates to bools and, for a
    row, holds for every value below one that fits. Top digit first, each
    step tests the candidates ``v + j << shift``, j = 1 .. 2**_RADIX_BITS - 1,
    and adds the number that fit: they are a prefix of the j."""
    steps = _digits(bits)
    js = jnp.arange(1, 1 << _RADIX_BITS, dtype=jnp.uint32)
    top = jnp.uint32((1 << bits) - 1)

    def step(i, v):
        shift = (_RADIX_BITS * (steps - 1 - i)).astype(jnp.uint32)
        cands = v[:, None] + (js << shift)
        # the top digit is narrower when bits is no multiple of the radix
        ok = (js <= (top - v[:, None]) >> shift) & fits(cands)
        return v + (jnp.sum(ok, axis=1, dtype=jnp.uint32) << shift)

    return jax.lax.fori_loop(0, steps, step, jnp.zeros((rows,), jnp.uint32))


def _topk_rows(scores: jax.Array, k: jax.Array) -> jax.Array:
    """Per-row {0,1} selection of the ``k`` largest scores (``+0.0`` or more,
    or ``-inf``), with ``k`` *traced*: the set a stable descending argsort
    selects (``core.evolve._exact_topk_mask``), ties toward the lower index.

    No sort and no scatter: a radix search finds each row's threshold key
    ``t``, the largest with ``count(key >= t) >= k``; then a radix search
    over the index cuts the keys tied at ``t`` so that exactly ``k`` are
    selected. Every step is one count pass over the block
    (``topk_row_passes``)."""
    rows, n = scores.shape
    key = _order_keys(scores)
    k = jnp.asarray(k, jnp.int32)

    t = _radix_search(
        _KEY_BITS, rows,
        lambda cands: _count_pass(lambda x: key >= x, cands) >= k)[:, None]
    above = key > t
    tied = key == t
    need = k - jnp.sum(above, axis=1, dtype=jnp.int32)
    idx = jax.lax.broadcasted_iota(jnp.uint32, (rows, n), 1)

    # the largest c with count(tied & idx < c) < need: the need-th tied key
    # sits at index c (k == 0 leaves t at the all-ones key, which no score
    # maps to, so nothing ties)
    c = _radix_search(
        (n - 1).bit_length(), rows,
        lambda cands: _count_pass(lambda x: tied & (idx < x), cands)
        < need[:, None])
    return (above | (tied & (idx <= c[:, None]))).astype(jnp.float32)


def stacked_evolve_exact(params: PyTree, masks: PyTree, grads: PyTree,
                         counts: dict) -> tuple[PyTree, PyTree]:
    """Alg. 2 (magnitude prune + gradient regrow), batched over the K dim.

    ``counts`` maps sparsifiable leaf paths (unstacked convention, e.g.
    ``"conv0/w"``) to traced ``(n_keep, n_prune)`` int32 scalars — the same
    integers the reference computes from ``(prune_rate, n_active)`` with
    ``math.ceil`` on the host, so the cosine schedule (and dispfl_anneal's
    per-round ERK budgets) never trigger a recompile.  Leaves without an
    entry pass through unchanged.  Bit-identical per client to
    ``core.evolve.evolve_mask_layer``.
    """

    def one(path, w, m, g):
        if path not in counts:
            return m, w
        n_keep, n_prune = counts[path]
        kdim = w.shape[0]
        mf = m.reshape(kdim, -1).astype(jnp.float32)
        wf = w.reshape(kdim, -1).astype(jnp.float32)
        gf = g.reshape(kdim, -1).astype(jnp.float32)
        neg_inf = jnp.float32(-jnp.inf)
        keep_scores = jnp.where(mf > 0, jnp.abs(wf), neg_inf)
        m_half = _topk_rows(keep_scores, n_keep)
        grow_scores = jnp.where(m_half > 0, neg_inf, jnp.abs(gf))
        grown = _topk_rows(grow_scores, n_prune)
        new_m = (m_half + grown).reshape(w.shape)
        new_w = w * new_m.astype(w.dtype)
        return new_m.astype(m.dtype), new_w

    paired = tree_map_with_path(one, params, masks, grads)
    is_pair = lambda x: isinstance(x, tuple)  # noqa: E731
    new_masks = jax.tree.map(lambda t: t[0], paired, is_leaf=is_pair)
    new_params = jax.tree.map(lambda t: t[1], paired, is_leaf=is_pair)
    return new_masks, new_params


def evolve_topk_work(params: PyTree, counts: dict) -> tuple[int, int]:
    """Host-side (selections, count passes) of one ``stacked_evolve_exact``
    call: a keep and a grow selection per client and counted leaf, each
    making ``topk_row_passes`` passes over its row. Shapes only, no read."""
    selects = passes = 0
    for path, w in tree_leaves_with_path(params):
        if path in counts:
            kdim = w.shape[0]
            selects += 2 * kdim
            passes += 2 * kdim * topk_row_passes(w.size // kdim)
    return selects, passes


def evolve_counts_for(budgets: dict[str, int], prune_rate: float) -> dict:
    """Host-side per-round counts: the exact ``(n_keep, n_prune)`` integers
    the reference derives per layer (``math.ceil`` on the host float, so no
    f32 rounding drift against ``core.evolve.evolve_mask_layer``), as numpy
    int32 scalars: they reach the device with the step's other inputs, not
    one eager conversion each."""
    import math

    out = {}
    for path, n_active in budgets.items():
        n_prune = int(math.ceil(prune_rate * n_active))
        out[path] = (np.int32(n_active - n_prune), np.int32(n_prune))
    return out


def default_threshold_sparsifiable(w: jax.Array) -> bool:
    """Matrix-shaped stacked leaves; stacked norm scales / biases / dt
    vectors stay dense (mirrors ``core.masks.default_sparsifiable`` on the
    unstacked tree)."""
    return w.ndim >= 3 and w.shape[-1] >= 64 and w.shape[-2] >= 64


def stacked_prune_regrow_threshold(
    params: PyTree, masks: PyTree, grads: PyTree, prune_rate: jax.Array,
    density: float,
    sparsifiable: Callable[[jax.Array], bool] = default_threshold_sparsifiable,
) -> tuple[PyTree, PyTree]:
    """Threshold-based stacked prune/regrow for giant archs.

    Per client and leaf: kth-order-statistic thresholds via sort (the
    selection of ``core.evolve.evolve_mask_layer`` up to ties).  Layer budgets
    are static (``density`` x numel) so the program is shape-static; the
    |g| > 0 guard keeps zero-gradient coordinates (embedding rows absent
    from the batch) from mass-regrowing on threshold ties at 0.  This is
    the sampled-threshold counterpart of ``stacked_evolve_exact`` — tie
    drift tolerated, no exact-count guarantee — practical for leaves where
    an argsort-based exact top-k would dominate the step.
    """

    def one(w, g, m):
        if not sparsifiable(w):
            return m, w
        k = w.shape[0]
        wf = w.reshape(k, -1).astype(jnp.float32)
        gf = g.reshape(k, -1).astype(jnp.float32)
        mf = m.reshape(k, -1).astype(jnp.float32)
        n = wf.shape[1]
        n_active = max(1, int(round(density * n)))
        n_prune = jnp.ceil(prune_rate * n_active).astype(jnp.int32)
        n_keep = n_active - n_prune
        keep_sorted = jnp.sort(
            jnp.where(mf > 0, jnp.abs(wf), -jnp.inf), axis=1)[:, ::-1]
        w_th = jnp.take_along_axis(
            keep_sorted,
            jnp.broadcast_to(jnp.maximum(n_keep - 1, 0), (k,))[:, None],
            axis=1)
        grow_sorted = jnp.sort(
            jnp.where(mf > 0, -jnp.inf, jnp.abs(gf)), axis=1)[:, ::-1]
        g_th = jnp.take_along_axis(
            grow_sorted,
            jnp.broadcast_to(jnp.maximum(n_prune - 1, 0), (k,))[:, None],
            axis=1)
        keep = (mf > 0) & (jnp.abs(wf) >= w_th)
        grown = (mf <= 0) & (jnp.abs(gf) >= g_th) & (jnp.abs(gf) > 0)
        new_m = keep | grown
        new_w = (wf * keep).astype(w.dtype).reshape(w.shape)
        return new_m.astype(m.dtype).reshape(m.shape), new_w

    paired = jax.tree.map(one, params, grads, masks)
    is_pair = lambda x: isinstance(x, tuple)  # noqa: E731
    new_masks = jax.tree.map(lambda t: t[0], paired, is_leaf=is_pair)
    new_params = jax.tree.map(lambda t: t[1], paired, is_leaf=is_pair)
    return new_masks, new_params


# ---------------------------------------------------------------------------
# Stacked packed payloads
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StackedPacked:
    """K clients' packed messages for one leaf, in stacked form.

    ``bitmap`` is (K, n_words) uint32; ``values`` is (K, max_nnz) with each
    client's held values left-aligned and zero right-padding; ``nnz`` is
    the (K,) true counts.  ``shape`` is the *per-client* dense leaf shape
    (static aux data)."""

    bitmap: jax.Array
    values: jax.Array
    nnz: jax.Array
    shape: tuple[int, ...]

    @property
    def n_clients(self) -> int:
        return int(self.bitmap.shape[0])

    @property
    def n_coords(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    def tree_flatten(self):
        return (self.bitmap, self.values, self.nnz), (tuple(self.shape),)

    @classmethod
    def tree_unflatten(cls, aux, children):
        bitmap, values, nnz = children
        return cls(bitmap=bitmap, values=values, nnz=nnz, shape=aux[0])


def _is_stacked_packed(x) -> bool:
    return isinstance(x, StackedPacked)


def pack_stacked(stacked_params: PyTree, stacked_masks: Optional[PyTree] = None,
                 dtype=None) -> PyTree:
    """Pack a stacked (K-leading) state into ``StackedPacked`` leaves.

    Eager, data-dependent-shape (message-boundary) work — the stacked
    analogue of ``sparse.pack_tree``; ``masks=None`` packs dense (all-ones
    bitmaps, max_nnz = n_coords)."""

    def one(w, m):
        w = np.asarray(w)
        kdim = w.shape[0]
        shape = tuple(w.shape[1:])
        flat = w.reshape(kdim, -1)
        if m is None:
            flags = np.ones(flat.shape, dtype=bool)
        else:
            flags = np.asarray(m).reshape(kdim, -1) != 0
        nnz = flags.sum(axis=1).astype(np.int32)
        width = int(nnz.max()) if kdim else 0
        vals = np.zeros((kdim, width),
                        dtype=flat.dtype if dtype is None else dtype)
        words = np.zeros((kdim, n_words(flat.shape[1])), dtype=np.uint32)
        for k in range(kdim):
            held = flat[k][flags[k]]
            vals[k, : nnz[k]] = held if dtype is None else held.astype(dtype)
            words[k] = _pack_bits(flags[k])
        return StackedPacked(bitmap=jnp.asarray(words),
                             values=jnp.asarray(vals),
                             nnz=jnp.asarray(nnz), shape=shape)

    if stacked_masks is None:
        return jax.tree.map(lambda w: one(w, None), stacked_params)
    return jax.tree.map(one, stacked_params, stacked_masks)


def unpack_stacked(packed: PyTree) -> PyTree:
    """Dense stacked state from ``StackedPacked`` leaves (exact zeros off
    the bitmaps — ``unpack_stacked(pack_stacked(w, m)) == w ⊙ m``)."""

    def one(sp: StackedPacked):
        kdim = sp.n_clients
        out = np.zeros((kdim, sp.n_coords),
                       dtype=np.asarray(sp.values).dtype)
        words = np.asarray(sp.bitmap)
        vals = np.asarray(sp.values)
        nnz = np.asarray(sp.nnz)
        for k in range(kdim):
            flags = _unpack_bits(words[k], sp.n_coords)
            out[k, flags] = vals[k, : nnz[k]]
        return jnp.asarray(out.reshape((kdim,) + sp.shape))

    return jax.tree.map(one, packed, is_leaf=_is_stacked_packed)


def split_stacked(packed: PyTree) -> list[PyTree]:
    """The K individual ``PackedSparse`` trees of a stacked payload — what
    physically crosses a link (codec-framable, padding stripped)."""
    leaves = jax.tree.leaves(packed, is_leaf=_is_stacked_packed)
    if not leaves:
        return []
    kdim = leaves[0].n_clients

    def one_client(k):
        return jax.tree.map(
            lambda sp: PackedSparse(
                bitmap=sp.bitmap[k],
                values=sp.values[k, : int(sp.nnz[k])],
                shape=sp.shape),
            packed, is_leaf=_is_stacked_packed)

    return [one_client(k) for k in range(kdim)]


def stack_payloads(payloads: Sequence[PyTree]) -> PyTree:
    """Inverse of ``split_stacked``: K ``PackedSparse`` trees (identical
    structure/shapes, possibly ragged nnz) into one ``StackedPacked``."""

    def one(*leaves: PackedSparse):
        nnz = np.asarray([p.nnz for p in leaves], dtype=np.int32)
        width = int(nnz.max()) if leaves else 0
        vals = np.zeros((len(leaves), width),
                        dtype=np.asarray(leaves[0].values).dtype)
        for k, p in enumerate(leaves):
            vals[k, : nnz[k]] = np.asarray(p.values)
        return StackedPacked(
            bitmap=jnp.stack([p.bitmap for p in leaves]),
            values=jnp.asarray(vals), nnz=jnp.asarray(nnz),
            shape=leaves[0].shape)

    return jax.tree.map(one, *payloads, is_leaf=_is_packed)


def _fold_rows_pallas(nu: jax.Array, de: jax.Array, sp: StackedPacked,
                      alpha: float) -> tuple[jax.Array, jax.Array]:
    """One-launch stacked fold via ``kernels.packed_accum.packed_accum_rows``
    (grid = clients x coordinate blocks)."""
    from repro.kernels.packed_accum import BLOCK_N, packed_accum_rows

    kdim = sp.n_clients
    n = sp.n_coords
    pad = (-n) % BLOCK_N
    n_pad = n + pad
    words = np.zeros((kdim, n_pad // 32), dtype=np.uint32)
    words[:, : n_words(n)] = np.asarray(sp.bitmap)
    vals_in = np.asarray(sp.values)
    vals = np.zeros((kdim, vals_in.shape[1] + BLOCK_N), dtype=vals_in.dtype)
    vals[:, : vals_in.shape[1]] = vals_in
    # per-client exclusive prefixes of per-block popcounts (host, tiny)
    offsets = np.zeros((kdim, n_pad // BLOCK_N), dtype=np.int32)
    for k in range(kdim):
        pc = _unpack_bits(words[k], n_pad).reshape(-1, BLOCK_N).sum(axis=1)
        offsets[k] = np.concatenate([[0], np.cumsum(pc)[:-1]])
    shape = (kdim,) + sp.shape
    numf = jnp.pad(nu.reshape(kdim, -1).astype(jnp.float32), ((0, 0), (0, pad)))
    denf = jnp.pad(de.reshape(kdim, -1).astype(jnp.float32), ((0, 0), (0, pad)))
    num2, den2 = packed_accum_rows(
        numf, denf, jnp.asarray(words), jnp.asarray(vals),
        jnp.asarray(offsets), jnp.float32(alpha))
    return (num2[:, :n].reshape(shape).astype(nu.dtype),
            den2[:, :n].reshape(shape).astype(de.dtype))


def fold_stacked(num: PyTree, den: PyTree, packed: PyTree, alpha: float = 1.0,
                 backend: str = "ref") -> tuple[PyTree, PyTree]:
    """Fold a stacked payload into stacked (num, den) accumulators —
    client k's payload into accumulator row k.  Backends: ``"ref"`` /
    ``"pallas"`` loop clients through the same per-payload
    ``repro.sparse.ops.accumulate`` fold the per-client mix uses;
    ``"pallas_rows"`` launches the batched ``packed_accum_rows`` kernel
    once per leaf (grid = clients x blocks)."""
    from repro.sparse.ops import accumulate

    def one(nu, de, sp: StackedPacked):
        if backend == "pallas_rows":
            return _fold_rows_pallas(nu, de, sp, alpha)
        rows_n, rows_d = [], []
        for k in range(sp.n_clients):
            ps = PackedSparse(bitmap=sp.bitmap[k],
                              values=sp.values[k, : int(sp.nnz[k])],
                              shape=sp.shape)
            rn, rd = accumulate(nu[k], de[k], ps, alpha, backend)
            rows_n.append(rn)
            rows_d.append(rd)
        return jnp.stack(rows_n), jnp.stack(rows_d)

    paired = jax.tree.map(one, num, den, packed,
                          is_leaf=lambda x: _is_stacked_packed(x))
    is_pair = lambda x: isinstance(x, tuple)  # noqa: E731
    new_num = jax.tree.map(lambda t: t[0], paired, is_leaf=is_pair)
    new_den = jax.tree.map(lambda t: t[1], paired, is_leaf=is_pair)
    return new_num, new_den


@jax.jit
def _nnz_per_client(stacked_masks: PyTree) -> jax.Array:
    return sum(jnp.sum(jnp.reshape(m != 0, (m.shape[0], -1)), axis=1)
               for m in jax.tree.leaves(stacked_masks))


def stacked_nnz_per_client(stacked_masks: PyTree,
                           syncs: Optional[Counter] = None) -> list[int]:
    """Per-client nnz of a stacked mask tree (the comm-accounting input):
    one program over every leaf and one blocking device read, counted in
    ``syncs``."""
    total = np.asarray(_nnz_per_client(stacked_masks))
    if syncs is not None:
        syncs.inc()
    return [int(c) for c in total]
