"""Packed compute: gossip / axpy over ``PackedSparse`` payloads.

These are the ops the mix hot path runs on received messages: a client
keeps ONE pair of dense accumulators (num, den) per leaf and folds each
arrived payload in as

    num += alpha * scatter(values at bitmap support)      # packed axpy
    den += bitmap                                         # intersection count

then finalizes with the intersection average (``core.gossip``'s exact
formula), so ``packed_gossip_one`` is bit-identical to
``core.gossip.gossip_average_one`` fed the equivalent dense neighbors —
the golden contract ``tests/test_sparse.py`` pins down.

Cost model, stated honestly: per activation the work is O(degree) payload
folds — O(degree · nnz) value traffic plus one dense accumulator pass per
fold (the fused kernel's HBM round-trip) — versus the generic fallback's
O(K) full-tree mix.  It scales with node degree, never with the number of
clients; the *wire* is strictly O(nnz).

Two backends:

* ``"ref"`` (default) — eager numpy/jnp expansion, the oracle and the
  default path,
* ``"pallas"`` — the fused ``repro.kernels.packed_accum`` kernel,
  accumulating in place block by block (interpret mode on CPU; it does
  not yet compile for TPU, see that module).

``COUNTERS`` tracks accumulate work (calls / values touched) so tests can
assert the O(degree · nnz) — not O(K · model) — scaling of the per-client
mix (``Strategy.mix_one``).
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gossip import _intersection_avg
from repro.obs import CounterSet
from repro.sparse.packed import (
    PackedSparse,
    _unpack_bits,
    n_words,
    unpack,
    unpack_mask,
)

PyTree = Any

#: accumulate instrumentation: calls == payload-leaf folds performed,
#: values == nnz actually touched (reset with ``reset_counters``)
COUNTERS = {"accum_calls": 0, "accum_values": 0}

# mirror the dict into the process-wide registry (dict stays the API the
# scaling tests use; the gauges read it live, so snapshots never drift)
OBS = CounterSet("sparse.ops")
OBS.gauge("accum_calls", fn=lambda: COUNTERS["accum_calls"])
OBS.gauge("accum_values", fn=lambda: COUNTERS["accum_values"])


def reset_counters() -> None:
    COUNTERS["accum_calls"] = 0
    COUNTERS["accum_values"] = 0


def _accumulate_ref(num: jax.Array, den: jax.Array, ps: PackedSparse,
                    alpha: float) -> tuple[jax.Array, jax.Array]:
    up = unpack(ps).astype(num.dtype)
    m = unpack_mask(ps, den.dtype)
    # alpha == 1.0 folds with a bare add, matching the dense gossip loop's
    # ``num + w_j * m_j`` bit for bit
    num = num + up if alpha == 1.0 else num + alpha * up
    return num, den + m


def _accumulate_pallas(num: jax.Array, den: jax.Array, ps: PackedSparse,
                       alpha: float) -> tuple[jax.Array, jax.Array]:
    from repro.kernels.packed_accum import BLOCK_N, packed_accum_flat

    shape = num.shape
    n = ps.n_coords
    pad = (-n) % BLOCK_N
    n_pad = n + pad
    words = np.zeros(n_pad // 32, dtype=np.uint32)
    words[: n_words(n)] = np.asarray(ps.bitmap)
    vals = np.asarray(ps.values)
    vals = np.concatenate([vals, np.zeros(BLOCK_N, dtype=vals.dtype)])
    # exclusive prefix of per-block popcounts (host side, tiny)
    pc = _unpack_bits(words, n_pad).reshape(-1, BLOCK_N).sum(axis=1)
    offsets = np.concatenate([[0], np.cumsum(pc)[:-1]]).astype(np.int32)
    numf = jnp.pad(num.reshape(-1).astype(jnp.float32), (0, pad))
    denf = jnp.pad(den.reshape(-1).astype(jnp.float32), (0, pad))
    num2, den2 = packed_accum_flat(
        numf, denf, jnp.asarray(words), jnp.asarray(vals),
        jnp.asarray(offsets), jnp.float32(alpha))
    return (num2[:n].reshape(shape).astype(num.dtype),
            den2[:n].reshape(shape).astype(den.dtype))


def accumulate(num: jax.Array, den: jax.Array, ps: PackedSparse,
               alpha: float = 1.0, backend: str = "ref"):
    """Fold one packed leaf into dense (num, den) accumulators."""
    COUNTERS["accum_calls"] += 1
    COUNTERS["accum_values"] += ps.nnz
    if backend == "pallas":
        return _accumulate_pallas(num, den, ps, alpha)
    return _accumulate_ref(num, den, ps, alpha)


def packed_gossip_one(own_params: PyTree, own_mask: PyTree,
                      neighbor_packed: Sequence[PyTree],
                      backend: str = "ref") -> PyTree:
    """Intersection-weighted gossip for ONE client from packed neighbor
    payloads (paper Alg. 1 line 7) — O(degree · nnz) work, bit-identical to
    ``gossip_average_one`` on the densified neighbors."""

    def one(w, m, *packs):
        mf = m.astype(w.dtype)
        num = w * mf
        den = mf
        for p in packs:
            num, den = accumulate(num, den, p, 1.0, backend)
        return _intersection_avg(num, den, mf)

    return jax.tree.map(one, own_params, own_mask, *neighbor_packed)


def packed_axpy(acc: PyTree, packed: PyTree, alpha: float,
                backend: str = "ref") -> PyTree:
    """acc + alpha * densify(packed), leafwise, without materializing the
    densified payload outside the fused accumulate."""

    def one(a, p):
        COUNTERS["accum_calls"] += 1
        COUNTERS["accum_values"] += p.nnz
        if backend == "pallas":
            num, _ = _accumulate_pallas(a, jnp.zeros_like(a), p, alpha)
            return num
        return a + alpha * unpack(p).astype(a.dtype)

    return jax.tree.map(one, acc, packed)
