"""Intersection-weighted gossip averaging (paper Alg. 1 line 7, Fig. 1b).

Given neighbor models w_j (stored densely but zero outside their masks) and
masks m_j, client k forms

    w_{k,t+1/2} = ( (w_k + sum_j w_j) / (m_k + sum_j m_j) ) ⊙ m_k

i.e. each coordinate is averaged over the subset of peers that actually hold
it.  Non-sparsifiable leaves (all-ones masks) reduce to the plain gossip
average.  Two forms:

* ``gossip_average_one`` — single-client form (list of neighbor trees), used
  by the per-client engine and simulator paths, and the tests' oracle.
* ``masked_gossip_stacked`` — all clients at once over a stacked (K-leading)
  client dim, the one stacked gossip: ``ScaleEngine``'s round program, the
  LM step of ``launch/train.py`` and the dry-run steps all call it.  Under a
  mesh the K dim is sharded over the client axes and GSPMD emits the
  collectives.  ``plain_mix_stacked`` is D-PSGD's row-stochastic mixing in
  the same stacked form.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any

def _intersection_avg(num, den, mask):
    """num/den on held coordinates, zero elsewhere.  den>=1 wherever mask=1."""
    den = jnp.maximum(den, 1.0)
    return (num / den) * mask


def gossip_average_one(
    own_params: PyTree,
    own_mask: PyTree,
    neighbor_params: list[PyTree],
    neighbor_masks: list[PyTree],
) -> PyTree:
    """Single-client intersection-weighted gossip (paper Alg. 1 line 7)."""

    def one(w, m, *rest):
        n = len(rest) // 2
        ws, ms = rest[:n], rest[n:]
        num = w * m.astype(w.dtype)
        den = m.astype(w.dtype)
        for wj, mj in zip(ws, ms):
            num = num + wj * mj.astype(w.dtype)
            den = den + mj.astype(w.dtype)
        return _intersection_avg(num, den, m.astype(w.dtype))

    return jax.tree.map(
        one, own_params, own_mask, *neighbor_params, *neighbor_masks
    )


REDUCTIONS = ("einsum", "ordered")


def _check_reduction(reduction: str) -> None:
    if reduction not in REDUCTIONS:
        raise ValueError(
            f"reduction must be one of {REDUCTIONS}, got {reduction!r}")


def masked_gossip_stacked(params: PyTree, masks: PyTree, adjacency: jax.Array,
                          reduction: str = "einsum",
                          accum_dtype=jnp.float32) -> PyTree:
    """Intersection-weighted gossip over the stacked client dim.

    ``adjacency`` is the (K, K) receive matrix with unit diagonal (client k
    mixes the models of every j with A[k, j] > 0, itself included).

    * ``"einsum"``: num/den are adjacency matmuls over K — the SPMD form
      (GSPMD turns the K-sharded contraction into collectives).  XLA picks
      the fp reduction order, so results match the reference engine to a
      few ulps, not bitwise.
    * ``"ordered"``: a fori-loop fold that adds contributions in exactly the
      reference order (own model first, then senders in ascending index),
      bit-identical to ``gossip_average_one`` per client.
    """
    _check_reduction(reduction)
    a = adjacency.astype(accum_dtype)

    if reduction == "einsum":

        def one(w, m):
            mf = m.astype(accum_dtype)
            wf = w.astype(accum_dtype) * mf
            num = jnp.einsum("kj,j...->k...", a, wf)
            den = jnp.einsum("kj,j...->k...", a, mf)
            mix = (num.astype(jnp.float32)
                   / jnp.maximum(den.astype(jnp.float32), 1.0))
            return (mix * m.astype(jnp.float32)).astype(w.dtype)

        return jax.tree.map(one, params, masks)

    k_clients = adjacency.shape[0]
    # off-diagonal gate: sender j contributes to receiver k iff an edge
    gate = a * (1.0 - jnp.eye(k_clients, dtype=accum_dtype))
    gate = (gate > 0).astype(accum_dtype)

    def one(w, m):
        mf = m.astype(accum_dtype)
        wf = w.astype(accum_dtype)
        bshape = (k_clients,) + (1,) * (w.ndim - 1)

        def body(j, carry):
            num, den = carry
            g = gate[:, j].reshape(bshape)
            return (num + g * (wf[j] * mf[j]), den + g * mf[j])

        num, den = jax.lax.fori_loop(0, k_clients, body, (wf * mf, mf))
        mix = (num.astype(jnp.float32)
               / jnp.maximum(den.astype(jnp.float32), 1.0))
        return (mix * m.astype(jnp.float32)).astype(w.dtype)

    return jax.tree.map(one, params, masks)


def plain_mix_stacked(params: PyTree, mixing: jax.Array,
                      reduction: str = "einsum") -> PyTree:
    """Row-stochastic mixing ``w_k <- sum_j W[k, j] w_j`` over the K dim
    (D-PSGD / Metropolis).  ``"ordered"`` adds terms in ascending sender
    index, matching the reference engine's accumulation bit for bit."""
    _check_reduction(reduction)
    if reduction == "einsum":

        def one(w):
            return jnp.einsum("kj,j...->k...", mixing.astype(w.dtype), w)

        return jax.tree.map(one, params)

    k_clients = mixing.shape[0]

    def one(w):
        wm = mixing.astype(w.dtype)
        bshape = (k_clients,) + (1,) * (w.ndim - 1)

        def body(j, acc):
            return acc + wm[:, j].reshape(bshape) * w[j]

        return jax.lax.fori_loop(0, k_clients, body, jnp.zeros_like(w))

    return jax.tree.map(one, params)
