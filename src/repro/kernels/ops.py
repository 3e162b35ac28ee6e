"""Jitted public wrappers around the Pallas kernels.

These handle arbitrary shapes (padding/reshaping to tile-aligned layouts),
threshold computation for prune/regrow, and pytree-level convenience APIs.
The kernels are written for the TPU lowering (MXU-aligned tiles, scalar
prefetch, VMEM scratch); the backend picks compiled or interpret mode
(``repro.kernels._interpret``).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.gossip_avg import gossip_avg_flat
from repro.kernels.masked_matmul import (
    batched_masked_matmul as _batched_masked_matmul_tiled,
    masked_matmul as _masked_matmul_tiled,
)
from repro.kernels.prune_regrow import prune_regrow_flat

PyTree = Any


# ---------------------------------------------------------------------------
# gossip average
# ---------------------------------------------------------------------------


def gossip_avg(w_list: list[jax.Array], m_list: list[jax.Array],
               own_mask: jax.Array) -> jax.Array:
    """Intersection-weighted average of J same-shape tensors (self first)."""
    shape = own_mask.shape
    w_stack = jnp.stack([w.reshape(-1) for w in w_list])
    m_stack = jnp.stack([m.reshape(-1) for m in m_list])
    out = gossip_avg_flat(w_stack, m_stack, own_mask.reshape(-1))
    return out.reshape(shape)


def gossip_avg_tree(params_list: list[PyTree], masks_list: list[PyTree],
                    own_mask: PyTree) -> PyTree:
    """Pytree-level gossip (self must be params_list[0]/masks_list[0])."""
    flat = [jax.tree.leaves(p) for p in params_list]
    flat_m = [jax.tree.leaves(m) for m in masks_list]
    own_leaves, treedef = jax.tree.flatten(own_mask)
    out = []
    for i, own in enumerate(own_leaves):
        out.append(gossip_avg([f[i] for f in flat], [f[i] for f in flat_m],
                              own))
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# block-sparse masked matmul
# ---------------------------------------------------------------------------


def masked_matmul(x: jax.Array, w: jax.Array, mask: jax.Array,
                  bm: int = 128, bn: int = 128, bk: int = 128) -> jax.Array:
    """y = x @ (w ⊙ mask) with zero-block skipping; pads to tile multiples."""
    m_dim, k_dim = x.shape
    k2, n_dim = w.shape
    assert k_dim == k2
    pm, pk, pn = (-m_dim) % bm, (-k_dim) % bk, (-n_dim) % bn
    xp = jnp.pad(x, ((0, pm), (0, pk)))
    wp = jnp.pad(w, ((0, pk), (0, pn)))
    mp = jnp.pad(mask, ((0, pk), (0, pn)))
    y = _masked_matmul_tiled(xp, wp, mp, bm=bm, bn=bn, bk=bk)
    return y[:m_dim, :n_dim]


def batched_masked_matmul(x: jax.Array, w: jax.Array, mask: jax.Array,
                          bm: int = 128, bn: int = 128, bk: int = 128
                          ) -> jax.Array:
    """y[u] = x[u] @ (w[u] ⊙ mask[u]) in one launch — the multi-tenant
    serving matmul (repro.serve).  Pads M/K/N to tile multiples; the user
    dim U is a grid dimension, never padded."""
    u_dim, m_dim, k_dim = x.shape
    u2, k2, n_dim = w.shape
    assert (u_dim, k_dim) == (u2, k2), ((u_dim, k_dim), (u2, k2))
    pm, pk, pn = (-m_dim) % bm, (-k_dim) % bk, (-n_dim) % bn
    xp = jnp.pad(x, ((0, 0), (0, pm), (0, pk)))
    wp = jnp.pad(w, ((0, 0), (0, pk), (0, pn)))
    mp = jnp.pad(mask, ((0, 0), (0, pk), (0, pn)))
    y = _batched_masked_matmul_tiled(xp, wp, mp, bm=bm, bn=bn, bk=bk)
    return y[:, :m_dim, :n_dim]


def block_occupancy(mask: jax.Array, bk: int = 128, bn: int = 128) -> float:
    """Fraction of (bk, bn) weight tiles that are non-empty — the *compute*
    density the TPU actually sees (DESIGN.md §3: ERK/RigL concentrate layer
    density, so this tracks but upper-bounds coordinate density)."""
    from repro.kernels.masked_matmul import block_mask_from_mask
    k, n = mask.shape
    pk, pn = (-k) % bk, (-n) % bn
    mp = jnp.pad(mask, ((0, pk), (0, pn)))
    bm_ = block_mask_from_mask(mp, bk, bn)
    return float(jnp.mean(bm_.astype(jnp.float32)))


# ---------------------------------------------------------------------------
# prune + regrow
# ---------------------------------------------------------------------------


def prune_regrow(w: jax.Array, g: jax.Array, m: jax.Array,
                 prune_rate: float):
    """Threshold-based Alg. 2 apply for one layer.

    Thresholds are derived from the exact counts (kth order statistics), so
    up to ties this matches core.evolve.evolve_mask_layer.
    Returns (new_mask, new_weights).
    """
    wf = w.reshape(-1)
    gf = g.reshape(-1)
    mf = m.reshape(-1)
    n_active = jnp.sum(mf > 0)
    n_prune = jnp.ceil(prune_rate * n_active).astype(jnp.int32)
    n_keep = (n_active - n_prune).astype(jnp.int32)

    keep_scores = jnp.where(mf > 0, jnp.abs(wf.astype(jnp.float32)), -jnp.inf)
    sorted_keep = jnp.sort(keep_scores)[::-1]
    w_thresh = sorted_keep[jnp.maximum(n_keep - 1, 0)]

    grow_scores = jnp.where(mf > 0, -jnp.inf, jnp.abs(gf.astype(jnp.float32)))
    sorted_grow = jnp.sort(grow_scores)[::-1]
    g_thresh = sorted_grow[jnp.maximum(n_prune - 1, 0)]

    new_m, new_w = prune_regrow_flat(wf, gf, mf, w_thresh, g_thresh)
    return new_m.reshape(m.shape).astype(m.dtype), new_w.reshape(w.shape)
