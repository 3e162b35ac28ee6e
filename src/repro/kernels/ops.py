"""Public wrappers around the block-sparse masked-matmul kernels.

They pad arbitrary shapes to tile-aligned layouts and slice the result
back.  The kernels are written for the TPU lowering (MXU-aligned tiles,
scalar prefetch, VMEM scratch); the backend picks compiled or interpret
mode (``repro.kernels._interpret``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.masked_matmul import (
    batched_masked_matmul as _batched_masked_matmul_tiled,
    masked_matmul as _masked_matmul_tiled,
)


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
    density the TPU actually sees (ERK/RigL concentrate layer density, so
    this tracks but upper-bounds coordinate density)."""
    from repro.kernels.masked_matmul import block_mask_from_mask
    k, n = mask.shape
    pk, pn = (-k) % bk, (-n) % bn
    mp = jnp.pad(mask, ((0, pk), (0, pn)))
    bm_ = block_mask_from_mask(mp, bk, bn)
    return float(jnp.mean(bm_.astype(jnp.float32)))
