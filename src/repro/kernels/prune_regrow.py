"""Pallas TPU kernel: fused threshold prune + gradient regrow (Alg. 2 apply).

The top-k *selection* (finding the per-layer magnitude threshold for pruning
and the gradient threshold for regrowth) is a tiny reduction done outside in
jnp (``ops.prune_regrow``); this kernel fuses the expensive elementwise pass
over the full weight/grad/mask tensors:

    keep   = mask==1 & |w| >= w_thresh
    grown  = mask==0 & |g| >= g_thresh
    new_m  = keep | grown
    new_w  = w * keep          (regrown coords re-enter at 0, paper §3.2)

Tie handling: threshold semantics may keep/grow a few more coordinates than
the exact-count argsort in ``core.evolve`` when values are exactly equal at
the threshold; tests compare against the threshold oracle in ``ref.py`` and
separately check the count drift against the exact version.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import _interpret

BLOCK = 2048


def _pr_kernel(w_ref, g_ref, m_ref, th_ref, new_m_ref, new_w_ref):
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    w_th = th_ref[0, 0]
    g_th = th_ref[0, 1]
    keep = (m > 0) & (jnp.abs(w) >= w_th)
    # zero-gradient coords never regrow (guards the all-ties-at-zero case)
    grown = (m <= 0) & (jnp.abs(g) >= g_th) & (jnp.abs(g) > 0)
    new_m = keep | grown
    new_m_ref[...] = new_m.astype(new_m_ref.dtype)
    new_w_ref[...] = (w * keep.astype(jnp.float32)).astype(new_w_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block",))
def prune_regrow_flat(w: jax.Array, g: jax.Array, m: jax.Array,
                      w_thresh: jax.Array, g_thresh: jax.Array,
                      block: int = BLOCK):
    """All inputs (N,); thresholds scalars.  Returns (new_mask, new_weights)."""
    n = w.shape[0]
    pad = (-n) % block
    if pad:
        w = jnp.pad(w, (0, pad))
        g = jnp.pad(g, (0, pad))
        m = jnp.pad(m, (0, pad))
    n_pad = n + pad
    th = jnp.stack([w_thresh, g_thresh]).astype(jnp.float32)[None, :]
    new_m, new_w = pl.pallas_call(
        _pr_kernel,
        grid=(n_pad // block,),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n_pad), m.dtype),
            jax.ShapeDtypeStruct((1, n_pad), w.dtype),
        ],
        interpret=_interpret(),
    )(w[None, :], g[None, :], m[None, :], th)
    return new_m[0, :n], new_w[0, :n]
