"""Pallas TPU kernel: fused intersection-weighted gossip average.

Computes, for one client k with J received models (self included):

    out = (sum_j W[j]) / max(sum_j M[j], 1) * m_own

in a single pass: the stacked neighbor tensors stream HBM->VMEM tile by
tile and the reduction, divide and re-mask fuse in VMEM, avoiding the two
HBM round-trips (numerator and denominator materialization) of the naive
implementation.

Layout: inputs are flattened to (J, N) with N padded to a multiple of the
lane tile; the grid walks N in ``block_n`` chunks, each block loading the
full J (neighbor counts are small: degree <= 10 busiest-node bound).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import _interpret

BLOCK_N = 1024  # lanes per grid step (multiple of 128)


def _gossip_kernel(w_ref, m_ref, own_ref, out_ref):
    w = w_ref[...].astype(jnp.float32)       # (J, block_n)
    m = m_ref[...].astype(jnp.float32)
    own = own_ref[...].astype(jnp.float32)   # (1, block_n)
    num = jnp.sum(w, axis=0, keepdims=True)
    den = jnp.maximum(jnp.sum(m, axis=0, keepdims=True), 1.0)
    out_ref[...] = ((num / den) * own).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n",))
def gossip_avg_flat(w_stack: jax.Array, m_stack: jax.Array, own_mask: jax.Array,
                    block_n: int = BLOCK_N) -> jax.Array:
    """w_stack, m_stack: (J, N); own_mask: (N,).  Returns (N,)."""
    j, n = w_stack.shape
    pad = (-n) % block_n
    if pad:
        w_stack = jnp.pad(w_stack, ((0, 0), (0, pad)))
        m_stack = jnp.pad(m_stack, ((0, 0), (0, pad)))
        own_mask = jnp.pad(own_mask, (0, pad))
    n_pad = n + pad
    grid = (n_pad // block_n,)
    out = pl.pallas_call(
        _gossip_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((j, block_n), lambda i: (0, i)),
            pl.BlockSpec((j, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), w_stack.dtype),
        interpret=_interpret(),
    )(w_stack, m_stack, own_mask[None, :])
    return out[0, :n]
