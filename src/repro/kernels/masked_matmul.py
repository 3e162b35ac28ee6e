"""Pallas TPU kernel: block-sparse masked matmul  y = x @ (w ⊙ m).

This is the TPU-native realization of DisPFL's sparse-compute saving: the
MXU has no unstructured-sparsity path, so the coordinate mask is
summarized into a (K/bk, N/bn) *block mask*; tiles whose block is empty are skipped entirely via ``@pl.when`` on a scalar-prefetched
SMEM mask — the MXU never sees them.  Non-empty tiles multiply the
elementwise-masked weights, so the result equals the dense reference
exactly (``ref.masked_matmul_ref``).

Grid: (M/bm, N/bn, K/bk), K innermost; a VMEM f32 scratch accumulates
across K and flushes at the last K step.

``batched_masked_matmul`` is the multi-tenant serving form (repro.serve):
a leading *user-major* grid dimension serves U personalized (w, m) pairs in
ONE launch — the per-user block masks ride the same scalar prefetch, so a
user whose mask leaves a tile empty skips it while other users still
compute theirs.  This batches the matmul kernel exactly the way
``packed_accum_rows`` batched the accumulator kernel: same kernel body,
one more grid dimension mapping users to grid rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _interpret

DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 128, 128, 128


def _mm_kernel(bmask_ref, x_ref, w_ref, m_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    j = pl.program_id(1)
    live = bmask_ref[k, j] != 0

    @pl.when(live)
    def _accum():
        x = x_ref[...]
        w = (w_ref[...] * m_ref[...].astype(w_ref.dtype))
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def block_mask_from_mask(mask: jax.Array, bk: int, bn: int) -> jax.Array:
    """(K, N) coordinate mask -> (K/bk, N/bn) int32 block occupancy."""
    k, n = mask.shape
    mb = mask.reshape(k // bk, bk, n // bn, bn)
    return (jnp.sum(mb != 0, axis=(1, 3)) > 0).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk"))
def masked_matmul(x: jax.Array, w: jax.Array, mask: jax.Array,
                  bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                  bk: int = DEFAULT_BK) -> jax.Array:
    """x: (M, K); w, mask: (K, N).  Shapes must tile evenly (wrapper in
    ops.py pads arbitrary shapes)."""
    m_dim, k_dim = x.shape
    _, n_dim = w.shape
    assert m_dim % bm == 0 and k_dim % bk == 0 and n_dim % bn == 0, (
        f"shape ({m_dim},{k_dim})x({k_dim},{n_dim}) not divisible by "
        f"({bm},{bk},{bn})")
    n_k = k_dim // bk
    bmask = block_mask_from_mask(mask, bk, bn)
    grid = (m_dim // bm, n_dim // bn, n_k)
    return pl.pallas_call(
        functools.partial(_mm_kernel, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k, *_: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k, *_: (k, j)),
                pl.BlockSpec((bk, bn), lambda i, j, k, *_: (k, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m_dim, n_dim), x.dtype),
        interpret=_interpret(),
    )(bmask, x, w, mask)


# ---------------------------------------------------------------------------
# user-batched form: U personalized (w, m) pairs in one launch (repro.serve)
# ---------------------------------------------------------------------------


def _bmm_kernel(bmask_ref, x_ref, w_ref, m_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    u = pl.program_id(0)
    j = pl.program_id(2)
    live = bmask_ref[u, k, j] != 0

    @pl.when(live)
    def _accum():
        x = x_ref[0]
        w = (w_ref[0] * m_ref[0].astype(w_ref.dtype))
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def batched_block_mask(mask: jax.Array, bk: int, bn: int) -> jax.Array:
    """(U, K, N) coordinate masks -> (U, K/bk, N/bn) int32 block occupancy."""
    u, k, n = mask.shape
    mb = mask.reshape(u, k // bk, bk, n // bn, bn)
    return (jnp.sum(mb != 0, axis=(2, 4)) > 0).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk"))
def batched_masked_matmul(x: jax.Array, w: jax.Array, mask: jax.Array,
                          bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                          bk: int = DEFAULT_BK) -> jax.Array:
    """y[u] = x[u] @ (w[u] ⊙ m[u]) for every user u, one device launch.

    x: (U, M, K); w, mask: (U, K, N).  Shapes must tile evenly (the wrapper
    in ops.py pads arbitrary shapes).  Grid is (U, M/bm, N/bn, K/bk) — the
    user dim maps to grid rows, per-user block masks are scalar-prefetched,
    and the same ``@pl.when`` tile-skipping applies per user.
    """
    u_dim, m_dim, k_dim = x.shape
    u2, _, n_dim = w.shape
    assert u_dim == u2, (u_dim, u2)
    assert m_dim % bm == 0 and k_dim % bk == 0 and n_dim % bn == 0, (
        f"shape ({u_dim},{m_dim},{k_dim})x({u_dim},{k_dim},{n_dim}) not "
        f"divisible by ({bm},{bk},{bn})")
    n_k = k_dim // bk
    bmask = batched_block_mask(mask, bk, bn)
    grid = (u_dim, m_dim // bm, n_dim // bn, n_k)
    return pl.pallas_call(
        functools.partial(_bmm_kernel, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, bk), lambda u, i, j, k, *_: (u, i, k)),
                pl.BlockSpec((1, bk, bn), lambda u, i, j, k, *_: (u, k, j)),
                pl.BlockSpec((1, bk, bn), lambda u, i, j, k, *_: (u, k, j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda u, i, j, k, *_: (u, i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((u_dim, m_dim, n_dim), x.dtype),
        interpret=_interpret(),
    )(bmask, x, w, mask)
