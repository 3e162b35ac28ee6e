"""Pallas TPU kernel: fused bitmap-expand + accumulate for packed payloads.

The packed-gossip hot path repeats, per received neighbor payload,

    num += alpha * scatter(values at bitmap support)
    den += bitmap

The naive route densifies the payload (materialize the scattered tensor in
HBM, then add).  This kernel fuses the expansion into the accumulation: a
grid step loads one coordinate block of the accumulators, the matching
bitmap words, and a window of the contiguous value vector; bits are
expanded in VMEM, the block's values are gathered by an in-register prefix
sum, and the updated accumulator block is written back in place
(``input_output_aliases``) — one HBM round-trip per block, no dense
intermediate per neighbor.

Index plumbing: coordinate ``c`` of the block holds value
``offsets[block] + popcount(bits before c in the block)`` — ``offsets`` is
the host-precomputed exclusive prefix of per-block popcounts, so blocks are
independent and the grid is embarrassingly parallel.

Layout: 2D ``(1, N)`` arrays (TPU wants >= 2D); ``block_n`` coordinates per
grid step (multiple of 128 lanes and of the 32-bit word size).  ``values``
is padded by one block so a window load never overruns.  The jnp oracle
is ``repro.kernels.ref.packed_accum_ref``.

Neither kernel compiles for TPU yet: Mosaic refuses the ``(1, block_n // 32)``
word block and, in the rows form, the ``(1, block_n)`` row block of a
``(K, N)`` array (the last two block dims must be divisible by (8, 128) or
equal the array's).  They run in interpret mode on CPU and are off every
default path (``backend="ref"``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import _interpret

BLOCK_N = 1024  # coords per grid step: 8 sublane rows of 128 lanes, 32 words


def _packed_accum_kernel(num_ref, den_ref, words_ref, values_ref,
                         offsets_ref, alpha_ref, num_out, den_out,
                         *, block_n: int, block_dim: int = 0):
    words = words_ref[0, :]                       # (block_n // 32,) uint32
    shifts = jax.lax.broadcasted_iota(
        jnp.uint32, (words.shape[0], 32), dimension=1)
    bits = ((words[:, None] >> shifts) & jnp.uint32(1)).astype(jnp.int32)
    mask = bits.reshape(1, block_n).astype(jnp.float32)
    # local value index per coordinate: offset + #set bits before it
    # (int32 cumsum: exact for any nnz, unlike a float prefix sum)
    pos = jnp.cumsum(bits.reshape(-1)) - 1
    idx = jnp.maximum(pos + offsets_ref[0, pl.program_id(block_dim)], 0)
    vals = values_ref[0, :].astype(jnp.float32)
    contrib = (jnp.where(mask.reshape(-1) > 0, jnp.take(vals, idx), 0.0)
               .reshape(1, block_n))
    alpha = alpha_ref[0, 0].astype(jnp.float32)
    num_out[...] = (num_ref[...].astype(jnp.float32)
                    + alpha * contrib).astype(num_out.dtype)
    den_out[...] = (den_ref[...].astype(jnp.float32)
                    + mask).astype(den_out.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_n",))
def packed_accum_flat(num: jax.Array, den: jax.Array, words: jax.Array,
                      values: jax.Array, offsets: jax.Array,
                      alpha: jax.Array, block_n: int = BLOCK_N):
    """num, den: (N,) f32 with N a multiple of ``block_n``; words:
    (N // 32,) uint32; values: (nnz + block_n,) zero-padded; offsets:
    (N // block_n,) int32 exclusive prefix of per-block popcounts; alpha:
    () scalar.  Returns the updated (num, den), accumulated in place."""
    n = num.shape[0]
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    bw = block_n // 32
    n_blocks = grid[0]
    nv = values.shape[0]
    num2, den2 = pl.pallas_call(
        functools.partial(_packed_accum_kernel, block_n=block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, bw), lambda i: (0, i)),
            pl.BlockSpec((1, nv), lambda i: (0, 0)),
            pl.BlockSpec((1, n_blocks), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), num.dtype),
            jax.ShapeDtypeStruct((1, n), den.dtype),
        ],
        input_output_aliases={0: 0, 1: 1},
        interpret=_interpret(),
    )(num[None, :], den[None, :], words[None, :], values[None, :],
      offsets[None, :], jnp.asarray(alpha, jnp.float32).reshape(1, 1))
    return num2[0], den2[0]


@functools.partial(jax.jit,
                   static_argnames=("block_n",))
def packed_accum_rows(num: jax.Array, den: jax.Array, words: jax.Array,
                      values: jax.Array, offsets: jax.Array,
                      alpha: jax.Array, block_n: int = BLOCK_N):
    """Client-stacked form of ``packed_accum_flat``: fold K packed payloads
    into K accumulator rows in one launch.

    num, den: (K, N) f32 with N a multiple of ``block_n``; words:
    (K, N // 32) uint32 bitmaps; values: (K, max_nnz + block_n) per-client
    value rows (left-aligned, zero right-padded so a window load never
    overruns); offsets: (K, N // block_n) int32 exclusive prefixes of
    per-block popcounts *per client*; alpha: () scalar shared.

    The grid is (K, N // block_n) — the client dim maps to grid rows, so
    the same VMEM-resident kernel body serves both layouts (this is the
    stacked fold ``repro.scale.fold_stacked`` launches with
    ``backend="pallas_rows"``).  Accumulates in place via
    ``input_output_aliases`` exactly like the flat form.
    """
    k, n = num.shape
    assert n % block_n == 0, (n, block_n)
    grid = (k, n // block_n)
    bw = block_n // 32
    n_blocks = grid[1]
    nv = values.shape[1]
    num2, den2 = pl.pallas_call(
        functools.partial(_packed_accum_kernel, block_n=block_n,
                          block_dim=1),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda r, i: (r, i)),
            pl.BlockSpec((1, block_n), lambda r, i: (r, i)),
            pl.BlockSpec((1, bw), lambda r, i: (r, i)),
            pl.BlockSpec((1, nv), lambda r, i: (r, 0)),
            pl.BlockSpec((1, n_blocks), lambda r, i: (r, 0)),
            pl.BlockSpec((1, 1), lambda r, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda r, i: (r, i)),
            pl.BlockSpec((1, block_n), lambda r, i: (r, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, n), num.dtype),
            jax.ShapeDtypeStruct((k, n), den.dtype),
        ],
        input_output_aliases={0: 0, 1: 1},
        interpret=_interpret(),
    )(num, den, words, values, offsets,
      jnp.asarray(alpha, jnp.float32).reshape(1, 1))
    return num2, den2
