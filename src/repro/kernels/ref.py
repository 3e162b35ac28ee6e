"""Pure-jnp oracles for every Pallas kernel (the correctness references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def masked_matmul_ref(x: jax.Array, w: jax.Array, mask: jax.Array) -> jax.Array:
    return (x @ (w * mask.astype(w.dtype))).astype(x.dtype)


def batched_masked_matmul_ref(x: jax.Array, w: jax.Array,
                              mask: jax.Array) -> jax.Array:
    """Per-user oracle for the user-batched kernel: x (U, M, K); w, mask
    (U, K, N) -> (U, M, N), each user against its own masked weights."""
    return jax.vmap(masked_matmul_ref)(x, w, mask)


def packed_accum_ref(num: jax.Array, den: jax.Array, flags: jax.Array,
                     values: jax.Array, alpha: float = 1.0):
    """Oracle for kernels.packed_accum: num += alpha * scatter(values at
    flags), den += flags.  flags: (N,) bool; values: (nnz,) in flag order."""
    flags = flags.reshape(-1)
    pos = jnp.cumsum(flags.astype(jnp.int32)) - 1
    vals = jnp.take(values.astype(jnp.float32), jnp.maximum(pos, 0))
    contrib = jnp.where(flags, vals, 0.0)
    return (num + jnp.float32(alpha) * contrib,
            den + flags.astype(jnp.float32))
