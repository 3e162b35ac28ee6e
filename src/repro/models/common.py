"""Shared model building blocks: init, norms, RoPE, activations, embeddings.

Everything is framework-free: params are nested dicts of jnp arrays, modules
are (init_fn, apply_fn) pairs of plain functions.  Compute dtype and param
dtype are separated so the same definitions serve the CPU simulator (f32)
and the TPU dry-run (bf16 params, f32 accumulation).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def normal_init(key, shape, dtype, stddev):
    return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)


def lecun_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    return normal_init(key, shape, dtype, 1.0 / np.sqrt(max(fan_in, 1)))


def embed_init(key, shape, dtype):
    return normal_init(key, shape, dtype, 1.0)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d, dtype):
    return {"scale": jnp.zeros((d,), dtype)}  # (1+scale) parameterization


def rmsnorm(params, x, eps=1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(dt)


def groupnorm_init(c, dtype):
    return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}


def groupnorm(params, x, groups=32, eps=1e-5):
    """GroupNorm over NHWC tensors (paper replaces BN with GN, App. B.2).

    The group fold runs on the ``(n, c)`` channel sums, never on the
    activation: splitting ``c`` into ``(groups, c / groups)`` would put a
    dimension of 2-16 in the TPU's 128-wide lanes and relayout the whole
    activation around every norm, forward and backward.  Two passes, as
    ``jnp.var``: the mean, then the mean square of the centred values.
    """
    n, h, w, c = x.shape
    g = min(groups, c)
    while c % g != 0:
        g -= 1

    def group_mean(t):  # (n, h, w, c) -> (n, 1, 1, c), each group's mean
        s = t.sum(axis=(1, 2)).reshape(n, g, c // g).sum(axis=-1)
        s = s / (h * w * (c // g))
        return jnp.repeat(s, c // g, axis=-1)[:, None, None, :]

    xf = x.astype(jnp.float32)
    xc = xf - group_mean(xf)
    xf = xc * jax.lax.rsqrt(group_mean(jnp.square(xc)) + eps)
    return (xf * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, D/2)
    ang = ang[..., None, :]  # (..., S, 1, D/2) broadcast over heads
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def activation(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": lambda x: jax.nn.gelu(x, approximate=True),
        "relu": jax.nn.relu,
    }[name]


# ---------------------------------------------------------------------------
# Dense / embedding layers
# ---------------------------------------------------------------------------


def dense_init(key, d_in, d_out, dtype, use_bias=False):
    p = {"w": lecun_init(key, (d_in, d_out), dtype)}
    if use_bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(params, x):
    y = x @ params["w"]
    if "b" in params:
        y = y + params["b"]
    return y


def embed_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    return jnp.take(table, ids, axis=0)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------


def softmax_xent(logits: jax.Array, labels: jax.Array, mask=None):
    """Mean token cross-entropy with f32 logsumexp; labels < 0 are padding."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    per_tok = lse - ll
    valid = (labels >= 0).astype(jnp.float32)
    if mask is not None:
        valid = valid * mask.astype(jnp.float32)
    return jnp.sum(per_tok * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def accuracy(logits: jax.Array, labels: jax.Array):
    pred = jnp.argmax(logits, axis=-1)
    valid = labels >= 0
    return jnp.sum((pred == labels) & valid) / jnp.maximum(jnp.sum(valid), 1)


@dataclasses.dataclass
class DTypePolicy:
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32

    @staticmethod
    def tpu():
        return DTypePolicy(param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16)
