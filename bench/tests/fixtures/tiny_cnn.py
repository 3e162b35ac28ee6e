"""Plain reference of the program's small CNN: three stride-2 3x3
convolutions of width w, 2w, 4w, each followed by GroupNorm and relu, global
average pooling and one linear head.  A CPU-test size only."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def init(key, cfg) -> dict:
    w, cin = cfg["width"], cfg["in_channels"]
    chans = [(cin, w), (w, 2 * w), (2 * w, 4 * w)]
    ks = jax.random.split(key, 4)
    p = {}
    for i, (a, b) in enumerate(chans):
        p[f"conv{i}"] = {"w": jax.random.normal(ks[i], (3, 3, a, b)) / np.sqrt(9 * a)}
        p[f"gn{i}"] = {"scale": jnp.ones((b,)), "bias": jnp.zeros((b,))}
    p["fc"] = {"w": jax.random.normal(ks[3], (4 * w, cfg["num_classes"])) / np.sqrt(4 * w),
               "b": jnp.zeros((cfg["num_classes"],))}
    return p


def _gn(p, x, groups, eps):
    n, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(n, h, w, g, c // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = jnp.square(xg - mean).mean(axis=(1, 2, 4), keepdims=True)
    y = ((xg - mean) * lax.rsqrt(var + eps)).reshape(n, h, w, c)
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def apply(params, x, cfg, dtype=jnp.float32, precision=lax.Precision.HIGHEST):
    x = x.astype(dtype)
    for i in range(3):
        x = lax.conv_general_dilated(
            x, params[f"conv{i}"]["w"].astype(dtype), (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
        x = jax.nn.relu(_gn(params[f"gn{i}"], x, cfg["gn_groups"], cfg["gn_eps"]))
    x = x.mean(axis=(1, 2))
    return (jnp.dot(x, params["fc"]["w"].astype(dtype), precision=precision)
            + params["fc"]["b"].astype(dtype))


def fwd_flops(cfg) -> float:
    w, h, cin = cfg["width"], cfg["image_hw"], cfg["in_channels"]
    total = 0.0
    for a, b in [(cin, w), (w, 2 * w), (2 * w, 4 * w)]:
        h //= 2
        total += 2.0 * 9 * a * b * h * h
    return total + 2.0 * 4 * w * cfg["num_classes"]
