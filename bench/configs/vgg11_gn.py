"""Plain reference of the CIFAR VGG11 with GroupNorm (Simonyan and Zisserman
2015, configuration "A" as torchvision's ``vgg11`` builds it, with every
BatchNorm replaced by GroupNorm as DisPFL's App. B.2 does).  Written from
the architecture alone, in ``jax.numpy`` with no kernel, batching or cache,
so that the benchmark can check the program against it.  Sizes come from
``vgg11_gn.json``: ``features`` lists the 3x3 convolutions' widths, each
followed by GroupNorm and relu, and ``"M"`` for a 2x2 max-pool of stride 2.

Departures from torchvision's VGG11, each the program's:

- the CIFAR head: one linear layer on the 512 features of the last pool
  (a 1x1 map at 32x32), in place of the adaptive 7x7 pool and the three
  layers of 4096.  The paper gives no CIFAR classifier, so this one is
  assumed (``assumed["head"]`` in ``vgg11_gn.json``);
- GroupNorm in place of BatchNorm;
- no bias on the convolutions (GroupNorm's bias follows each).

``dtype`` and ``precision`` select the reference (float32 at HIGHEST) or the
lower-precision control (bfloat16 at DEFAULT).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _convs(cfg):
    """(index, cin, cout) of every convolution, in order."""
    out, cin = [], cfg["in_channels"]
    for c in cfg["features"]:
        if c != "M":
            out.append((len(out), cin, c))
            cin = c
    return out


def init(key, cfg) -> dict:
    """LeCun-normal convolutions and head, GroupNorm scale 1 and bias 0."""
    convs = _convs(cfg)
    keys = jax.random.split(key, len(convs) + 1)
    p = {}
    for i, cin, cout in convs:
        p[f"conv{i}"] = {"w": jax.random.normal(keys[i], (3, 3, cin, cout),
                                                jnp.float32) / np.sqrt(9 * cin)}
        p[f"gn{i}"] = {"scale": jnp.ones((cout,), jnp.float32),
                       "bias": jnp.zeros((cout,), jnp.float32)}
    width = convs[-1][2]
    p["fc"] = {"w": jax.random.normal(keys[-1], (width, cfg["num_classes"]),
                                      jnp.float32) / np.sqrt(width),
               "b": jnp.zeros((cfg["num_classes"],), jnp.float32)}
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


def _max_pool(x):
    """2x2 max-pool of stride 2: the largest of each 2x2 block."""
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def apply(params, x, cfg, dtype=jnp.float32, precision=lax.Precision.HIGHEST):
    """Logits (B, classes) of images (B, H, W, C)."""
    groups, eps = cfg["gn_groups"], cfg["gn_eps"]
    x = x.astype(dtype)
    i = 0
    for c in cfg["features"]:
        if c == "M":
            x = _max_pool(x)
            continue
        x = lax.conv_general_dilated(
            x, params[f"conv{i}"]["w"].astype(dtype), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
        x = jax.nn.relu(_gn(params[f"gn{i}"], x, groups, eps))
        i += 1
    x = x.mean(axis=(1, 2))
    return (jnp.dot(x, params["fc"]["w"].astype(dtype), precision=precision)
            + params["fc"]["b"].astype(dtype))


def fwd_flops(cfg) -> float:
    """Forward FLOPs of one image (multiply-add = 2): every convolution and
    the head, as ``models/cnn.py``'s ``vgg11_fwd_flops`` counts them."""
    h, cin, total = cfg["image_hw"], cfg["in_channels"], 0.0
    for c in cfg["features"]:
        if c == "M":
            h //= 2
        else:
            total += 2.0 * 9 * cin * c * h * h
            cin = c
    return total + 2.0 * cin * cfg["num_classes"]
