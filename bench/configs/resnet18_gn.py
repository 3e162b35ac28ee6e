"""Plain reference of the CIFAR ResNet18 with GroupNorm (He et al. 2016,
with every BatchNorm replaced by GroupNorm as DisPFL's App. B.2 does; a 3x3
stem without max-pool).  Written from the architecture alone, in ``jax.numpy``
with no kernel, batching or cache, so that the benchmark can check the
program against it.  Sizes come from ``resnet18_gn.json``.

``dtype`` and ``precision`` select the reference (float32 at HIGHEST) or the
lower-precision control (bfloat16 at DEFAULT).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)


def _conv_init(key, k, cin, cout):
    return {"w": _normal(key, (k, k, cin, cout), k * k * cin)}


def _gn_init(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def _blocks(cfg):
    """(name, cin, cout, stride) of every basic block, in order."""
    out, cin = [], cfg["stem_width"]
    for si, (w, nb) in enumerate(zip(cfg["stage_widths"],
                                     cfg["blocks_per_stage"])):
        for bi in range(nb):
            stride = cfg["stage_strides"][si] if bi == 0 else 1
            out.append((f"s{si}b{bi}", cin, w, stride))
            cin = w
    return out


def init(key, cfg) -> dict:
    """LeCun-normal convolutions and head, GroupNorm scale 1 and bias 0."""
    blocks = _blocks(cfg)
    keys = jax.random.split(key, len(blocks) + 2)
    p = {"stem": _conv_init(keys[0], 3, cfg["in_channels"], cfg["stem_width"]),
         "gn_stem": _gn_init(cfg["stem_width"])}
    for i, (name, cin, cout, stride) in enumerate(blocks):
        ks = jax.random.split(keys[i + 1], 3)
        b = {"conv1": _conv_init(ks[0], 3, cin, cout), "gn1": _gn_init(cout),
             "conv2": _conv_init(ks[1], 3, cout, cout), "gn2": _gn_init(cout)}
        if stride != 1 or cin != cout:
            b["down"] = _conv_init(ks[2], 1, cin, cout)
            b["gn_down"] = _gn_init(cout)
        p[name] = b
    width = cfg["stage_widths"][-1]
    p["fc"] = {"w": _normal(keys[-1], (width, cfg["num_classes"]), width),
               "b": jnp.zeros((cfg["num_classes"],), jnp.float32)}
    return p


def _conv(w, x, stride, dtype, precision):
    return lax.conv_general_dilated(
        x, w.astype(dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


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
    """Logits (B, classes) of images (B, H, W, C)."""
    groups, eps = cfg["gn_groups"], cfg["gn_eps"]
    x = x.astype(dtype)
    x = jax.nn.relu(_gn(params["gn_stem"],
                        _conv(params["stem"]["w"], x, 1, dtype, precision),
                        groups, eps))
    for name, cin, cout, stride in _blocks(cfg):
        b = params[name]
        y = jax.nn.relu(_gn(b["gn1"], _conv(b["conv1"]["w"], x, stride,
                                            dtype, precision), groups, eps))
        y = _gn(b["gn2"], _conv(b["conv2"]["w"], y, 1, dtype, precision),
                groups, eps)
        if "down" in b:
            x = _gn(b["gn_down"], _conv(b["down"]["w"], x, stride, dtype,
                                        precision), groups, eps)
        x = jax.nn.relu(x + y)
    x = x.mean(axis=(1, 2))
    return (jnp.dot(x, params["fc"]["w"].astype(dtype), precision=precision)
            + params["fc"]["b"].astype(dtype))


def fwd_flops(cfg) -> float:
    """Forward FLOPs of one image (multiply-add = 2): every convolution and
    the head, as ``models/cnn.py``'s ``resnet18_fwd_flops`` counts them."""
    h = cfg["image_hw"]
    total = 2.0 * 9 * cfg["in_channels"] * cfg["stem_width"] * h * h
    for _, cin, cout, stride in _blocks(cfg):
        h //= stride
        total += 2.0 * 9 * cin * cout * h * h + 2.0 * 9 * cout * cout * h * h
        if stride != 1 or cin != cout:
            total += 2.0 * cin * cout * h * h
    return total + 2.0 * cfg["stage_widths"][-1] * cfg["num_classes"]
