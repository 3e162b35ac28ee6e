"""Model adapters the serving engine is generic over.

The engine only needs three things from a model: a base ``init``, a
per-request input builder (seed-derived, so runs are reproducible), and a
*batched* forward that scores U user models against U inputs in one device
launch.  Three adapters cover the repo's model families:

* ``MLPModel`` — a bias-free relu MLP whose whole forward is a chain of
  masked matmuls.  This is the one model the block-sparse kernels can run
  end to end, so it supports all three backends:

  - ``vmap``   — ``jax.vmap`` over the per-user dense-masked params.  The
    store's params are already ``w ⊙ m``, so this is bit-exact (fp32)
    against the per-user loop — the property the engine's exactness tests
    pin down.
  - ``ref``    — per-layer ``kernels.ref.batched_masked_matmul_ref``
    (pure jnp, one fused launch per layer).
  - ``pallas`` — per-layer ``kernels.ops.batched_masked_matmul``: the
    user-major grid kernel with scalar-prefetched per-user block masks and
    ``@pl.when`` tile skipping.

* ``TaskModel`` — wraps an FL ``Task`` (the CNN backbones training
  checkpoints come from).  Conv models have no masked-matmul pipeline, so
  only the ``vmap`` backend applies.

* ``ArchModel`` — wraps a registered smoke arch (``configs.SMOKE_ARCHS``)
  as a one-step scorer: prefill a short prompt, return last-position
  logits.  ``vmap`` backend only, same stacked-params pattern the old
  serving demo used.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

BACKENDS = ("vmap", "ref", "pallas")


def _relu(x):
    return jnp.maximum(x, 0.0)


class MLPModel:
    """Bias-free relu MLP: every layer is ``h @ (w ⊙ m)`` — the matmul
    pipeline the batched kernel serves.  ``rows`` is the number of input
    rows per request (M of the matmul)."""

    def __init__(self, d_in: int = 64, widths: tuple[int, ...] = (128, 128),
                 n_out: int = 32, rows: int = 4):
        self.d_in = int(d_in)
        self.dims = (self.d_in, *[int(w) for w in widths], int(n_out))
        self.rows = int(rows)
        self._keys = [f"layer{i}" for i in range(len(self.dims) - 1)]
        self._jfwd: dict = {}

    def init(self, key: jax.Array) -> PyTree:
        ks = jax.random.split(key, len(self._keys))
        params = {}
        for i, name in enumerate(self._keys):
            fan_in = self.dims[i]
            params[name] = {"w": (jax.random.normal(
                ks[i], (self.dims[i], self.dims[i + 1]), jnp.float32)
                / np.sqrt(fan_in))}
        return params

    def make_input(self, seed: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x1]))
        return rng.standard_normal((self.rows, self.d_in)).astype(np.float32)

    def forward(self, params: PyTree, x: jax.Array) -> jax.Array:
        """Single-user forward over dense(-masked) params — the oracle the
        batched backends are checked against."""
        h = x
        for i, name in enumerate(self._keys):
            h = h @ params[name]["w"]
            if i < len(self._keys) - 1:
                h = _relu(h)
        return h

    def _build(self, backend: str):
        if backend == "vmap":
            def fwd(ps, ms, xs):
                del ms  # params are already w ⊙ m
                return jax.vmap(self.forward)(ps, xs)
            return jax.jit(fwd)
        if backend == "ref":
            from repro.kernels.ref import batched_masked_matmul_ref as bmm
        elif backend == "pallas":
            from repro.kernels.ops import batched_masked_matmul as bmm
        else:
            raise ValueError(f"backend must be one of {BACKENDS}, got {backend}")

        def fwd(ps, ms, xs):
            h = xs
            for i, name in enumerate(self._keys):
                h = bmm(h, ps[name]["w"], ms[name]["w"])
                if i < len(self._keys) - 1:
                    h = _relu(h)
            return h
        return jax.jit(fwd)

    def batched_forward(self, params_stack: PyTree, masks_stack: PyTree,
                        xs: jax.Array, backend: str = "vmap") -> jax.Array:
        """xs: (U, rows, d_in) -> (U, rows, n_out); one launch per layer."""
        if backend not in self._jfwd:
            self._jfwd[backend] = self._build(backend)
        return self._jfwd[backend](params_stack, masks_stack, xs)

    def backends(self) -> tuple[str, ...]:
        return BACKENDS


class TaskModel:
    """Serve an FL ``Task``'s model family (conv CNNs): request = one image
    batch, response = class logits.  vmap backend only."""

    def __init__(self, task, hw: int = 16, in_ch: int = 3, rows: int = 1):
        self.task = task
        self.hw = int(hw)
        self.in_ch = int(in_ch)
        self.rows = int(rows)
        self._jfwd = None

    def init(self, key: jax.Array) -> PyTree:
        return self.task.init_fn(key)

    def make_input(self, seed: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x2]))
        return rng.standard_normal(
            (self.rows, self.hw, self.hw, self.in_ch)).astype(np.float32)

    def forward(self, params: PyTree, x: jax.Array) -> jax.Array:
        return self.task.apply_fn(params, x)

    def batched_forward(self, params_stack: PyTree, masks_stack: PyTree,
                        xs: jax.Array, backend: str = "vmap") -> jax.Array:
        del masks_stack
        if backend != "vmap":
            raise ValueError(
                f"TaskModel ({self.task.name}) has no masked-matmul "
                f"pipeline; only the vmap backend applies, got {backend}")
        if self._jfwd is None:
            self._jfwd = jax.jit(jax.vmap(self.forward))
        return self._jfwd(params_stack, xs)

    def backends(self) -> tuple[str, ...]:
        return ("vmap",)


class ArchModel:
    """Serve a registered smoke arch as a one-step scorer: prefill
    ``prompt_len`` tokens, return the last position's logits."""

    def __init__(self, cfg, prompt_len: int = 8, rows: int = 1):
        from repro.models import bind
        self.cfg = cfg
        self.api = bind(cfg, remat=False)
        self.prompt_len = int(prompt_len)
        self.rows = int(rows)
        self._jfwd = None

    def init(self, key: jax.Array) -> PyTree:
        return self.api.init(key)

    def make_input(self, seed: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x3]))
        return rng.integers(0, self.cfg.vocab,
                            size=(self.rows, self.prompt_len),
                            dtype=np.int32)

    def forward(self, params: PyTree, tokens: jax.Array) -> jax.Array:
        b, s = tokens.shape
        batch = {"tokens": tokens}
        kw = {}
        max_len = s + self.cfg.prefix_len    # prefix rides in the kv cache
        if self.cfg.prefix_len:
            batch["prefix"] = jnp.zeros(
                (b, self.cfg.prefix_len, self.cfg.d_model))
        if self.cfg.enc_layers:
            batch["frames"] = jnp.zeros((b, 8, self.cfg.d_model))
            kw["enc_len"] = 8
        cache = self.api.init_cache(b, max_len, **kw)
        logits, _ = self.api.prefill(params, batch, cache)
        return logits[:, -1, :]

    def batched_forward(self, params_stack: PyTree, masks_stack: PyTree,
                        xs: jax.Array, backend: str = "vmap") -> jax.Array:
        del masks_stack
        if backend != "vmap":
            raise ValueError(
                f"ArchModel ({self.cfg.name}) has no masked-matmul "
                f"pipeline; only the vmap backend applies, got {backend}")
        if self._jfwd is None:
            self._jfwd = jax.jit(jax.vmap(self.forward))
        return self._jfwd(params_stack, xs)

    def backends(self) -> tuple[str, ...]:
        return ("vmap",)
