"""Masked-matmul kernel validation: shape/dtype sweeps vs the pure-jnp
oracles in ref.py, executed in interpret mode (kernel bodies run on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.masked_matmul import block_mask_from_mask


@pytest.mark.parametrize("shape", [(64, 128, 128), (128, 256, 128),
                                   (70, 200, 90), (13, 50, 17)])
@pytest.mark.parametrize("density", [0.0, 0.2, 1.0])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_matmul_sweep(shape, density, dtype):
    m_dim, k_dim, n_dim = shape
    key = jax.random.PRNGKey(m_dim + k_dim)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (m_dim, k_dim)).astype(dtype)
    w = jax.random.normal(ks[1], (k_dim, n_dim)).astype(dtype)
    mask = (jax.random.uniform(ks[2], (k_dim, n_dim)) < density).astype(jnp.float32)
    y = ops.masked_matmul(x, w, mask, bm=32, bn=64, bk=64)
    exp = ref.masked_matmul_ref(x, w, mask)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(exp, np.float32), atol=tol * k_dim ** 0.5,
                               rtol=tol)


def test_block_mask_occupancy():
    mask = jnp.zeros((256, 256)).at[0, 0].set(1.0).at[200, 200].set(1.0)
    bm = block_mask_from_mask(mask, 128, 128)
    np.testing.assert_array_equal(np.asarray(bm), [[1, 0], [0, 1]])
    assert ops.block_occupancy(mask, 128, 128) == pytest.approx(0.5)


def test_masked_matmul_skips_equal_dense():
    """Zero blocks contribute exactly nothing (skip path == masked math)."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 128))
    mask = jnp.zeros((256, 128)).at[:128, :].set(1.0)  # half the K blocks dead
    y = ops.masked_matmul(x, w, mask, bm=64, bn=128, bk=128)
    exp = x[:, :128] @ w[:128, :]
    np.testing.assert_allclose(np.asarray(y), np.asarray(exp), rtol=2e-5, atol=1e-4)
