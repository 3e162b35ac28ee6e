"""Intersection-weighted gossip: hand cases, properties, stacked-vs-single
consistency, and the stacked folds against the per-client reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hermetic env: fixed-seed sampling fallback
    from _hypothesis_fallback import given, settings, st

from repro.core.gossip import (
    gossip_average_one,
    masked_gossip_stacked,
    plain_mix_stacked,
)
from repro.core.topology import fully_connected, mixing_matrix

pytestmark = pytest.mark.tier1


def test_hand_example():
    # two clients, one coordinate held by both, one held by self only
    w_own = {"w": jnp.array([2.0, 4.0])}
    m_own = {"w": jnp.array([1.0, 1.0])}
    w_nb = {"w": jnp.array([6.0, 0.0])}
    m_nb = {"w": jnp.array([1.0, 0.0])}
    out = gossip_average_one(w_own, m_own, [w_nb], [m_nb])
    np.testing.assert_allclose(out["w"], [4.0, 4.0])  # (2+6)/2, 4/1


def test_respects_own_mask():
    w_own = {"w": jnp.array([0.0, 0.0])}
    m_own = {"w": jnp.array([0.0, 1.0])}
    w_nb = {"w": jnp.array([5.0, 5.0])}
    m_nb = {"w": jnp.array([1.0, 1.0])}
    out = gossip_average_one(w_own, m_own, [w_nb], [m_nb])
    np.testing.assert_allclose(out["w"], [0.0, 2.5])


@settings(max_examples=15, deadline=None)
@given(k=st.integers(2, 6), n=st.integers(1, 64), seed=st.integers(0, 99))
def test_all_dense_masks_reduce_to_plain_average(k, n, seed):
    key = jax.random.PRNGKey(seed)
    w = jax.random.normal(key, (k, n))
    m = jnp.ones((k, n))
    a = jnp.asarray(fully_connected(k))
    out = masked_gossip_stacked({"w": w}, {"w": m}, a)
    expected = plain_mix_stacked({"w": w},
                                 jnp.asarray(mixing_matrix(np.array(a))))
    np.testing.assert_allclose(out["w"], expected["w"], rtol=1e-4, atol=1e-6)


def test_stacked_matches_single():
    key = jax.random.PRNGKey(0)
    k, n = 4, 37
    w = jax.random.normal(key, (k, n))
    m = (jax.random.uniform(jax.random.PRNGKey(1), (k, n)) < 0.6).astype(jnp.float32)
    w = w * m
    a = np.eye(k)
    a[0, 2] = a[0, 3] = 1.0  # client 0 hears 2 and 3
    out = masked_gossip_stacked({"w": w}, {"w": m}, jnp.asarray(a))
    single = gossip_average_one(
        {"w": w[0]}, {"w": m[0]},
        [{"w": w[2]}, {"w": w[3]}], [{"w": m[2]}, {"w": m[3]}])
    np.testing.assert_allclose(out["w"][0], single["w"], rtol=1e-5)


def test_density_preserved():
    key = jax.random.PRNGKey(0)
    k, n = 5, 200
    m = (jax.random.uniform(key, (k, n)) < 0.5).astype(jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * m
    a = jnp.asarray(fully_connected(k))
    out = masked_gossip_stacked({"w": w}, {"w": m}, a)
    assert bool(jnp.all((out["w"] != 0) <= (m > 0)))


@pytest.mark.parametrize("j", [1, 3, 7])
@pytest.mark.parametrize("n", [128, 1000, 5000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_gossip_stacked_matches_reference_fold(j, n, dtype):
    """K=8 clients, each hearing the j that follow it: ``"ordered"`` is
    bit-exact against the per-client fold in float32 and ``"einsum"`` within
    1e-5; in bfloat16 both stay within 2e-2 of the float32 reference."""
    k = 8
    ks = jax.random.split(jax.random.PRNGKey(j * 100 + n), 2)
    m = (jax.random.uniform(ks[0], (k, n)) < 0.5).astype(jnp.float32)
    w = jax.random.normal(ks[1], (k, n)) * m
    a = np.eye(k, dtype=np.float32)
    for i in range(k):
        for d in range(1, j + 1):
            a[i, (i + d) % k] = 1.0
    ref = []
    for i in range(k):
        nbrs = [q for q in range(k) if a[i, q] > 0 and q != i]
        ref.append(gossip_average_one({"w": w[i]}, {"w": m[i]},
                                      [{"w": w[q]} for q in nbrs],
                                      [{"w": m[q]} for q in nbrs])["w"])
    ref = np.stack(ref)
    adj = jnp.asarray(a)
    wd, md = w.astype(dtype), m.astype(dtype)
    for reduction in ("ordered", "einsum"):
        got = jax.jit(lambda p, q: masked_gossip_stacked(
            {"w": p}, {"w": q}, adj, reduction))(wd, md)["w"]
        assert got.dtype == dtype
        if dtype == jnp.bfloat16:
            np.testing.assert_allclose(np.asarray(got, np.float32), ref,
                                       atol=2e-2, rtol=2e-2)
        elif reduction == "ordered":
            np.testing.assert_array_equal(np.asarray(got), ref)
        else:
            np.testing.assert_allclose(np.asarray(got), ref, atol=1e-5)
