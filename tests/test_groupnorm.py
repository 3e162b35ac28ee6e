"""GroupNorm folds its groups on the (n, c) channel sums, not the activation.

The oracle below is the textbook formulation: split the channels of the
whole activation into (groups, c / groups) and take the two-pass mean and
variance over (h, w, c / groups).  ``repro.models.common.groupnorm`` must
agree with it, forward and grads, and its jaxpr must never split the
activation (on the TPU that split puts 2-16 channels in the 128-wide lanes
and relayouts the activation around every norm).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.common import groupnorm, groupnorm_init

pytestmark = pytest.mark.tier1


def split_groupnorm(params, x, groups=32, eps=1e-5):
    """The oracle: GroupNorm on the (n, h, w, g, c / g) view of ``x``."""
    n, h, w, c = x.shape
    g = min(groups, c)
    while c % g != 0:
        g -= 1
    xf = x.astype(jnp.float32).reshape(n, h, w, g, c // g)
    mean = xf.mean(axis=(1, 2, 4), keepdims=True)
    var = xf.var(axis=(1, 2, 4), keepdims=True)
    xf = (xf - mean) * jax.lax.rsqrt(var + eps)
    xf = xf.reshape(n, h, w, c)
    return (xf * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(x.dtype)


def _case(c, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed + c), 4)
    # an offset and a spread, so the centring matters
    x = 3.0 * jax.random.normal(k[0], (4, 8, 8, c)) + 1.5
    params = {"scale": 1.0 + 0.1 * jax.random.normal(k[1], (c,)),
              "bias": 0.1 * jax.random.normal(k[2], (c,))}
    cot = jax.random.normal(k[3], x.shape)
    return params, x, cot


# 64, 128 and 512 are ResNet18's and VGG11's widths; 48 has 24 groups
# (32 does not divide it), 10 has one channel a group
@pytest.mark.parametrize("c", [64, 128, 512, 48, 10])
def test_groupnorm_matches_the_split_oracle(c):
    params, x, cot = _case(c)
    np.testing.assert_allclose(groupnorm(params, x),
                               split_groupnorm(params, x),
                               rtol=1e-5, atol=1e-5)

    def vjp_of(fn):
        return jax.grad(lambda p, xx: jnp.sum(fn(p, xx) * cot),
                        argnums=(0, 1))(params, x)

    (gp, gx), (op, ox) = vjp_of(groupnorm), vjp_of(split_groupnorm)
    for got, want in [(gx, ox), (gp["scale"], op["scale"]),
                      (gp["bias"], op["bias"])]:
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-5 * float(jnp.max(jnp.abs(want))))


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else [p]):
                sub = getattr(sub, "jaxpr", sub)  # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_groupnorm_grad_never_splits_the_activation():
    shape = (8, 16, 16, 64)
    params = groupnorm_init(64, jnp.float32)
    closed = jax.make_jaxpr(jax.grad(
        lambda p, x: jnp.sum(jnp.square(groupnorm(p, x))),
        argnums=(0, 1)))(params, jnp.ones(shape))
    size = int(np.prod(shape))
    eqns = list(_eqns(closed.jaxpr))
    assert any(e.primitive.name == "reduce_sum" for e in eqns)
    for eqn in eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            assert getattr(aval, "ndim", 0) != 5, (eqn, aval)
        if eqn.primitive.name == "reshape":
            assert int(np.prod(eqn.invars[0].aval.shape)) < size, eqn

    # the oracle does split it: the guard can see the split
    split = jax.make_jaxpr(jax.grad(
        lambda x: jnp.sum(jnp.square(split_groupnorm(params, x)))))(
            jnp.ones(shape))
    assert any(e.primitive.name == "reshape"
               and int(np.prod(e.invars[0].aval.shape)) == size
               for e in _eqns(split.jaxpr))
