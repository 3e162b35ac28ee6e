"""The main path compiles for a TPU v5e chip, at real widths.

Nothing runs: the TPU compiler, which is installed beside JAX, compiles for
a chip that is described, not attached.  That catches what interpret mode
cannot (tile alignment, VMEM limits, unsupported lowerings).  The topology
is described inside a fixture, never at import: only one process may load
the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels.masked_matmul as mm_mod
import repro.models.cnn as cnn_mod
from repro.fl.base import stacked_local_phase
from repro.kernels import ops
from repro.optim import SGDConfig
from test_groupnorm import split_groupnorm

pytestmark = pytest.mark.tier1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_mode(monkeypatch):
    """Steer the kernels to compiled (Mosaic) mode, as on a TPU backend;
    drop traces made in interpret mode before and after."""
    monkeypatch.setattr(mm_mod, "_interpret", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# the serve launcher's MLP (d_in 64, widths 128-128, n_out 32) over its
# default pool of 16 slots, 4 rows per request
@pytest.mark.parametrize("k_dim,n_dim", [(64, 128), (128, 128), (128, 32)])
def test_batched_masked_matmul_compiles_at_serve_pool_shapes(
        one_chip, compiled_mode, k_dim, n_dim):
    u, rows = 16, 4
    hlo = _compile_text(ops.batched_masked_matmul, one_chip,
                        (u, rows, k_dim), (u, k_dim, n_dim), (u, k_dim, n_dim))
    assert "tpu_custom_call" in hlo


def test_masked_matmul_compiles_at_resnet18_im2col_shape(one_chip,
                                                         compiled_mode):
    # last ResNet18 stage: 3x3x512 -> 512 conv at 4x4 over a batch of 32
    m, k, n = 32 * 4 * 4, 3 * 3 * 512, 512
    hlo = _compile_text(ops.masked_matmul, one_chip, (m, k), (k, n), (k, n))
    assert "tpu_custom_call" in hlo


def _local_phase_bytes(one_chip, k=2, steps=1, batch=8, hw=32):
    """Cost-model bytes of ResNet18-GN's stacked local phase, compiled."""
    shapes = jax.eval_shape(
        lambda: cnn_mod.init_resnet18(jax.random.PRNGKey(0), 10))

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda s: spec((k,) + s.shape), shapes)
    args = (params, params, spec((k, steps, batch, hw, hw, 3)),
            spec((k, steps, batch), jnp.int32), spec((k, steps), jnp.bool_),
            spec(()))
    fn = jax.jit(lambda *a: stacked_local_phase(
        cnn_mod.resnet18_apply, SGDConfig(), *a))
    return fn.lower(*args).compile().cost_analysis()["bytes accessed"]


def test_groupnorm_keeps_the_local_phase_out_of_layout_copies(one_chip,
                                                              monkeypatch):
    # the group split of the activation relayouts it around every norm
    new = _local_phase_bytes(one_chip)
    monkeypatch.setattr(cnn_mod, "groupnorm", split_groupnorm)
    old = _local_phase_bytes(one_chip)
    assert new <= 0.6 * old, (new, old)
