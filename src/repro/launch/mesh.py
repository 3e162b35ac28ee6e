"""Production mesh construction (functions only — importing this module
never touches jax device state; see MULTI-POD DRY-RUN step 1)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 256 chips as (data=16, model=16).  Multi-pod: 2 pods of
    256 as (pod=2, data=16, model=16); the 'pod' axis carries pod-level
    DisPFL clients (DESIGN.md §3 cross-pod gossip)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pods: int = 0) -> Mesh:
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count
    >= data*model*max(pods,1) set before jax initializes)."""
    if pods:
        return _make_mesh((pods, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


def client_capacity(mesh: Mesh) -> int:
    """Max stacked clients the mesh hosts (product of client axes)."""
    cap = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        cap *= mesh.shape["pod"]
    return cap
