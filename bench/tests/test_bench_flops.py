"""The benchmark's copied FLOP functions against the published counts and
against the program's own per-layer functions; the plain references' trees
against the program's models."""
from __future__ import annotations

import json

import pytest

from bench.harness.registry import BENCH_DIR, load_module

# published multiply-accumulates per 32x32 image (CIFAR variants)
PUBLISHED_GMAC = {"resnet18_gn": 0.556}
PARAMS = {"resnet18_gn": 11.17e6}
PROGRAM_FLOPS = {"resnet18_gn": "resnet18_fwd_flops"}


def _config(name):
    cfg = json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())
    return cfg, load_module(BENCH_DIR / "configs" / f"{name}.py", f"ref_{name}")


@pytest.mark.parametrize("name", sorted(PUBLISHED_GMAC))
def test_fwd_flops(name):
    from repro.models import cnn

    cfg, ref = _config(name)
    flops = ref.fwd_flops(cfg)
    assert flops / 2e9 == pytest.approx(PUBLISHED_GMAC[name], rel=0.01)
    program = getattr(cnn, PROGRAM_FLOPS[name])(cfg["num_classes"], cfg["image_hw"])
    assert flops == sum(program.values())


@pytest.mark.parametrize("name", sorted(PARAMS))
def test_reference_param_count_and_tree(name):
    import jax
    import numpy as np

    from repro.fl import make_cnn_task

    cfg, ref = _config(name)
    tree = jax.eval_shape(lambda k: ref.init(k, cfg), jax.random.key(0))
    prog = jax.eval_shape(make_cnn_task(cfg["model"]).init_fn, jax.random.key(0))
    assert jax.tree.structure(tree) == jax.tree.structure(prog)
    assert [x.shape for x in jax.tree.leaves(tree)] == [x.shape for x in jax.tree.leaves(prog)]
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert total == pytest.approx(PARAMS[name], rel=0.002)
