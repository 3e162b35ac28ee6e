"""The VGG11-GN configuration against the program: the plain reference's
tree, FLOPs and logits against ``models/cnn.py``'s VGG11, and a small
VGG11-GN cell at the published widths (K=2, batch 8, 8 images a client)
added to a copy of the benchmark by files alone, judged ``correct`` true
through ``bench/run.py``, and false with the bfloat16 reference in the
program's place."""
from __future__ import annotations

import json
import shutil

import numpy as np
import pytest

from bench.tests._tiny import run_tiny, tiny_tree
from bench.tests.test_bench_flops import _config

NAME = "vgg11_gn"
TINY = "vgg11_gn_tiny"
CELL = f"{TINY}.train_tiny_short"
SEED = 2**31 + 29
# CIFAR VGG11: 0.153 GMAC a 32x32 image, 9.23M parameters with its GroupNorm
PUBLISHED_GMAC = 0.153
PARAMS = 9.23e6


def test_reference_tree_and_param_count():
    import jax

    from repro.fl import make_cnn_task

    cfg, ref = _config(NAME)
    tree = jax.eval_shape(lambda k: ref.init(k, cfg), jax.random.key(0))
    prog = jax.eval_shape(make_cnn_task(cfg["model"]).init_fn, jax.random.key(0))
    assert jax.tree.structure(tree) == jax.tree.structure(prog)
    assert ([(x.shape, x.dtype) for x in jax.tree.leaves(tree)]
            == [(x.shape, x.dtype) for x in jax.tree.leaves(prog)])
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert total == pytest.approx(PARAMS, rel=0.001)


def test_fwd_flops():
    from repro.models import cnn

    cfg, ref = _config(NAME)
    flops = ref.fwd_flops(cfg)
    assert flops == sum(cnn.vgg11_fwd_flops(cfg["num_classes"],
                                            cfg["image_hw"]).values())
    assert flops / 2e9 == pytest.approx(PUBLISHED_GMAC, rel=0.01)


def test_logits_match_program():
    """Seeded weights (GroupNorm's scale and bias moved off 1 and 0), batch
    4, both in float32 at HIGHEST.  Tolerance 1e-4 of the largest logit:
    the two sum the same products in different orders (the convolution
    algorithm, the variance formula), float32 rounding of about 1e-7 a
    term over sums of up to 4608 terms, through eight GroupNorms that
    rescale it; the bfloat16 reference misses by far more."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.models import cnn

    cfg, ref = _config(NAME)
    k_w, k_n, k_x = jax.random.split(jax.random.key(7), 3)
    params = ref.init(k_w, cfg)
    noise = jax.random.split(k_n, len(jax.tree.leaves(params)))
    params = jax.tree.unflatten(jax.tree.structure(params), [
        x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
        for x, k in zip(jax.tree.leaves(params), noise)])
    x = jax.random.normal(k_x, (4, cfg["image_hw"], cfg["image_hw"],
                                cfg["in_channels"]))
    with jax.default_matmul_precision("highest"):
        prog = np.asarray(jax.jit(cnn.vgg11_apply)(params, x))
    want = np.asarray(jax.jit(lambda p, x: ref.apply(p, x, cfg))(params, x))
    tol = 1e-4 * np.abs(want).max()
    np.testing.assert_allclose(prog, want, rtol=0, atol=tol)
    bf16 = np.asarray(jax.jit(lambda p, x: ref.apply(
        p, x, cfg, dtype=jnp.bfloat16, precision=lax.Precision.DEFAULT))(
            params, x), np.float32)
    assert np.abs(bf16 - want).max() > 10 * tol


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with a VGG11-GN cell at a CPU size: the
    configuration's own widths and reference, two clients, batch 8."""
    tmp = tmp_path_factory.mktemp("vgg")
    root = tiny_tree(tmp)
    cfg = json.loads((root / "configs" / f"{NAME}.json").read_text())
    cfg.update(name=TINY, n_clients=2, batch_size=8, degree=1)
    (root / "configs" / f"{TINY}.json").write_text(json.dumps(cfg))
    shutil.copy(root / "configs" / f"{NAME}.py", root / "configs" / f"{TINY}.py")
    traffic = json.loads((root / "traffic" / "short_local.json").read_text())
    traffic.update(samples_per_class=4, test_per_client=2)
    (root / "traffic" / "tiny_short.json").write_text(json.dumps(traffic))
    limits = dict.fromkeys(["leaf_change_gap_r1", "leaf_change_gap_r2",
                            "unmasked_leaf_gap_r1", "median_client_gap_r1"],
                           0.001)
    (root / "workloads" / f"{CELL}.json").write_text(json.dumps({
        "config": TINY, "traffic": "tiny_short", "chips": 1,
        "why": "a CPU-test cell", "limits": dict(limits, nnz_off_budget=0)}))
    spec = json.loads((tmp / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": CELL, "config": TINY,
                              "traffic": "tiny_short", "chips": 1,
                              "why": "a CPU-test cell"})
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def judged(root):
    """One run of the tiny cell through ``bench/run.py``, and the cell, the
    driver and the float32 reference's rounds that the run compared."""
    from bench.harness import registry

    cell = registry.load_cell(CELL, root)
    driver = registry.load_driver(cell)
    compared = {}
    compare = driver.compare

    def spy(cell, prog, ref, final):
        compared["ref"] = ref
        return compare(cell, prog, ref, final)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(driver, "compare", spy)
        mp.setattr(registry, "load_driver", lambda c: driver)
        res = run_tiny(root, CELL, seed=SEED)
    return res, cell, driver, compared["ref"]


def test_tiny_cell_correct(judged):
    res = judged[0]
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {"nnz_off_budget", "leaf_change_gap_r1",
                                  "leaf_change_gap_r2", "unmasked_leaf_gap_r1",
                                  "median_client_gap_r1"}
    assert res["attempted"] >= 1 and res["metrics"]["client_rounds_per_s"]["value"] > 0


def test_tiny_cell_bf16_control_fails(judged):
    """The bfloat16 reference's rounds judged in place of the program's,
    against the run's own float32 reference, as ``bench.tools.control_train``
    judges the chip cell's control."""
    import jax.numpy as jnp
    from jax import lax

    from bench.run import judge

    _, cell, driver, ref = judged
    seed = SEED % (2**31 - 1)
    p0, m0 = driver.start_state(cell, seed)
    bf16 = driver.reference_stats(
        cell, seed, driver.clients_for(cell, seed), p0, m0,
        cell.traffic["checked_rounds"], dtype=jnp.bfloat16,
        precision=lax.Precision.DEFAULT)
    checks = driver.compare(cell, bf16, ref, bf16[-1])
    assert judge(checks) is False, checks
