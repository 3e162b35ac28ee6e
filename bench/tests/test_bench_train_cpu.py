"""The train driver at a CPU size: a cell added by files alone produces the
contract's last line with ``correct`` true, and the bfloat16 control, read
in the program's place, comes out not correct."""
from __future__ import annotations

import pytest

from bench.tests._tiny import run_tiny, tiny_tree

CELL = "tiny_cnn.train_tiny"
SEED = 2**31 + 17


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_tree(tmp_path_factory.mktemp("tiny"))


def test_result_line_shape(root):
    res = run_tiny(root, CELL)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"client_rounds_per_s", "setup_s"}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


def test_bf16_control_fails(root, monkeypatch):
    """The bfloat16 reference put in the program's place: the run drives
    the engine as always, but what it compares is the control's rounds."""
    import jax.numpy as jnp
    from jax import lax

    from bench.harness import registry

    cell = registry.load_cell(CELL, root)
    driver = registry.load_driver(cell)

    def control_rounds(cell, engine, rounds, params0, t_start):
        driver_rounds(cell, engine, rounds, params0, t_start)
        seed = SEED % (2**31 - 1)
        p0, m0 = driver.start_state(cell, seed)
        return driver.reference_stats(
            cell, seed, driver.clients_for(cell, seed), p0, m0,
            cell.traffic["checked_rounds"], dtype=jnp.bfloat16,
            precision=lax.Precision.DEFAULT)

    driver_rounds = driver.checked_rounds
    monkeypatch.setattr(driver, "checked_rounds", control_rounds)
    monkeypatch.setattr(registry, "load_driver", lambda c: driver)
    res = run_tiny(root, CELL, seed=SEED)
    assert res["correct"] is False, res["checks"]
