"""A run with the timed train path broken underneath comes out not
correct: a round step that returns its state unchanged, and half of each
batch left out (the mean taken over the rest)."""
from __future__ import annotations

import pytest

from bench.tests._tiny import run_tiny, tiny_tree

CELL = "tiny_cnn.train_tiny"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_tree(tmp_path_factory.mktemp("tiny"))


def _unchanged(monkeypatch):
    from repro.scale import engine

    monkeypatch.setattr(engine.ScaleEngine, "_step_fn",
                        lambda self: (lambda state, *args: state))


def _half_batch(monkeypatch):
    from repro.scale import engine

    sched, evolve = engine.ScaleEngine._batch_schedule, engine.ScaleEngine._evolve_batches

    def half_sched(self, ctx):
        bx, by, live = sched(self, ctx)
        h = bx.shape[2] // 2
        return bx[:, :, :h], by[:, :, :h], live

    def half_evolve(self, ctx):
        x, y = evolve(self, ctx)
        return x[:, : x.shape[1] // 2], y[:, : y.shape[1] // 2]

    monkeypatch.setattr(engine.ScaleEngine, "_batch_schedule", half_sched)
    monkeypatch.setattr(engine.ScaleEngine, "_evolve_batches", half_evolve)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_fault_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    res = run_tiny(root, CELL)
    assert res["correct"] is False, res["checks"]
