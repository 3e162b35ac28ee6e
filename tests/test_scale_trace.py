"""ScaleEngine's own instrumentation: the round program's ops named by
phase scope, the round's host phases as spans in the profiler's trace and
in the ``repro.obs`` tracer, the input-byte and host-sync counters, and
``repro.obs`` still importing without jax."""
from __future__ import annotations

import glob
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.data import build_federated_image_task
from repro.fl import FLConfig, make_cnn_task, make_strategy
from repro.obs import Tracer, set_tracer
from repro.scale import ScaleEngine

pytestmark = pytest.mark.tier1

PHASES = ("mix", "local", "evolve")
HOST_PHASES = ["scale.inputs", "scale.dispatch", "scale.comm"]
# the innermost name of an op_name component: "vmap(transpose(evolve))"
_INNER = re.compile(r"^(?:[^()]*\()*([^()]*)\)*$")


def _scopes(op_name: str, names) -> set:
    return {_INNER.match(p).group(1) for p in op_name.split("/")} & set(names)


@pytest.fixture(scope="module")
def setup():
    clients, _ = build_federated_image_task(
        0, n_clients=4, partition="pathological", classes_per_client=2,
        n_train_per_class=12, n_test_per_client=4, hw=8, noise=0.7)
    task = make_cnn_task("smallcnn", 10, 8, width=4)
    # eval only after the last round, so the two traced rounds run none
    cfg = FLConfig(n_clients=4, rounds=4, local_epochs=1, batch_size=8,
                   degree=2, eval_every=4)
    return task, clients, cfg


def _engine(setup):
    task, clients, cfg = setup
    return ScaleEngine(make_strategy("dispfl"), task, clients, cfg)


def _round_inputs(eng, t=0):
    ctx = eng._make_ctx(t)
    bi, by, live = eng._batch_schedule(ctx)
    ev_i, ev_y = eng._evolve_batches(ctx)
    return (eng._device_images(), jnp.asarray(eng.adapter.mix_matrix(ctx)),
            bi, by, live, ev_i, ev_y, jnp.float32(ctx.lr),
            eng.adapter.evolve_counts(ctx))


def test_round_program_ops_carry_one_phase_scope(setup):
    eng = _engine(setup)
    hlo = eng._step_fn().lower(eng.state, *_round_inputs(eng)).compile().as_text()
    names = [n for n in re.findall(r'op_name="([^"]*)"', hlo)
             if n.startswith("jit(round_step)/")]
    assert names
    seen = set()
    for n in names:
        found = _scopes(n, PHASES)
        assert len(found) == 1, n
        seen |= found
    assert seen == set(PHASES)


def test_eval_program_ops_carry_the_eval_scope(setup):
    task, clients, _ = setup
    from repro.fl.base import stack_eval_arrays

    eng = _engine(setup)
    hlo = task._acc_stacked.lower(eng.state["params"],
                                  *stack_eval_arrays(clients)).compile().as_text()
    names = [n for n in re.findall(r'op_name="([^"]*)"', hlo)
             if n.startswith("jit(")]
    assert names and all(_scopes(n, ("eval",) + PHASES) == {"eval"}
                         for n in names)


def _host_line_spans(xplane_path: str, probe: str) -> list[str]:
    """The ``scale.*`` events, in time order, on the host line that also
    holds the ``probe`` annotation (the calling thread's)."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(xplane_path).planes:
        for line in plane.lines:
            events = sorted(line.events, key=lambda e: e.start_ns)
            if any(e.name == probe for e in events):
                return [e.name for e in events if e.name.startswith("scale.")]
    raise AssertionError(f"no host line holds {probe!r}")


def test_host_phases_reach_the_profiler_and_the_tracer(setup, tmp_path):
    eng = _engine(setup)
    rounds = eng.rounds()
    next(rounds)                                   # compiles outside the trace
    tracer = Tracer()
    old = set_tracer(tracer)
    tracer.enable(mode="full")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            with jax.profiler.TraceAnnotation("probe"):
                next(rounds)
                next(rounds)
    finally:
        set_tracer(old)
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert _host_line_spans(path, "probe") == HOST_PHASES * 2
    spans = tracer.spans(track="engine")
    assert [s.name for s in spans] == HOST_PHASES * 2
    assert [s.attrs["round"] for s in spans] == [1] * 3 + [2] * 3
    dispatch = [s for s in spans if s.name == "scale.dispatch"]
    assert all(s.attrs["compiles"] == 0 for s in dispatch)


def test_input_bytes_and_host_syncs_count_the_round(setup, monkeypatch):
    eng = _engine(setup)
    seen = []
    real = eng._step_fn()

    def recording_step(state, images, *inputs):
        seen.append(sum(x.nbytes for x in jax.tree.leaves(inputs)))
        return real(state, images, *inputs)

    monkeypatch.setattr(eng, "_step_fn", lambda: recording_step)
    images = eng._device_images().nbytes           # copied once
    rounds = eng.rounds()
    for r in range(1, 3):
        next(rounds)
        snap = eng.scale_obs.snapshot()
        assert snap["input_bytes"] == images + sum(seen)
        assert snap["host_syncs"] == r                # every leaf in one read
    # a round ships the indices of its images, not the images: under a
    # quarter of the bytes of the float32 batches they pick
    bi = _round_inputs(eng)[2]
    assert 0 < seen[1] < bi.size * 4 * math.prod(eng._img_shape) / 4


def test_topk_counters_count_the_evolve_selections(setup):
    from repro.scale.stacked import topk_row_passes
    from repro.utils.tree import tree_leaves_with_path

    eng = _engine(setup)
    kdim = len(eng.clients)
    counts = eng.adapter.evolve_counts(eng._make_ctx(0))
    sizes = [w.size // kdim
             for path, w in tree_leaves_with_path(eng.state["params"])
             if path in counts]
    assert len(sizes) == len(counts) > 0
    rounds = eng.rounds()
    for r in range(1, 3):
        next(rounds)
        snap = eng.scale_obs.snapshot()
        # a keep and a grow selection per client and counted leaf
        assert snap["topk_selects"] == r * 2 * kdim * len(counts)
        assert snap["topk_passes"] == r * 2 * kdim * sum(
            topk_row_passes(n) for n in sizes)
        assert snap["host_syncs"] == r


def test_topk_counters_stay_zero_without_an_evolve(setup):
    task, clients, cfg = setup
    eng = ScaleEngine(make_strategy("dpsgd"), task, clients, cfg)
    next(eng.rounds())
    snap = eng.scale_obs.snapshot()
    assert snap["step_calls"] == 1
    assert snap["topk_selects"] == snap["topk_passes"] == 0


def test_disabled_tracer_keeps_the_null_span_and_annotation_still_enters():
    from repro.obs import get_tracer, span

    assert not get_tracer().enabled
    assert span("x") is span("y")                  # the shared no-op
    with span("scale.probe", track="engine", annotate=True, n=1) as sp:
        sp.attrs["k"] = 2                          # mutable, as enabled


def test_import_obs_does_not_import_jax():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(__file__)), "src"),
         os.environ.get("PYTHONPATH", "")]))
    code = ("import sys, repro.obs; "
            "from repro.obs import span; "
            "span('a', annotate=False).__enter__(); "
            "assert 'jax' not in sys.modules, 'jax imported'")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
