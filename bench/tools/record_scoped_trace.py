"""Record a small profiler trace on the chip for the phase reduction's
test (``bench/harness/scopes.py``).

    python -m bench.tools.record_scoped_trace <out.xplane.pb>

Three annotated rounds of one jitted step whose parts sit under the
``mix`` (a matmul), ``local`` (a scan: a while loop) and ``evolve`` (an
argsort and a rank scatter, as the round's top-k) scopes, each round's
host work in
``scale.inputs`` (with a 3 ms sleep, so the chip idles under it),
``scale.dispatch`` and ``scale.comm`` (a blocking read) spans written by
``repro.obs`` into the trace, and a 2 ms sleep under no span.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.harness.xplane import find_trace
    from repro.obs import span

    def step(x):
        with jax.named_scope("mix"):
            x = x @ x.T * (1.0 / x.shape[0])
        with jax.named_scope("local"):
            x, _ = jax.lax.scan(lambda w, _: (jnp.tanh(w @ w) * 0.5, None),
                                x, None, length=8)
        with jax.named_scope("evolve"):
            # the round's top-k: an argsort and a rank scatter per row
            order = jnp.argsort(-jnp.abs(x), axis=1)
            rows = jnp.arange(x.shape[0])[:, None]
            ranks = jnp.zeros(x.shape, jnp.int32).at[rows, order].set(
                jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32),
                                 x.shape))
            x = jnp.where(ranks < x.shape[1] // 2, x, 0.0)
        return x

    f = jax.jit(step)
    host = np.ones((512, 512), np.float32)
    np.asarray(f(jnp.asarray(host)))
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    for i in range(3):
        with jax.profiler.StepTraceAnnotation("round", step_num=i):
            with span("scale.inputs", annotate=True):
                x = jnp.asarray(host * (i + 1))
                time.sleep(0.003)
            with span("scale.dispatch", annotate=True):
                y = f(x)
            with span("scale.comm", annotate=True):
                np.asarray(y)
            time.sleep(0.002)
    jax.profiler.stop_trace()
    shutil.copy(find_trace(d), out)
    shutil.rmtree(d)


if __name__ == "__main__":
    sys.path[:0] = [".", "src"]
    main(sys.argv[1])
