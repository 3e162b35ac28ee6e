"""Record a small profiler trace on the chip for the reduction's test.

    python -m bench.tools.record_trace <out.xplane.pb>

Three annotated steps of a small jitted computation with a host sleep
between them, so the trace holds device ops, idle gaps and annotations.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from bench.harness.xplane import find_trace

    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    for i in range(3):
        with jax.profiler.StepTraceAnnotation("round", step_num=i):
            y = x
            for _ in range(4):
                y = f(y)
            y.block_until_ready()
            time.sleep(0.005)
    jax.profiler.stop_trace()
    shutil.copy(find_trace(d), out)
    shutil.rmtree(d)


if __name__ == "__main__":
    sys.path[:0] = [".", "src"]
    main(sys.argv[1])
