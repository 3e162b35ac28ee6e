"""A profiler trace of the measured window, reduced and then deleted."""
from __future__ import annotations

import shutil
import tempfile

from bench.harness.xplane import find_trace, reduce_trace


class Profile:
    """``start``/``stop`` around the window; ``reduce`` reads the trace
    (written under ``TMPDIR``) and removes it.  The Python tracer is off,
    so the host side holds the runtime's and the harness's annotations."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def reduce(self, annotations: set[str]) -> dict:
        try:
            return reduce_trace(find_trace(self.dir), annotations)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
