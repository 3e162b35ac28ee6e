"""``bench/run.py`` refuses a machine without a TPU: an error, a non-zero
exit and no result line."""
from __future__ import annotations

import os
import subprocess
import sys

from bench.harness.registry import BENCH_DIR


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(BENCH_DIR / "run.py"), *args],
                          capture_output=True, text=True, env=env, timeout=300)


def test_refuses_cpu():
    p = _run("--workload", "resnet18_gn.train_long_local", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_unknown_workload():
    p = _run("--workload", "nope", "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and p.stdout == ""
    assert "unknown workload" in p.stderr
