"""chip_smoke.py rehearsed on the CPU at tiny sizes: its train, serve and
sharded-vs-single phases run their checks through the same entry points,
its main refuses a machine without a TPU, and the compile cache lands where
``repro.launch.compile_cache`` says."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

pytestmark = pytest.mark.tier1

TINY_TRAIN = [
    "simulate", "--scale", "--strategy", "dispfl", "--model", "smallcnn",
    "--hw", "8", "--width", "4", "--clients", "4", "--degree", "2",
    "--density", "0.5", "--partition", "pathological",
    "--samples-per-class", "20", "--batch-size", "8", "--local-epochs", "1",
    "--rounds", "2", "--eval-every", "1",
]


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def test_train_phase_checks_every_round():
    checks = chip_smoke.train_phase(TINY_TRAIN)
    assert len(checks.accs) == 2
    assert all(0.0 <= a <= 1.0 for a in checks.accs)
    assert checks.round1_masks and all(m.dtype == bool
                                       for m in checks.round1_masks)


def test_serve_phase_matches_float64_oracle():
    summary = chip_smoke.serve_phase([
        "--model", "mlp", "--backend", "pallas", "--users", "12",
        "--cache-size", "4", "--max-batch", "4", "--requests", "16"])
    assert summary["requests"] == 16
    assert summary["store_misses"] > 0


def test_oracle_bound_catches_a_wrong_output():
    import numpy as np

    rng = np.random.default_rng(0)
    ws = [rng.standard_normal((64, 128)) / 8, rng.standard_normal((128, 32))]
    x = rng.standard_normal((4, 64))
    want, sigma = chip_smoke._oracle(ws, x)
    bound = chip_smoke.SERVE_SIGMAS * sigma

    def bf16(a):  # round to nearest bf16, kept in float32
        b = np.asarray(a, np.float32).view(np.uint32).astype(np.uint64)
        b = (b + 0x7FFF + ((b >> 16) & 1)) >> 16 << 16
        return b.astype(np.uint32).view(np.float32)

    # an f32 forward whose dots round both operands to bf16 stays inside
    h = np.maximum(bf16(x) @ bf16(ws[0]), 0)
    served = bf16(h) @ bf16(ws[1])
    assert np.all(np.abs(served - want) <= bound)
    assert np.max(np.abs(served - want)) > 1e-3 * np.max(np.abs(want))
    # a 10% error on the largest output is far outside
    bad = want.copy()
    bad.flat[np.argmax(np.abs(want))] *= 1.1
    assert not np.all(np.abs(bad - want) <= bound)


def test_mesh_phase_on_four_host_devices():
    code = ("import chip_smoke; "
            f"chip_smoke.mesh_phase({TINY_TRAIN!r})")
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "[mesh] mask coordinates differing after round 1" in r.stdout


def test_main_refuses_without_a_tpu():
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       capture_output=True, text=True, timeout=300,
                       env=_env())
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


_CACHE_PROBE = """
import json, jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
d = enable_compile_cache()
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
print(json.dumps({"dir": d, "config": jax.config.jax_compilation_cache_dir}))
"""


def test_compile_cache_follows_the_environment(tmp_path):
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], capture_output=True, text=True,
        timeout=300,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0"))
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["dir"] == out["config"] == str(tmp_path)
    assert os.listdir(tmp_path)


def test_compile_cache_defaults_to_the_repo():
    code = ("from repro.launch.compile_cache import enable_compile_cache; "
            "print(enable_compile_cache())")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=_env())
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == os.path.join(REPO,
                                                             ".jax_cache")
