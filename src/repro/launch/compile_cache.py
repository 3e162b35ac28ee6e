"""Where JAX's persistent compilation cache lives.

Called from the entry points (``launch/train.py``, ``launch/serve.py``,
``chip_smoke.py``), never at import.  The cache directory is part of each
entry's key, so it is a fixed path: ``<repo root>/.jax_cache``, unless
``JAX_COMPILATION_CACHE_DIR`` is set, in which case JAX reads that
variable itself and nothing is set here.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory and return
    that directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
