"""Personalized sparse serving: the repro.serve plane end to end — packed
delta store, LRU unpack cache, micro-batched launches — first over the
matmul-pipeline MLP (ref backend), then over an assigned smoke arch
(reduced config on CPU, vmap backend).

    PYTHONPATH=src python examples/serve_personalized.py [arch]
"""
import os
import subprocess
import sys

ARCH = sys.argv[1] if len(sys.argv) > 1 else "gemma3-1b"

# inherit the caller's environment (jax flags, tmpdirs, PATH) and only
# overlay what the child actually needs; the children run one at a time, so
# each can hold the accelerator in turn
ENV = {**os.environ, "PYTHONPATH": "src"}

subprocess.run(
    [sys.executable, "-m", "repro.launch.serve", "--model", "mlp",
     "--backend", "ref", "--users", "32", "--cache-size", "8",
     "--max-batch", "8", "--requests", "128", "--density", "0.3"],
    check=True, env=ENV,
)

subprocess.run(
    [sys.executable, "-m", "repro.launch.serve", "--model", ARCH,
     "--backend", "vmap", "--users", "4", "--cache-size", "2",
     "--max-batch", "2", "--requests", "8", "--rows", "1"],
    check=True, env=ENV,
)
