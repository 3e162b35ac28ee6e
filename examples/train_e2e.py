"""End-to-end driver: DisPFL-train a transformer LM for a few hundred steps
on synthetic non-IID corpora (one Markov domain per client).

Default is CPU-sized (~6M params/client, 200 steps).  For the ~100M-model
run on a real machine:

    PYTHONPATH=src python examples/train_e2e.py --d-model 768 --layers 12 \
        --steps 300 --clients 4

This wraps ``repro.launch.train lm``: the stacked gossip of
``repro.core.gossip.masked_gossip_stacked``, which the mesh-scale train step
also uses, then masked SGD and mask evolution.
"""
import argparse
import subprocess
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="qwen3-8b")
ap.add_argument("--clients", default="4")
ap.add_argument("--steps", default="200")
ap.add_argument("--rounds", default="10")
ap.add_argument("--d-model", default="256", dest="d_model")
ap.add_argument("--layers", default="2")
ap.add_argument("--seq", default="128")
args = ap.parse_args()

subprocess.run(
    [sys.executable, "-m", "repro.launch.train", "lm",
     "--arch", args.arch, "--clients", args.clients, "--steps", args.steps,
     "--rounds", args.rounds, "--d-model", args.d_model,
     "--layers", args.layers, "--seq", args.seq],
    check=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin"},
)
