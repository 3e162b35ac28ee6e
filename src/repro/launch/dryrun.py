import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=" + \
    os.environ.get("REPRO_DRYRUN_DEVICES", "512")

# NOTE: the two lines above MUST precede every other import (jax locks the
# device count at first init) — hence no `from __future__ import` here.

DOC = """Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

For each combination this script
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. lowers the DisPFL ``train_step`` (train shapes) or ``serve_step``
     (prefill/decode shapes) with ShapeDtypeStruct inputs under the sharding
     rules of sharding/rules.py,
  3. compiles, printing ``memory_analysis()`` and ``cost_analysis()``,
  4. parses collective bytes out of the partitioned HLO,
  5. writes a JSON artifact consumed by benchmarks/roofline.py and
     EXPERIMENTS.md.

Failures here (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the system — the run aborts loudly.
"""

import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCHS, INPUT_SHAPES
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import build_report, total_params
from repro.launch.steps import lower_for, plan_for
from repro.models.registry import bind
from repro.utils import hlo as hlo_mod

# long_500k needs sub-quadratic attention / recurrent decode; only these
# archs run it (DESIGN.md §Arch-applicability) — pure full-attention archs
# skip with a recorded reason.
LONG_CONTEXT_OK = {"gemma3-1b", "mamba2-1.3b", "jamba-1.5-large-398b"}

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _mesh_name(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def should_skip(arch_name: str, shape_name: str) -> str | None:
    if shape_name == "long_500k" and arch_name not in LONG_CONTEXT_OK:
        return ("full-attention arch: 500k decode KV memory/latency is not "
                "servable without sliding-window/SSM; skipped per assignment")
    return None


def analytic_state_bytes_per_device(plan, lowered_args_bytes: float) -> float:
    del plan
    return lowered_args_bytes


def run_one(arch_name: str, shape_name: str, multi_pod: bool,
            gossip: str = "einsum", out_dir: str = OUT_DIR,
            verbose: bool = True, smoke: bool = False,
            unroll: bool = False, remat: str = "full") -> dict:
    arch = ARCHS[arch_name]
    shape = INPUT_SHAPES[shape_name]
    if smoke:
        # reduced configs + tiny shapes on a small test mesh: exercises the
        # whole lowering pipeline in seconds (used by the integration test)
        import dataclasses as _dc
        from repro.configs import SMOKE_ARCHS
        from repro.launch.mesh import make_test_mesh
        arch = SMOKE_ARCHS[arch_name]
        shape = _dc.replace(shape, seq_len=max(64, shape.seq_len // 4096),
                            global_batch=min(shape.global_batch, 8))
    mesh_name = ("test" if smoke else "") + _mesh_name(multi_pod)
    tag = f"{arch_name}__{shape_name}__{mesh_name}" + (
        f"__{gossip}" if gossip != "einsum" else "") + (
        f"__remat_{remat}" if remat != "full" else "")
    skip = should_skip(arch_name, shape_name)
    record: dict = {"arch": arch_name, "shape": shape_name, "mesh": mesh_name,
                    "gossip": gossip, "tag": tag, "unroll": unroll}
    if skip and not smoke:
        record.update(status="skipped", reason=skip)
        _write(out_dir, tag, record)
        if verbose:
            print(f"[dryrun] SKIP {tag}: {skip}")
        return record

    t0 = time.time()
    if smoke:
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(2, 2, pods=2 if multi_pod else 0)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    plan, lowered = lower_for(arch, shape, mesh, gossip=gossip, unroll=unroll,
                              remat=(remat != "none"),
                              remat_policy=(remat if remat != "none" else "full"))
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    # ---- memory ----------------------------------------------------------
    mem: dict = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                mem[attr] = int(v)
        print("[memory_analysis]", mem if mem else ma)
    except Exception as e:  # CPU backend may not implement it fully
        mem["error"] = str(e)
        print("[memory_analysis] unavailable:", e)

    # ---- cost ------------------------------------------------------------
    cost = {k: float(v) for k, v in compiled.cost_analysis().items()
            if isinstance(v, (int, float)) and k in
            ("flops", "bytes accessed", "optimal_seconds")}
    print("[cost_analysis]", {k: f"{v:.3e}" for k, v in cost.items()})

    # ---- collectives -----------------------------------------------------
    hlo_text = compiled.as_text()
    coll = hlo_mod.collective_bytes(hlo_text)

    report = build_report(arch, shape, mesh_name, chips, cost,
                          coll.total_bytes, density=1.0)
    record.update(
        status="ok",
        chips=chips,
        n_clients=plan.n_clients,
        per_client_batch=plan.per_client_batch,
        fsdp2d=plan.fsdp2d,
        seq_data=plan.seq_data,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        memory=mem,
        cost=cost,
        collectives=coll.row(),
        coll_bytes_per_device=coll.total_bytes,
        total_params=total_params(arch),
        roofline=report.row(),
        hlo_ops=hlo_mod.op_histogram(hlo_text, top=12),
    )
    _write(out_dir, tag, record)
    if verbose:
        print(f"[dryrun] OK {tag}: clients={plan.n_clients} "
              f"compile={t_compile:.0f}s bottleneck={report.bottleneck} "
              f"terms(ms)=({report.compute_s*1e3:.2f}, {report.memory_s*1e3:.2f}, "
              f"{report.collective_s*1e3:.2f})")
    return record


def _write(out_dir: str, tag: str, record: dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(record, f, indent=2, default=str)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="input shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--gossip", default="einsum",
                    choices=["einsum", "einsum_bf16", "einsum_noopt", "ppermute", "none"])
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--remat", default="full", choices=["full", "dots", "none"])
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer scans for trip-count-faithful "
                         "cost_analysis (roofline pass)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced archs + tiny shapes on a 2x2(x2) test mesh "
                         "(set REPRO_DRYRUN_DEVICES=8 first)")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for mp in meshes:
        for a in archs:
            for s in shapes:
                tag = f"{a}__{s}__{_mesh_name(mp)}" + (
                    f"__{args.gossip}" if args.gossip != "einsum" else "") + (
                    f"__remat_{args.remat}" if args.remat != "full" else "")
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("status") in ("ok", "skipped"):
                            print(f"[dryrun] cached {tag}")
                            continue
                try:
                    run_one(a, s, mp, gossip=args.gossip, out_dir=args.out,
                            smoke=args.smoke, unroll=args.unroll,
                            remat=args.remat)
                except Exception:
                    traceback.print_exc()
                    failures.append(tag)
                    _write(args.out, tag,
                           {"arch": a, "shape": s, "mesh": _mesh_name(mp),
                            "status": "failed",
                            "error": traceback.format_exc()[-2000:]})
    if failures:
        print(f"[dryrun] FAILURES ({len(failures)}):")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("[dryrun] all combinations lowered + compiled successfully")


if __name__ == "__main__":
    main()
