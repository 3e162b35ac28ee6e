"""One traced run of a train cell, with the round split by the program's
own phase scopes, host spans and counters.

    python -m bench.tools.trace_phases --workload <cell> --seed <n> --seconds <s>

The run is the train driver's own (set-up, window, reference check), with
the profiler on over the window as ``bench/run.py --trace 1`` has it.  The
profile object handed to the driver also snapshots the engine's
``scale.engine`` counters where the window starts and where it ends, and
reduces the trace with ``bench/harness/scopes.py`` as well.  The last line
of standard output is one JSON object: ``metrics`` (the cell's per-layer
metrics and ``scopes.phase_metrics``), ``trace`` (the reduction: phase and
program device time, the unscoped ops, idle time by host span, the top
ops and idle gaps), ``counters`` (their change over the window),
``rounds``, ``window_s`` and ``correct``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def _phase_profile():
    from bench.harness.profile import Profile
    from bench.harness.scopes import read_xspace, reduce_scopes
    from bench.harness.xplane import find_trace, reduce_planes
    from repro.obs import snapshot_counters

    class PhaseProfile(Profile):
        def start(self) -> None:
            self.c0 = snapshot_counters("scale.engine")
            super().start()

        def stop(self) -> None:
            super().stop()
            self.c1 = snapshot_counters("scale.engine")

        def counters(self) -> dict:
            return {k.split("/", 1)[1]: v - self.c0.get(k, 0)
                    for k, v in self.c1.items()}

        def reduce(self, annotations: set[str]) -> dict:
            try:
                planes = read_xspace(find_trace(self.dir))
                return dict(reduce_planes(planes, annotations),
                            **reduce_scopes(planes, annotations),
                            gap_context=gap_context(planes, annotations))
            finally:
                shutil.rmtree(self.dir, ignore_errors=True)

    return PhaseProfile()


def gap_context(planes, annotations: set[str], top: int = 3,
                events: int = 8) -> list:
    """For the longest idle gaps of the first chip: the host events of any
    thread, and the chip's ``XLA Modules`` events, that overlap each gap
    most, as ``[line: name, seconds of overlap]``."""
    from bench.harness.scopes import DEVICE_PREFIX, OPS_LINE, _window, gaps
    from bench.harness.xplane import _clip, _union

    lo, hi, _ = _window(planes, annotations)
    device = next(p for p in planes if p.name.startswith(DEVICE_PREFIX))
    busy = _union(_clip([(e.start_ns, e.start_ns + e.duration_ns)
                         for line in device.lines if line.name == OPS_LINE
                         for e in line.events], lo, hi))
    idle = sorted(((b - a, a, b) for a, b in gaps(busy, lo, hi)),
                  reverse=True)[:top]
    others = [(f"{p.name} {line.name}", e) for p in planes
              for line in p.lines
              if p.name.startswith("/host:") or line.name == "XLA Modules"
              for e in line.events]
    out = []
    for d, a, b in idle:
        near = sorted(((min(b, e.start_ns + e.duration_ns) - max(a, e.start_ns),
                        f"{where}: {e.name[:80]}") for where, e in others
                       if e.start_ns < b and e.start_ns + e.duration_ns > a),
                      reverse=True)[:events]
        out.append({"gap_s": d * 1e-9, "starts_s": (a - lo) * 1e-9,
                    "overlaps": [[name, o * 1e-9] for o, name in near]})
    return out


def run(args, root: Path) -> dict:
    from bench.harness.device import peaks, require_tpu
    from bench.harness.registry import load_cell, load_driver
    from bench.harness.scopes import phase_metrics
    from bench.run import judge

    cell = load_cell(args.workload, root)
    devices = require_tpu(cell.chips)
    driver = load_driver(cell)
    profile = _phase_profile()
    out = driver.run(cell, args.seed, args.seconds, True, devices, T_START,
                     profile=profile)
    red = profile.reduce({driver.ANNOTATION})
    counters = profile.counters()
    ctx = dict(out["context"], trace=red,
               peak=peaks(devices[0].device_kind))
    metrics = {}
    for m in cell.per_layer:
        value = cell.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = value
    metrics.update(phase_metrics(red, counters, out["context"]["rounds"]))
    return {"metrics": metrics, "trace": red, "counters": counters,
            "rounds": out["context"]["rounds"],
            "window_s": out["context"]["window_s"],
            "correct": judge(out["checks"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from bench.harness.registry import BenchError

    try:
        result = run(args, ROOT / "bench")
    except BenchError as e:
        print(f"trace_phases: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
