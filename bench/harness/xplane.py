"""From a profiler trace (``*.xplane.pb``) to the device's busy time, its
idle share, the ops that took most time and the longest idle gaps.

Op times are self times (less the ops nested inside, as a while loop's
body is), summed per op and per opcode.  Busy time is the union of the intervals in which an op runs on a device's
``XLA Ops`` line, clipped to the traced window and averaged over the chips.
The window is the span of the harness's own annotations on the host (one per
round or launch), so host and device times share the profiler's clock.  Each
idle gap is named by the harness annotation open over its midpoint and the
innermost host event open there on the same thread.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP_N = 10


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def op_label(hlo: str) -> tuple[str, str]:
    """``("%sort.150", "sort")`` from an op's HLO text
    ``%sort.150 = (f32[...], ...) sort(...)``; a fusion keeps its kind."""
    name, _, rest = hlo.partition(" = ")
    depth, i = 0, 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            break
    opcode = rest[i + 1:].split("(", 1)[0].strip() or name
    if opcode == "fusion" and "kind=" in rest:
        opcode = "fusion:" + rest.split("kind=", 1)[1].split(",", 1)[0].split(" ", 1)[0]
    return name.strip(), opcode


def _self_times(ops):
    """Each op's duration less the ops nested inside it on the same line
    (a while loop's events enclose its body's)."""
    out, stack = [], []
    for a, b, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= a:
            out.append(stack.pop())
        if stack:
            stack[-1][3] -= min(b, stack[-1][1]) - a
        stack.append([a, b, name, b - a])
    out.extend(stack)
    return out


def reduce_planes(planes, annotations: set[str]) -> dict:
    """``planes``: objects with ``name`` and ``lines`` (each with ``name``
    and ``events`` carrying ``name``, ``start_ns``, ``duration_ns`` and
    ``stats``), as ``jax.profiler.ProfileData`` gives them."""
    marks, host_lines = [], []
    device_ops: dict[str, list] = {}
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                          for ev in line.events]
                mine = [e for e in events if e[2] in annotations]
                if mine:
                    marks.extend(mine)
                    host_lines.append(events)
    if not device_ops:
        raise ValueError(f"no {DEVICE_PREFIX}* plane with an {OPS_LINE!r} "
                         "line in the trace")
    if not marks:
        raise ValueError(f"none of the annotations {sorted(annotations)} "
                         "is in the trace")
    lo = min(m[0] for m in marks)
    hi = max(m[1] for m in marks)
    window_ns = hi - lo

    busy_ns, per_op, per_cat = [], defaultdict(float), defaultdict(float)
    first_union = None
    for name in sorted(device_ops):
        ops = [o for o in device_ops[name] if o[1] > lo and o[0] < hi]
        union = _union(_clip([(a, b) for a, b, _ in ops], lo, hi))
        busy_ns.append(sum(b - a for a, b in union))
        if first_union is None:
            first_union = union
        for a, b, hlo, self_ns in _self_times(ops):
            op, kind = op_label(hlo)
            per_op[(op, kind)] += self_ns / len(device_ops)
            per_cat[kind] += self_ns / len(device_ops)

    gaps = []
    edges = [lo] + [x for ab in first_union for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((b - a, _host_label((a + b) / 2, marks, host_lines)))
    gaps.sort(key=lambda g: -g[0])
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP_N]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy_ns) / len(busy_ns) * 1e-9,
        "device_ops": [[f"{op} ({kind})", d * 1e-9]
                       for (op, kind), d in top_ops],
        "categories": {c: d * 1e-9 for c, d in per_cat.items()},
        "idle_gaps": [[label, d * 1e-9] for d, label in gaps[:TOP_N]],
        "n_devices": len(device_ops),
    }


def _host_label(t: float, marks, host_lines) -> str:
    mark = next((m[2] for m in marks if m[0] <= t < m[1]), "between")
    inner = None
    for events in host_lines:
        for a, b, name in events:
            if a <= t < b and name not in (mark,) and (
                    inner is None or b - a < inner[1] - inner[0]):
                inner = (a, b, name)
    return f"{mark}: {inner[2]}" if inner else mark


def reduce_trace(path: str, annotations: set[str]) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, annotations)
