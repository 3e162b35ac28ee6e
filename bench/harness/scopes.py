"""What ``jax.profiler.ProfileData`` hides in a trace: each device op's
metadata (``tf_op``, where a ``jax.named_scope`` path lands, and
``program_id``), read from the ``*.xplane.pb`` bytes with a minimal
protobuf wire-format reader, and the reductions built on it.

* Phase time: the union of the intervals of the ops whose ``tf_op`` lies
  under each phase scope (``mix``, ``local``, ``evolve``, ``eval``), and
  of the ops of each compiled program, clipped to the traced window and
  averaged over the chips, as ``xplane.reduce_planes`` takes busy time.
  The TPU compiler leaves some ops it rewrites without metadata (the
  round's large rank scatters, its local-phase loop); such an op takes
  the phase of the scoped ops it encloses or between which it runs
  (``_phases``).
* Idle attribution: each idle interval of the device union, put down to
  the innermost program span open on the harness's host line whose name
  starts with ``scale.`` (the spans ``repro.obs`` writes into the profiler's
  trace), and to none where no such span is open.

The window and the host lines are the harness's, as in ``xplane``.  The
reader knows only the fields of ``tsl/profiler/protobuf/xplane.proto`` it
needs and imports no protobuf library, so it runs wherever JAX does.
"""
from __future__ import annotations

import re
import struct
from bisect import bisect_left
from collections import defaultdict
from dataclasses import dataclass, field

from bench.harness.xplane import DEVICE_PREFIX, OPS_LINE, TOP_N, _clip, _union

SCOPES = ("mix", "local", "evolve", "eval")
SPAN_PREFIX = "scale."
# the innermost name of a path component: "vmap(transpose(evolve))" -> "evolve"
_INNER = re.compile(r"^(?:[^()]*\()*([^()]*)\)*$")
_MODULE = re.compile(r"^(.*)\((\d+)\)$")

# xplane.proto field numbers
XSPACE_PLANES = 1
PLANE_NAME, PLANE_LINES, PLANE_EVENT_META, PLANE_STAT_META = 2, 3, 4, 5
LINE_NAME, LINE_TIMESTAMP_NS, LINE_EVENTS = 2, 3, 4
EVENT_META_ID, EVENT_OFFSET_PS, EVENT_DURATION_PS = 1, 2, 3
EMETA_NAME, EMETA_STATS = 2, 5
SMETA_NAME = 2
STAT_META_ID, STAT_DOUBLE, STAT_STR, STAT_BYTES, STAT_REF = 1, 2, 5, 6, 7


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` for each field of one message: an int for
    a varint, a ``memoryview`` for a length-delimited field, raw bytes for
    a fixed-width one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for f, v in _fields(view):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stats(view_list, stat_names: dict[int, str]) -> dict:
    """String-valued stats by name (``tf_op``, ``hlo_category``, ...) and
    integer ones (``program_id``, ``flops``, ...)."""
    out = {}
    for view in view_list:
        name, value = None, None
        for f, v in _fields(view):
            if f == STAT_META_ID:
                name = stat_names.get(v)
            elif f in (STAT_STR, STAT_BYTES):
                value = _text(v)
            elif f == STAT_REF:
                value = stat_names.get(v)
            elif f == STAT_DOUBLE:
                value = struct.unpack("<d", bytes(v))[0]
            else:
                value = v
        if name is not None:
            out[name] = value
    return out


@dataclass
class Event:
    name: str
    start_ns: float
    duration_ns: float
    stats: dict = field(default_factory=dict)


@dataclass
class Line:
    name: str
    events: list


@dataclass
class Plane:
    name: str
    lines: list


def read_xspace(path: str) -> list[Plane]:
    """The device planes' ``XLA Ops`` and ``XLA Modules`` lines, each op
    carrying its metadata's stats, and every host plane's lines, as
    planes, lines and events shaped as ``ProfileData`` gives them (so
    ``xplane.reduce_planes`` reads them too)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = []
    for fnum, pview in _fields(buf):
        if fnum != XSPACE_PLANES:
            continue
        name, line_views, emeta, smeta = "", [], [], []
        for f, v in _fields(pview):
            if f == PLANE_NAME:
                name = _text(v)
            elif f == PLANE_LINES:
                line_views.append(v)
            elif f == PLANE_EVENT_META:
                emeta.append(v)
            elif f == PLANE_STAT_META:
                smeta.append(v)
        device = name.startswith(DEVICE_PREFIX)
        if not (device or name.startswith("/host:")):
            continue
        stat_names = {}
        for entry in smeta:
            key, value = _map_entry(entry)
            stat_names[key] = next(
                (_text(v) for f, v in _fields(value) if f == SMETA_NAME), "")
        metas = {}
        for entry in emeta:
            key, value = _map_entry(entry)
            mname, mstats = "", []
            for f, v in _fields(value):
                if f == EMETA_NAME:
                    mname = _text(v)
                elif f == EMETA_STATS and device:
                    mstats.append(v)
            metas[key] = (mname, _stats(mstats, stat_names) if device else {})
        lines = []
        for lview in line_views:
            lname, ts_ns, events = "", 0, []
            for f, v in _fields(lview):
                if f == LINE_NAME:
                    lname = _text(v)
                elif f == LINE_TIMESTAMP_NS:
                    ts_ns = v
                elif f == LINE_EVENTS:
                    events.append(v)
            if device and lname not in (OPS_LINE, "XLA Modules"):
                continue
            out = []
            for ev in events:
                mid = off = dur = 0
                for f, v in _fields(ev):
                    if f == EVENT_META_ID:
                        mid = v
                    elif f == EVENT_OFFSET_PS:
                        off = v
                    elif f == EVENT_DURATION_PS:
                        dur = v
                mname, mstats = metas.get(mid, ("", {}))
                out.append(Event(mname, ts_ns + off / 1000, dur / 1000, mstats))
            lines.append(Line(lname, out))
        planes.append(Plane(name, lines))
    return planes


def scope_of(tf_op: str | None) -> str | None:
    """The one phase scope on an op's ``tf_op`` path, or None (no scope,
    or more than one)."""
    if not tf_op:
        return None
    found = {_INNER.match(part).group(1) for part in tf_op.rstrip(":").split("/")}
    found &= set(SCOPES)
    return found.pop() if len(found) == 1 else None


def _window(planes, annotations):
    marks, host_lines = [], []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                mine = [e for e in line.events if e.name in annotations]
                if mine:
                    marks.extend(mine)
                    host_lines.append(line)
    if not marks:
        raise ValueError(f"none of the annotations {sorted(annotations)} "
                         "is in the trace")
    return (min(m.start_ns for m in marks),
            max(m.start_ns + m.duration_ns for m in marks), host_lines)


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy, lo, hi):
    """The idle intervals of ``[lo, hi]`` around ``busy`` (sorted, disjoint
    and clipped to the window, as ``_union(_clip(...))`` gives them)."""
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _innermost_spans(host_lines, lo, hi):
    """Disjoint ``(start, end, name)`` pieces of the window, each carrying
    the innermost ``scale.*`` span open there (the latest opened)."""
    spans = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for line in host_lines for e in line.events
                    if e.name.startswith(SPAN_PREFIX)),
                   key=lambda s: (s[0], -s[1]))
    edges = sorted({lo, hi, *(t for a, b, _ in spans for t in (a, b)
                              if lo < t < hi)})
    pieces, open_ = [], []
    it = iter(spans)
    nxt = next(it, None)
    for a, b in zip(edges, edges[1:]):
        while nxt is not None and nxt[0] <= a:
            open_.append(nxt)
            nxt = next(it, None)
        open_ = [s for s in open_ if s[1] > a]
        if open_:
            pieces.append((a, b, open_[-1][2]))
    return pieces, {s[2] for s in spans}


def _phases(rows) -> list:
    """Each op's phase, for ``rows`` ``(start, end, program, scope)`` in
    start order: its own scope; else, for an op the compiler built without
    metadata in a program that has scopes (a rewritten scatter, a loop op
    around its body), the one scope of the scoped ops of its program that
    start inside it, or, where none does, the scope its nearest scoped
    neighbours of the same program before and after it share; else None."""
    scoped = defaultdict(list)
    for i, r in enumerate(rows):
        if r[3] is not None:
            scoped[r[2]].append(i)
    starts = {m: [rows[i][0] for i in idx] for m, idx in scoped.items()}
    out = []
    for a, b, program, scope in rows:
        if scope is None and program in scoped:
            idx, st = scoped[program], starts[program]
            k = bisect_left(st, a)
            inner = {rows[j][3] for j in idx[k:bisect_left(st, b)]}
            if len(inner) == 1:
                scope = inner.pop()
            elif not inner and 0 < k < len(idx) and (
                    rows[idx[k - 1]][3] == rows[idx[k]][3]):
                scope = rows[idx[k]][3]
        out.append(scope)
    return out


def reduce_scopes(planes, annotations: set[str]) -> dict:
    """Seconds, averaged over the chips: ``scopes`` (device time under each
    phase found, by ``_phases``), ``modules`` (device time of each compiled
    program), ``unscoped_ops`` (the ops of a program that has phases but
    none themselves, by time, most first) and ``idle_by_span`` (device
    idle time under each ``scale.*`` span found on the harness's host
    line; ``idle_s`` less their sum is idle under no such span)."""
    lo, hi, host_lines = _window(planes, annotations)
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    ops_by_device = [[e for line in p.lines if line.name == OPS_LINE
                      for e in line.events] for p in devices]
    ops_by_device = [ops for ops in ops_by_device if ops]
    if not ops_by_device:
        raise ValueError(f"no {DEVICE_PREFIX}* plane with an {OPS_LINE!r} "
                         "line in the trace")
    n_dev = len(ops_by_device)
    scopes, modules, unscoped = defaultdict(float), defaultdict(float), defaultdict(float)
    idle = defaultdict(float)
    idle_s = 0.0
    pieces, span_names = _innermost_spans(host_lines, lo, hi)
    for plane, ops in zip(devices, ops_by_device):
        names = {}
        for line in plane.lines:
            for e in line.events:
                m = _MODULE.match(e.name)
                if m:
                    names[m.group(2)] = m.group(1)
        rows, labels = [], []
        for e in sorted(ops, key=lambda e: (e.start_ns, -e.duration_ns)):
            a, b = e.start_ns, e.start_ns + e.duration_ns
            if b <= lo or a >= hi:
                continue
            program = str(e.stats.get("program_id", ""))
            rows.append((a, b, names.get(program, program or "?"),
                         scope_of(e.stats.get("tf_op"))))
            labels.append(e.name.partition(" = ")[0])
        by_scope, by_module = defaultdict(list), defaultdict(list)
        phased = {r[2] for r in rows if r[3] is not None}
        for (a, b, module, _), scope, label in zip(rows, _phases(rows), labels):
            by_module[module].append((a, b))
            if scope is not None:
                by_scope[scope].append((a, b))
            elif module in phased:
                unscoped[(module, label)] += b - a
        for scope, ivs in by_scope.items():
            scopes[scope] += _length(_union(_clip(ivs, lo, hi))) / n_dev
        for module, ivs in by_module.items():
            modules[module] += _length(_union(_clip(ivs, lo, hi))) / n_dev
        idle_ivs = gaps(_union(_clip([r[:2] for r in rows], lo, hi)), lo, hi)
        idle_s += _length(idle_ivs) / n_dev
        for name in span_names:
            idle[name] += 0.0
        for a, b, name in pieces:
            idle[name] += _length(_clip(idle_ivs, a, b)) / n_dev
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:TOP_N]
    return {
        "scopes": {k: v * 1e-9 for k, v in scopes.items()},
        "modules": {k: v * 1e-9 for k, v in modules.items()},
        "unscoped_ops": [[f"{m}: {op}", d / n_dev * 1e-9] for (m, op), d in top],
        "idle_by_span": {k: v * 1e-9 for k, v in idle.items()},
        "idle_s": idle_s * 1e-9,
    }


def phase_metrics(red: dict, counters: dict, rounds: int) -> dict:
    """The per-layer numbers of a traced train window, by the names they
    would be reported under, from ``red`` (``xplane.reduce_trace`` merged
    with ``reduce_scopes``) and the ``scale.engine`` counters' change over
    the window's ``rounds``: each phase scope's device time and the device
    idle time under ``scale.inputs`` and ``scale.comm``, in % of the
    window; MB copied host to device and blocking device reads, per round.
    A number whose scope, span or counter the trace or the program lacks
    is left out."""
    window = red["window_s"]
    out = {}
    for scope in ("mix", "local", "evolve"):
        if scope in red["scopes"]:
            out[f"{scope}_share.train"] = 100.0 * red["scopes"][scope] / window
    for span, name in (("scale.inputs", "idle_inputs_share.train"),
                       ("scale.comm", "idle_comm_share.train")):
        if span in red["idle_by_span"]:
            out[name] = 100.0 * red["idle_by_span"][span] / window
    if rounds and "input_bytes" in counters:
        out["input_mb.train"] = counters["input_bytes"] / 1e6 / rounds
    if rounds and "host_syncs" in counters:
        out["host_syncs.train"] = counters["host_syncs"] / rounds
    return out
