"""Per-phase device time and the program's own spans, from a traced run.

The program names its phases in two ways (``repro.pipeline.metrics``):

* device scopes: ``jax.named_scope("pi.<program>.<phase>")`` inside the
  jitted programs.  XLA keeps the scope in each HLO op's ``op_name``
  metadata (``jit(_step_single)/pi.step.probe/...``); the TPU profiler
  puts that string in the ``tf_op`` stat of the op's event metadata,
  keyed by the op's name and its program's id;
* host spans: ``span(name)``, a ``TraceAnnotation`` named ``pi.<name>``
  on the host plane, on the device trace's clock.

``load_xplane`` reads both, besides the ``bench.*`` spans and the module
events that ``trace.load_xplane`` reads.  ``reduce`` splits each
program's busy time by scope: every instant an op runs goes to the
innermost op running then (an op nested in another, such as a fusion
inside a ``while``, takes its own interval from its parent's), and that
op's scope path (``pi.step.rebuild/pi.rebuild.repack``) or ``unscoped``
takes it.  So a program's scopes and ``unscoped`` add up to its busy
time, the union of its op intervals.  Idle time goes, instant by
instant, to the innermost host span of either family open then, so the
parts add up to the idle time (``trace.attribute`` gives each gap whole
to the ``bench.*`` span that covers most of it).

``python3 -m bench.scopes --workload <cell> --seed <n> --seconds <s>``
runs the cell as ``bench.run --trace 1`` does and prints, after its
result line, one JSON line of what the result line does not carry: the
device time per program and scope, the idle gaps by span of either
family, and the program's span table over the window.  ``--fixture
<path>`` also writes 80 ms from the middle of the traced window as a
test fixture.
"""
from __future__ import annotations

import bisect
import gzip
import heapq
import json
import re
from collections import defaultdict
from dataclasses import dataclass

from bench import trace

SCOPE_PREFIX = "pi."
UNSCOPED = "unscoped"
OP_NAME_STAT = "tf_op"
SCOPE_RE = re.compile(r"^pi\.[A-Za-z0-9_]+(\.[A-Za-z0-9_]+)*$")
FIXTURE_SECONDS = 0.08


@dataclass
class Op:
    name: str
    start: float     # ns, trace clock
    end: float
    scope: str       # "/"-joined pi.* scopes of its op_name, or ""


def scope_path(op_name: str) -> str:
    """The ``pi.*`` components of an ``op_name``, outermost first."""
    return "/".join(p for p in op_name.split("/") if SCOPE_RE.match(p))


def load_xplane(path: str) -> trace.Planes:
    """Read the planes ``reduce`` needs from an ``.xplane.pb``: per
    device its ops (as ``Op``, with their scope paths) and modules, and
    the host spans of both families (``bench.*`` and ``pi.*``)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    op_names = _op_names(raw)
    out = trace.Planes()
    prefixes = (trace.SPAN_PREFIX, SCOPE_PREFIX)
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        m = trace.DEVICE_RE.match(plane.name)
        if m:
            dev = int(m.group(1))
            lines = {line.name: line for line in plane.lines}
            mods = list(trace._events(lines["XLA Modules"])) \
                if "XLA Modules" in lines else []
            out.modules[dev] = mods
            if "XLA Ops" in lines:
                out.ops[dev] = _scoped_ops(lines["XLA Ops"], mods,
                                           op_names.get(plane.name, {}))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefixes):
                        out.spans.append(trace.Event(
                            e.name, float(e.start_ns),
                            float(e.start_ns) + float(e.duration_ns)))
    return out


def _scoped_ops(line, mods, op_names):
    """The line's ops, each with the scope path of its ``op_name``, looked
    up by the program (the module running when it starts) and its name."""
    starts = [m.start for m in mods]
    ops = []
    for e in line.events:
        start = float(e.start_ns)
        i = bisect.bisect_right(starts, start) - 1
        pid = _program_id(mods[i].name) if i >= 0 else None
        ops.append(Op(e.name, start, start + float(e.duration_ns),
                      scope_path(op_names.get((pid, e.name), ""))))
    return ops


def _program_id(module: str):
    """``jit__step_single(1814...)`` -> 1814..., the module's program id."""
    head, _, tail = module.rpartition("(")
    return int(tail[:-1]) if head and tail[:-1].isdigit() else None


# The TPU trace keeps an op's ``op_name`` in its event *metadata* (the
# ``tf_op`` stat, with ``program_id``), which ``jax.profiler.ProfileData``
# does not expose; so the few fields needed are read from the XSpace
# protobuf (tsl/profiler/protobuf/xplane.proto) directly.

def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """(field number, value) of a protobuf message: an int for varints,
    bytes for the rest."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _map_value(entry):
    """The value of a protobuf map entry (field 2)."""
    for f, v in _fields(entry):
        if f == 2:
            return v
    return b""


def _op_names(raw: bytes) -> dict:
    """Per device plane name, ``{(program id, op name): op_name}`` from
    the ``tf_op`` stat of its event metadata."""
    out = {}
    buf = memoryview(raw)
    for f, plane in _fields(buf):
        if f != 1:                                  # XSpace.planes
            continue
        name, metas, stat_ids = "", [], {}
        for pf, pv in _fields(plane):
            if pf == 2:                             # XPlane.name
                name = bytes(pv).decode()
                if not trace.DEVICE_RE.match(name):
                    break
            elif pf == 4:                           # event_metadata map
                metas.append(_map_value(pv))
            elif pf == 5:                           # stat_metadata map
                sm = dict(_fields(_map_value(pv)))
                stat_ids[sm.get(1, 0)] = bytes(sm.get(2, b"")).decode()
        if not metas:
            continue
        table = out.setdefault(name, {})
        for meta in metas:
            ename, pid, op_name = "", None, ""
            for mf, mv in _fields(meta):
                if mf == 2:                         # XEventMetadata.name
                    ename = bytes(mv).decode()
                elif mf == 5:                       # XEventMetadata.stats
                    st = dict(_fields(mv))
                    stat = stat_ids.get(st.get(1))
                    if stat == OP_NAME_STAT and 5 in st:
                        op_name = bytes(st[5]).decode()
                    elif stat == "program_id":
                        pid = st.get(3, st.get(4))
            if op_name:
                table[(pid, ename)] = op_name
    return out


def dump_fixture(planes: trace.Planes, path: str, t0: float, t1: float):
    """Write the planes' events that overlap [t0, t1] as gzipped JSON;
    each op keeps its ``%name`` and its scope path."""
    def keep(e):
        return e.end >= t0 and e.start <= t1

    def short(name):
        return name.split(" ", 1)[0]

    obj = {"ops": {str(d): [[short(e.name), e.start, e.end, e.scope]
                            for e in v if keep(e)]
                   for d, v in planes.ops.items()},
           "modules": {str(d): [[e.name, e.start, e.end]
                                for e in v if keep(e)]
                       for d, v in planes.modules.items()},
           "spans": [[e.name, e.start, e.end] for e in planes.spans
                     if keep(e)]}
    with gzip.open(path, "wt") as f:
        json.dump(obj, f)


def load_fixture(path: str) -> trace.Planes:
    with gzip.open(path, "rt") as f:
        obj = json.load(f)
    return trace.Planes(
        ops={int(d): [Op(*r) for r in v] for d, v in obj["ops"].items()},
        modules={int(d): [trace.Event(*r) for r in v]
                 for d, v in obj["modules"].items()},
        spans=[trace.Event(*r) for r in obj["spans"]])


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def program_name(module: str) -> str:
    """``jit__step_single(12)`` -> ``_step_single``."""
    name = module.split("(", 1)[0]
    return name[len("jit_"):] if name.startswith("jit_") else name


def innermost(events):
    """The pieces ``(start, end, event)`` that partition the union of the
    events' intervals, in time order: each instant goes to the innermost
    event then, the one that started last (the shorter among equal
    starts)."""
    events = sorted(events, key=lambda o: o.start)
    times = sorted({t for o in events for t in (o.start, o.end)})
    running = []        # heap of (-start, duration, index); lazily pruned
    i = 0
    for a, b in zip(times, times[1:]):
        while i < len(events) and events[i].start <= a:
            heapq.heappush(running, (-events[i].start,
                                     events[i].end - events[i].start, i))
            i += 1
        while running and events[running[0][2]].end <= a:
            heapq.heappop(running)
        if running:
            yield a, b, events[running[0][2]]


def self_times(ops):
    """(op, seconds) pairs that partition the union of ``ops``' intervals
    by ``innermost``: an op's own time, less what ops inside it took."""
    own = defaultdict(float)
    ops_by_id = {}
    for a, b, o in innermost(ops):
        own[id(o)] += b - a
        ops_by_id[id(o)] = o
    return [(ops_by_id[k], s / 1e9) for k, s in own.items()]


@dataclass
class ScopeReduction:
    """Per program on the first device, its busy seconds, runs, and
    seconds by scope path; idle gaps by span of either family; the
    program's span table from the trace's ``pi.*`` host events."""

    window_s: float
    busy_s: float
    programs: dict      # program -> {"busy_s", "runs", "scopes": {path: s}}
    idle_by_span: list  # [[span name, seconds]]
    spans: dict         # pi.* span -> [count, total s, longest s]


def attribute(gaps, spans):
    """Seconds of idle time per host span of either family: each instant
    of a gap goes to the innermost span then (``innermost``), "other"
    where none is open, so the parts add up to the idle time."""
    by = defaultdict(float)
    pieces = list(innermost(e for e in spans
                            if e.name != trace.WINDOW_SPAN))
    j = 0
    for s, e in sorted(gaps):
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            a, b, sp = pieces[k]
            part = min(b, e) - max(a, s)
            if part > 0:
                by[sp.name] += part / 1e9
                covered += part
            k += 1
        if e - s > covered:
            by["other"] += (e - s - covered) / 1e9
    return sorted(([k, v] for k, v in by.items()), key=lambda kv: -kv[1])


def reduce(planes: trace.Planes, device=None) -> ScopeReduction:
    """Reduce the window's scoped planes on ``device`` (default: the
    lowest-numbered device with ops)."""
    t0, t1 = trace.window_bounds(planes)
    dev = min(planes.ops) if device is None else device
    ops = [Op(o.name, max(o.start, t0), min(o.end, t1), o.scope)
           for o in planes.ops[dev] if o.end > t0 and o.start < t1]
    mods = sorted((e for e in planes.modules.get(dev, ())
                   if t0 <= e.start < t1), key=lambda e: e.start)
    starts = [m.start for m in mods]
    programs = {}
    for m in mods:
        p = programs.setdefault(program_name(m.name),
                                {"busy_s": 0.0, "runs": 0, "scopes": {}})
        p["runs"] += 1
    by_prog = defaultdict(list)
    for o in ops:
        i = bisect.bisect_right(starts, o.start) - 1
        prog = (program_name(mods[i].name)
                if i >= 0 and o.start < mods[i].end else "other")
        by_prog[prog].append(o)
    for prog, pops in by_prog.items():
        p = programs.setdefault(prog, {"busy_s": 0.0, "runs": 0,
                                       "scopes": {}})
        p["busy_s"] = sum(e - s for s, e in trace.union(
            [[o.start, o.end] for o in pops])) / 1e9
        scopes = defaultdict(float)
        for o, s in self_times(pops):
            scopes[o.scope or UNSCOPED] += s
        p["scopes"] = dict(sorted(scopes.items(), key=lambda kv: -kv[1]))
    merged = trace.union([[o.start, o.end] for o in ops])
    edges = [t0] + [x for iv in merged for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [e for e in planes.spans if e.end > t0 and e.start < t1]
    table = {}
    for e in spans:
        if e.name.startswith(SCOPE_PREFIX) and e.start >= t0:
            row = table.setdefault(e.name, [0, 0.0, 0.0])
            d = (e.end - e.start) / 1e9
            row[0] += 1
            row[1] += d
            row[2] = max(row[2], d)
    return ScopeReduction(
        window_s=(t1 - t0) / 1e9,
        busy_s=sum(e - s for s, e in merged) / 1e9,
        programs=programs, idle_by_span=attribute(gaps, spans),
        spans=dict(sorted(table.items())))


# ---------------------------------------------------------------------------
# the program's span table, for readers of host metrics
# ---------------------------------------------------------------------------

def program_span_table():
    """The program's span table (``repro.pipeline.metrics.span_table``):
    ``pi.<name>`` -> (count, total s, longest s) over the process; None
    for a program that keeps none."""
    try:
        from repro.pipeline.metrics import span_table
    except ImportError:
        return None
    return span_table()


def span_total(table, name: str) -> float:
    return table[name][1] if name in table else 0.0


def span_count(table, name: str) -> int:
    return table[name][0] if name in table else 0


# ---------------------------------------------------------------------------
# the command
# ---------------------------------------------------------------------------

def _per_run(red: ScopeReduction) -> dict:
    out = {}
    for prog, p in sorted(red.programs.items(),
                          key=lambda kv: -kv[1]["busy_s"]):
        runs = max(p["runs"], 1)
        out[prog] = {"runs": p["runs"],
                     "busy_ms_per_run": p["busy_s"] / runs * 1e3,
                     "scopes_ms_per_run": {k: s / runs * 1e3 for k, s
                                           in p["scopes"].items()}}
    return out


def main(argv=None) -> int:
    import argparse
    import os
    import sys
    import time

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))
    from bench import harness, ycsb
    from repro.pipeline import metrics

    bench = harness.load_benchmark()
    cell, entry = harness.find_cell(bench, args.workload)
    try:
        devices = harness.chip_devices(cell["chips"])
    except harness.BenchError as e:
        print(f"bench.scopes: {args.workload}: {e}", file=sys.stderr)
        return 2
    kept = {}
    load, start = trace.load_xplane, harness.Profile.start

    def keep(path):
        kept["planes"] = load_xplane(path)
        return load(path)

    def start_clean(self, seconds):
        metrics.clear_span_table()      # the table from the window on
        start(self, seconds)

    trace.load_xplane, harness.Profile.start = keep, start_clean
    try:
        result = harness.run_cell(
            harness.load_config(entry), ycsb.load_traffic(cell["traffic"]),
            args.seed, args.seconds, True, devices, t_start=t_start,
            metric_names=harness.metrics_for(bench, cell["name"], True))
    finally:
        trace.load_xplane, harness.Profile.start = load, start
    print(json.dumps(result))
    planes = kept["planes"]
    red = reduce(planes, devices[0].id)
    print(json.dumps({
        "device_scopes": _per_run(red),
        "window_s": red.window_s, "busy_s": red.busy_s,
        "idle_gaps_program": red.idle_by_span[:16],
        "spans_traced": red.spans,
        "spans_window_and_drain": metrics.span_table()}))
    if args.fixture:
        t0, t1 = trace.window_bounds(planes)
        mid = (t0 + t1) / 2
        dump_fixture(planes, args.fixture, mid, mid + FIXTURE_SECONDS * 1e9)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
