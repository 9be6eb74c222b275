"""Reduce a profiler trace of the measured window to per-layer numbers.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``
(or, in tests, a trimmed JSON fixture of the same planes).  Device planes
are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
executed HLO operation and the ``XLA Modules`` line one per program run.
The benchmark's own host spans are ``TraceAnnotation`` events named
``bench.<span>`` on the host plane; ``bench.window`` brackets the window.

What it computes, per device used:

* busy time: the union of the ``XLA Ops`` intervals inside the window
  (operations that overlap count once);
* device time per program: the summed durations of the ``XLA Modules``
  events of each name, and their number;
* all-to-all time: ``XLA Ops`` events whose name holds ``all-to-all``;
* idle gaps: the holes between busy intervals, each attributed to the
  benchmark span (innermost first) that covers most of it.
"""
from __future__ import annotations

import bisect
import gzip
import json
import re
from collections import defaultdict
from dataclasses import dataclass, field

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
NAME_CHARS = 96          # an HLO op's name and the start of its shape
DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")


@dataclass
class Event:
    name: str
    start: float     # ns, trace clock
    end: float


@dataclass
class Planes:
    """What the reduction reads: per device, its op and module events;
    and the benchmark's host spans."""

    ops: dict = field(default_factory=dict)      # device -> [Event]
    modules: dict = field(default_factory=dict)  # device -> [Event]
    spans: list = field(default_factory=list)    # [Event] named bench.*


def _events(line):
    for e in line.events:
        yield Event(e.name, float(e.start_ns), float(e.start_ns)
                    + float(e.duration_ns))


def load_xplane(path: str) -> Planes:
    """Read the planes the reduction needs from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = Planes()
    for plane in data.planes:
        m = DEVICE_RE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out.ops[dev] = list(_events(line))
                elif line.name == "XLA Modules":
                    out.modules[dev] = list(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out.spans.append(Event(
                            e.name, float(e.start_ns),
                            float(e.start_ns) + float(e.duration_ns)))
    return out


def dump_fixture(planes: Planes, path: str, t0: float, t1: float):
    """Write the planes' events inside [t0, t1] as a gzipped JSON fixture."""
    def clip(evs):
        return [[e.name, e.start, e.end] for e in evs
                if e.end >= t0 and e.start <= t1]
    obj = {"ops": {str(d): clip(v) for d, v in planes.ops.items()},
           "modules": {str(d): clip(v) for d, v in planes.modules.items()},
           "spans": clip(planes.spans)}
    with gzip.open(path, "wt") as f:
        json.dump(obj, f)


def load_fixture(path: str) -> Planes:
    with gzip.open(path, "rt") as f:
        obj = json.load(f)
    ev = lambda rows: [Event(n, s, e) for n, s, e in rows]  # noqa: E731
    return Planes(ops={int(d): ev(v) for d, v in obj["ops"].items()},
                  modules={int(d): ev(v) for d, v in obj["modules"].items()},
                  spans=ev(obj["spans"]))


def union(intervals):
    """Merge [start, end] intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip_to(intervals, t0, t1):
    return [[max(s, t0), min(e, t1)] for s, e in intervals
            if e > t0 and s < t1]


@dataclass
class Reduction:
    """Per-device numbers of one traced window, in seconds."""

    window_s: float
    busy_s: dict                 # device -> busy seconds
    modules: dict                # device -> module name -> [s, runs]
    all_to_all_s: dict           # device -> seconds
    top_ops: list                # [[name, seconds]] over all devices
    idle_by_span: list           # [[span, seconds]] on the first device

    @property
    def devices(self):
        return sorted(self.busy_s)

    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    def program(self, name: str, device):
        """(seconds, runs) of the programs whose name holds ``name``."""
        hit = [v for k, v in self.modules.get(device, {}).items()
               if name in k]
        return sum(v[0] for v in hit), sum(v[1] for v in hit)


def window_bounds(planes: Planes):
    win = [e for e in planes.spans if e.name == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(win)}")
    return win[0].start, win[0].end


def attribute(gaps, spans):
    """Seconds of idle time per host span.

    Each gap goes to the span that covers most of it, the innermost (the
    shortest) among equal covers; a gap that no span covers is "other".
    """
    by = defaultdict(float)
    spans = sorted((e for e in spans if e.name != WINDOW_SPAN),
                   key=lambda e: e.start)
    starts = [e.start for e in spans]
    longest = max((e.end - e.start for e in spans), default=0.0)
    for s, e in gaps:
        best = (0.0, 0.0, "other")
        lo = bisect.bisect_left(starts, s - longest)
        for sp in spans[lo:bisect.bisect_right(starts, e)]:
            cover = min(e, sp.end) - max(s, sp.start)
            if cover > 0:
                best = max(best, (cover, sp.start - sp.end,
                                  sp.name[len(SPAN_PREFIX):]))
        by[best[2]] += (e - s) / 1e9
    return sorted(([k, v] for k, v in by.items()), key=lambda kv: -kv[1])


def reduce(planes: Planes, devices=None) -> Reduction:
    """Reduce a window's planes, for ``devices`` (default: all)."""
    t0, t1 = window_bounds(planes)
    devs = sorted(planes.ops) if devices is None else list(devices)
    if not devs or any(d not in planes.ops for d in devs):
        raise ValueError(f"no XLA Ops line for devices {devs}; planes "
                         f"hold {sorted(planes.ops)}")
    busy, a2a = {}, {}
    modules = {}
    op_s = defaultdict(float)
    idle = []
    for d in devs:
        ops = [e for e in planes.ops[d] if e.end > t0 and e.start < t1]
        merged = clip_to(union([[e.start, e.end] for e in ops]), t0, t1)
        busy[d] = sum(e - s for s, e in merged) / 1e9
        a2a[d] = sum(min(e.end, t1) - max(e.start, t0) for e in ops
                     if "all-to-all" in e.name) / 1e9
        for e in ops:
            op_s[e.name[:NAME_CHARS]] += (min(e.end, t1)
                                          - max(e.start, t0)) / 1e9
        modules[d] = defaultdict(lambda: [0.0, 0])
        for e in planes.modules.get(d, ()):
            if t0 <= e.start < t1:
                modules[d][e.name][0] += (e.end - e.start) / 1e9
                modules[d][e.name][1] += 1
        modules[d] = dict(modules[d])
        if d == devs[0]:
            edges = [t0] + [x for iv in merged for x in iv] + [t1]
            idle = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    top = sorted(([k, v] for k, v in op_s.items()), key=lambda kv: -kv[1])
    spans = [e for e in planes.spans if e.end > t0 and e.start < t1]
    return Reduction(window_s=(t1 - t0) / 1e9, busy_s=busy,
                     modules=modules, all_to_all_s=a2a,
                     top_ops=top[:10],
                     idle_by_span=attribute(idle, spans)[:10])
