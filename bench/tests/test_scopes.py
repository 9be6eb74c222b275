"""The per-scope reduction and the readers of the program's span table.

``fixtures/scopes_v5e.json.gz`` holds 80 ms from the middle of the traced
window of a ``--trace 1`` run of ``ycsb64m-wal.ycsb-a`` (64M records,
capacity 2^27, one TPU v5e, seed 3313000101): six runs of the point step
with each op's ``pi.*`` scope path, and the ``bench.*`` and ``pi.*`` host
spans, as ``bench.scopes.load_xplane`` read the run's xplane and
``dump_fixture`` wrote it (``python3 -m bench.scopes ... --fixture``)."""
import os
import random

import pytest

from bench import harness, scopes, trace
from bench.scopes import Op
from bench.trace import Event, Planes

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "scopes_v5e.json.gz")
STEP = "_step_single"
PHASES = ("pi.step.order", "pi.step.probe", "pi.step.apply",
          "pi.step.merge")


def planes():
    ops = [Op("fusion.1", 0, 10, "pi.step.order"),
           Op("while.2", 10, 40, "pi.step.probe"),
           Op("fusion.3", 15, 25, "pi.step.probe"),     # inside while.2
           Op("copy.4", 25, 30, ""),                   # inside while.2
           Op("fusion.5", 40, 50, "pi.step.rebuild/pi.rebuild.repack"),
           Op("sort.6", 60, 80, "pi.ranges.tables"),
           Op("fusion.7", 70, 90, "pi.ranges.walk"),    # overlaps sort.6
           Op("late", 150, 160, "pi.step.order")]
    mods = [Event("jit__step_single(7)", 0, 50),
            Event("jit_execute_ranges(3)", 60, 90)]
    spans = [Event("bench.window", 0, 100),
             Event("bench.submit", 45, 70),
             Event("pi.dispatch.wait", 50, 60),
             Event("bench.admit", 85, 100),
             Event("pi.collect.offer", 88, 100),
             Event("pi.collect.seal", 89, 95),
             Event("pi.collect.offer", 120, 130)]       # after the window
    return Planes(ops={0: ops}, modules={0: mods}, spans=spans)


def test_scope_path_keeps_the_pi_scopes_outermost_first():
    sp = scopes.scope_path
    assert sp("jit(_step_single)/pi.step.rebuild/cond/branch_1_fun/"
              "jit(rebuild)/cond/branch_0_fun/pi.rebuild.repack/add") \
        == "pi.step.rebuild/pi.rebuild.repack"
    assert sp("jit(execute_ranges)/pi.ranges.walk/jit(_take)/lt") \
        == "pi.ranges.walk"
    assert sp("jit(_step_single)/while/body/add") == ""
    assert sp("") == ""


def test_program_name_from_module_event():
    assert scopes.program_name("jit__step_single(7)") == STEP
    assert scopes.program_name("jit_execute_ranges(3)") == "execute_ranges"


def test_scopes_partition_each_programs_busy_time():
    r = scopes.reduce(planes())
    step, ranges = r.programs[STEP], r.programs["execute_ranges"]
    assert step["runs"] == ranges["runs"] == 1
    # nested ops of one scope count once; copy.4 takes its own interval
    assert step["scopes"] == pytest.approx({
        "pi.step.order": 10e-9, "pi.step.probe": 25e-9, "unscoped": 5e-9,
        "pi.step.rebuild/pi.rebuild.repack": 10e-9})
    # the later-starting of two overlapping ops takes the overlap
    assert ranges["scopes"] == pytest.approx({"pi.ranges.tables": 10e-9,
                                              "pi.ranges.walk": 20e-9})
    for p in (step, ranges):
        assert sum(p["scopes"].values()) == pytest.approx(p["busy_s"])
    assert step["busy_s"] == pytest.approx(50e-9)
    assert ranges["busy_s"] == pytest.approx(30e-9)
    assert r.busy_s == pytest.approx(80e-9)          # [150, 160] is out


def test_self_times_cover_the_union_once():
    ops = [Op("a", 0, 100, "x"), Op("b", 10, 20, "y"), Op("c", 10, 15, "z"),
           Op("d", 90, 130, "y"), Op("e", 200, 210, "x")]
    own = {o.name: s for o, s in scopes.self_times(ops)}
    assert own == pytest.approx({"a": 80e-9, "b": 5e-9, "c": 5e-9,
                                 "d": 40e-9, "e": 10e-9})
    assert sum(own.values()) == pytest.approx(sum(
        e - s for s, e in trace.union([[o.start, o.end] for o in ops])) / 1e9)


def test_idle_time_goes_to_the_innermost_span_of_either_family():
    r = scopes.reduce(planes())
    idle = dict(r.idle_by_span)
    # gap [50,60] lies in submit and in dispatch.wait, the inner one;
    # gap [90,100]: seal [89,95] is innermost to 95, then offer to 100
    assert idle == pytest.approx({"pi.dispatch.wait": 10e-9,
                                  "pi.collect.seal": 5e-9,
                                  "pi.collect.offer": 5e-9})
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)


def test_idle_attribution_matches_a_brute_force_sweep():
    """Every instant of a gap to the latest-started open span, counted on
    a grid of whole nanoseconds."""
    rng = random.Random(7)
    spans = []
    for k in range(120):
        s0 = rng.randrange(0, 1000)
        spans.append(Event(rng.choice(["bench.", "pi."]) + f"s{k}", s0,
                           s0 + rng.randrange(1, 60)))
    gaps, t = [], 0
    while t < 1100:
        t += rng.randrange(1, 15)
        g = rng.randrange(1, 25)
        gaps.append((t, t + g))
        t += g
    want = {}
    for s, e in gaps:
        for x in range(s, e):
            open_ = [sp for sp in spans if sp.start <= x < sp.end]
            name = max(open_, key=lambda sp: (sp.start, sp.start - sp.end)
                       ).name if open_ else "other"
            want[name] = want.get(name, 0) + 1e-9
    got = dict(scopes.attribute(gaps, spans))
    assert len(got) > 10
    assert got == pytest.approx(want)


def _varint(x):
    out = b""
    while True:
        out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
        x >>= 7
        if not x:
            return out


def _len(field, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field, x) -> bytes:
    return _varint(field << 3) + _varint(x)


def test_op_names_come_from_the_event_metadata():
    """``_op_names`` reads the ``tf_op`` and ``program_id`` stats of a
    device plane's event metadata from the XSpace protobuf."""
    def stat_meta(sid, name):
        return _len(5, _int(1, sid) + _len(2, _int(1, sid)
                                           + _len(2, name.encode())))

    def event_meta(eid, name, op_name, pid):
        stats = (_len(5, _int(1, 1) + _len(5, op_name.encode()))
                 + _len(5, _int(1, 2) + _int(3, pid)))
        return _len(4, _int(1, eid) + _len(
            2, _int(1, eid) + _len(2, name.encode()) + stats))

    op = "%fusion.68 = s32[65536]{0} fusion(s32[8]{0} %p)"
    device = (_int(1, 3) + _len(2, b"/device:TPU:0")
              + _len(3, b"\x08\x01")                         # a line
              + event_meta(7, op, "jit(_step_single)/pi.step.probe/gather:",
                           2**63 + 5)
              + event_meta(8, "%copy.1 = s32[8]{0} copy()", "", 9)
              + stat_meta(1, "tf_op") + stat_meta(2, "program_id"))
    host = _len(2, b"/host:CPU") + event_meta(7, op, "jit(x)/y", 1)
    names = scopes._op_names(_len(1, device) + _len(1, host))
    assert names == {"/device:TPU:0": {
        (2**63 + 5, op): "jit(_step_single)/pi.step.probe/gather:"}}
    assert scopes.scope_path(names["/device:TPU:0"][(2**63 + 5, op)]) \
        == "pi.step.probe"
    assert scopes._program_id("jit__step_single(9223372036854775813)") \
        == 2**63 + 5
    assert scopes._program_id("jit_f") is None


def test_span_table_from_the_trace_counts_spans_in_the_window():
    r = scopes.reduce(planes())
    assert r.spans["pi.collect.offer"] == pytest.approx([1, 12e-9, 12e-9])
    assert r.spans["pi.collect.seal"] == pytest.approx([1, 6e-9, 6e-9])
    assert "bench.admit" not in r.spans


def test_fixture_round_trip(tmp_path):
    path = str(tmp_path / "s.json.gz")
    scopes.dump_fixture(planes(), path, 0, 100)
    back = scopes.load_fixture(path)
    assert back.ops[0] == planes().ops[0][:7]        # [150, 160] is out
    assert back.modules == planes().modules
    assert back.spans == planes().spans[:6]


def test_recorded_v5e_trace_splits_the_step_by_phase():
    p = scopes.load_fixture(FIXTURE)
    r = scopes.reduce(p)
    step = r.programs[STEP]
    assert step["runs"] > 0 and step["busy_s"] > 0
    # every phase shows, and the phases with unscoped make the busy time
    phases = {k.split("/")[0] for k, s in step["scopes"].items() if s > 0}
    assert set(PHASES) <= phases
    assert sum(step["scopes"].values()) == pytest.approx(step["busy_s"],
                                                         rel=1e-9)
    assert phases <= set(PHASES) | {"pi.step.rebuild",
                                    "pi.rebuild.incremental",
                                    "pi.rebuild.repack", scopes.UNSCOPED}
    ops = [o for o in p.ops[0] if o.scope]
    assert ops and all(o.scope.startswith("pi.") for o in ops)
    # the busy time is the union of the step's op intervals (each once)
    mods = [m for m in p.modules[0] if "_step_single" in m.name]
    t0, t1 = trace.window_bounds(p)
    inside = [[max(o.start, t0), min(o.end, t1)] for o in p.ops[0]
              if any(m.start <= o.start < m.end for m in mods)
              and o.end > t0 and o.start < t1]
    assert step["busy_s"] == pytest.approx(sum(
        e - s for s, e in trace.union(inside)) / 1e9, rel=1e-9)
    names = {e.name for e in p.spans}
    assert {"pi.collect.offer", "pi.wal.append", "pi.wal.fsync",
            "pi.dispatch.launch", "pi.dispatch.wait"} <= names


TABLE = {"pi.collect.offer": (7, 0.020, 0.005),
         "pi.collect.take": (3, 0.004, 0.002),
         "pi.collect.seal": (4, 0.010, 0.004),
         "pi.wal.append": (4, 0.008, 0.003),
         "pi.wal.fsync": (5, 0.006, 0.002),
         "pi.dispatch.wait": (4, 0.040, 0.012)}


@pytest.mark.parametrize("name,want", [
    ("collect_ms", (0.020 + 0.004 - 0.008) / 4 * 1e3),
    ("wal_append_ms", (0.008 - 0.006) / 4 * 1e3),
    ("wal_fsync_ms", 0.006 / 4 * 1e3),
    ("dispatch_wait_ms", 0.040 / 4 * 1e3),
])
def test_span_table_readers(name, want, monkeypatch):
    monkeypatch.setattr(scopes, "program_span_table", lambda: TABLE)
    assert harness.reader(name)(None) == pytest.approx(want)
    monkeypatch.setattr(scopes, "program_span_table", lambda: {})
    assert harness.reader(name)(None) is None
    # a program that keeps no span table (before spans existed)
    monkeypatch.setattr(scopes, "program_span_table", lambda: None)
    assert harness.reader(name)(None) is None


def test_program_span_table_reads_the_programs_table():
    from repro.pipeline.metrics import clear_span_table, span

    clear_span_table()
    with span("wal.append"):
        with span("wal.fsync"):
            pass
    t = scopes.program_span_table()
    assert t["pi.wal.append"][0] == t["pi.wal.fsync"][0] == 1
    assert harness.reader("wal_fsync_ms")(None) > 0
    clear_span_table()
