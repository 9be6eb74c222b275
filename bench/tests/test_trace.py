"""The trace reduction, on hand-made planes and on a trimmed trace
recorded on a TPU v5e.

``fixtures/trace_v5e.json.gz`` holds the first 80 ms of the window of a
``--trace 1`` run of YCSB-A at 16M records, capacity 2^25 (seed 31, one
v5e): its planes as ``trace.load_xplane`` read them, written by
``trace.dump_fixture`` with each HLO op's name cut to its ``%name``."""
import os

import pytest

from bench import trace
from bench.trace import Event, Planes

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_v5e.json.gz")


def planes():
    ops = [Event("fusion.1", 0, 10), Event("fusion.2", 5, 20),
           Event("all-to-all.3", 30, 40), Event("late", 150, 160)]
    mods = [Event("jit__step_single(7)", 0, 20),
            Event("jit_execute_ranges(3)", 30, 40)]
    spans = [Event("bench.window", 0, 100), Event("bench.admit", 15, 35),
             Event("bench.wal", 20, 30), Event("bench.submit", 50, 60)]
    return Planes(ops={0: ops}, modules={0: mods}, spans=spans)


def test_busy_is_the_union_of_overlapping_ops():
    r = trace.reduce(planes())
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s[0] == pytest.approx(30e-9)      # [0,20] + [30,40]
    assert r.all_to_all_s[0] == pytest.approx(10e-9)
    assert r.program("_step_single", 0) == pytest.approx((20e-9, 1))
    assert r.program("execute_ranges", 0)[1] == 1


def test_idle_gaps_go_to_the_innermost_covering_span():
    r = trace.reduce(planes())
    idle = dict(r.idle_by_span)
    # gap [20,30] lies in admit and in wal: wal is the inner span;
    # gap [40,100] overlaps only submit, so all of it goes there
    assert idle["wal"] == pytest.approx(10e-9)
    assert idle["submit"] == pytest.approx(60e-9)
    assert sum(idle.values()) == pytest.approx(70e-9)


def test_fixture_round_trip(tmp_path):
    path = str(tmp_path / "t.json.gz")
    trace.dump_fixture(planes(), path, 0, 100)
    back = trace.load_fixture(path)
    assert back.ops[0] == planes().ops[0][:3]        # [150, 160] is out
    assert back.spans == planes().spans


def test_union_merges_and_sorts():
    assert trace.union([[5, 7], [0, 2], [1, 3], [6, 9]]) == [[0, 3], [5, 9]]


def test_recorded_v5e_trace_reduces():
    p = trace.load_fixture(FIXTURE)
    assert 0 in p.ops and p.ops[0] and p.modules[0]
    r = trace.reduce(p)
    assert 0 < r.busy_s[0] <= r.window_s
    t, n = r.program("_step_single", 0)
    assert n > 0 and 0 < t <= r.window_s
    assert r.top_ops and r.idle_by_span
    assert sum(v for _, v in r.idle_by_span) == pytest.approx(
        r.window_s - r.busy_s[0], rel=1e-6)
