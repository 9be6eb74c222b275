"""Spans and scopes of the serving path (``repro.pipeline.metrics.span``,
``jax.named_scope`` in the jitted programs).

* every device scope reaches the ``op_name`` metadata of the compiled
  programs that the profiler attributes device time by;
* a tiny collect → WAL → dispatch run under the profiler leaves the
  ``pi.*`` host spans in the trace, nested as the calls nest, and the
  span table counts what the trace holds;
* answers are the same with and without a profiler running.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import INSERT, RANGE, SEARCH, PIConfig, build
from repro.core import index as pi_index
from repro.pipeline import (Collector, Dispatcher, Durability, WindowConfig,
                            execute_ranges)
from repro.pipeline.dispatcher import _step_single
from repro.pipeline.metrics import clear_span_table, span, span_table

CFG = PIConfig(capacity=1 << 12, pending_capacity=1 << 9, fanout=8)
BATCH = 64
MAX_SPAN = 16

STEP_SCOPES = {"pi.step.order", "pi.step.probe", "pi.step.apply",
               "pi.step.merge", "pi.step.rebuild"}
REBUILD_SCOPES = {"pi.rebuild.incremental", "pi.rebuild.repack"}
RANGE_SCOPES = {"pi.ranges.tables", "pi.ranges.walk", "pi.ranges.pending"}
HOST_SPANS = {"pi.collect.offer", "pi.collect.take", "pi.collect.seal",
              "pi.wal.append", "pi.wal.fsync", "pi.dispatch.ranges",
              "pi.dispatch.launch", "pi.dispatch.snapshot",
              "pi.dispatch.wait", "pi.dispatch.result", "pi.ckpt.save"}


def tiny_index():
    keys = jnp.arange(0, 4000, 4, dtype=jnp.int32)
    return build(CFG, keys, keys * 3)


def op_name_scopes(hlo_text: str):
    names = re.findall(r'op_name="([^"]*)"', hlo_text)
    return {p for n in names for p in n.split("/") if p.startswith("pi.")}


def _compiled_text(program: str) -> str:
    idx = tiny_index()
    z = jnp.zeros((BATCH,), jnp.int32)
    k = jnp.arange(BATCH, dtype=jnp.int32)
    if program == "_step_single":
        return _step_single.lower(idx, z, k, k).compile().as_text()
    if program == "rebuild":
        return jax.jit(pi_index.rebuild).lower(idx).compile().as_text()
    return execute_ranges.lower(idx, z, k, k, MAX_SPAN).compile().as_text()


@pytest.mark.parametrize("program,want", [
    ("_step_single", STEP_SCOPES | REBUILD_SCOPES),
    ("rebuild", REBUILD_SCOPES),
    ("execute_ranges", RANGE_SCOPES),
])
def test_device_scopes_reach_op_name_metadata(program, want):
    assert want <= op_name_scopes(_compiled_text(program))


def test_rebuild_tiers_nest_in_the_step_rebuild_scope():
    # a reduction XLA builds while lowering may keep only the inner part
    # of the name stack (``cond/branch_0_fun/pi.rebuild.repack/...``), so
    # nesting is checked on the ops that keep it whole
    names = re.findall(r'op_name="([^"]*)"', _compiled_text("_step_single"))
    whole = [n for n in names if n.startswith("jit(_step_single)/")]
    for tier in sorted(REBUILD_SCOPES):
        assert any("/pi.step.rebuild/" in n for n in whole
                   if f"/{tier}/" in n)
        assert not any(n.startswith(f"jit(_step_single)/{tier}")
                       for n in whole)


def stream(n, seed):
    rng = np.random.default_rng(seed)
    ops = np.full(n, SEARCH, np.int32)
    r = rng.random(n)
    ops[r < 0.2] = RANGE
    ops[(r >= 0.2) & (r < 0.6)] = INSERT
    keys = rng.integers(0, 5000, n).astype(np.int32)
    keys2 = np.where(ops == RANGE, keys + rng.integers(0, 40, n), 0)
    vals = rng.integers(0, 1 << 20, n).astype(np.int32)
    return ops, keys, keys2.astype(np.int32), vals


def serve(directory, n=6 * BATCH, chunk=40, seed=5):
    """collect → WAL → dispatch over a fresh index; the per-arrival
    answers in qid order."""
    dur = Durability(str(directory), tiny_index())
    col = Collector(WindowConfig(batch=BATCH), on_seal=dur.on_seal)
    disp = Dispatcher(tiny_index(), depth=1, durability=dur,
                      max_span=MAX_SPAN)
    ops, keys, keys2, vals = stream(n, seed)
    answers = {}

    def retire(results):
        for r in results:
            answers.update(r.per_arrival())
            answers.update(r.per_arrival_ranges())

    for s in range(0, n, chunk):
        e = min(n, s + chunk)
        _, sealed = col.offer_many(np.full(e - s, float(s)), ops[s:e],
                                   keys[s:e], vals[s:e], np.arange(s, e),
                                   keys2[s:e])
        for w in sealed:
            retire(disp.submit(w))
    tail = col.take()
    if tail is not None:
        retire(disp.submit(tail))
    retire(disp.flush())
    dur.close()
    return [answers[q] for q in range(n)]


def host_events(trace_dir):
    """pi.* events per host line: {line: [(name, start, end)]}."""
    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith("pi.")]
            if evs:
                out[(plane.name, line.name)] = sorted(
                    evs, key=lambda x: (x[1], -x[2]))
    return out


def inside(evs, child, parents):
    """Every ``child`` event lies within an event named in ``parents``
    on the same line."""
    kids = [e for e in evs if e[0] == child]
    outer = [e for e in evs if e[0] in parents]
    return kids and all(any(p[1] <= c[1] and c[2] <= p[2] for p in outer)
                        for c in kids)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One served run under the profiler, one without; the span table of
    the traced run and its trace's pi.* host events."""
    plain = serve(tmp_path_factory.mktemp("plain"))
    trace_dir = tmp_path_factory.mktemp("trace")
    clear_span_table()
    with jax.profiler.trace(str(trace_dir)):
        traced = serve(tmp_path_factory.mktemp("traced"))
    table = span_table()
    return plain, traced, table, host_events(trace_dir)


def test_answers_do_not_depend_on_the_profiler(traced_run):
    plain, traced, _, _ = traced_run
    assert plain == traced


def test_host_spans_land_in_the_trace_nested(traced_run):
    _, _, _, lines = traced_run
    names = {e[0] for evs in lines.values() for e in evs}
    assert HOST_SPANS <= names
    evs = [e for line in lines.values() for e in line]
    appends = [e for e in evs if e[0] == "pi.wal.append"]
    fsyncs = [e for e in evs if e[0] == "pi.wal.fsync"]
    # fsync="per_window": one fsync inside each append, one more at close
    assert [sum(a[1] <= f[1] and f[2] <= a[2] for f in fsyncs)
            for a in appends] == [1] * len(appends)
    assert len(fsyncs) == len(appends) + 1
    assert inside(evs, "pi.wal.append", {"pi.collect.seal"})
    assert inside(evs, "pi.collect.seal",
                  {"pi.collect.offer", "pi.collect.take"})


def test_span_table_counts_what_the_trace_holds(traced_run):
    _, _, table, lines = traced_run
    evs = [e for line in lines.values() for e in line]
    for name in HOST_SPANS:
        n = sum(1 for e in evs if e[0] == name)
        count, total, longest = table[name]
        assert count == n > 0
        assert 0 < longest <= total
    windows = table["pi.collect.seal"][0]
    assert table["pi.wal.append"][0] == windows
    assert table["pi.dispatch.wait"][0] == windows
    assert table["pi.dispatch.launch"][0] == windows


def test_span_times_and_nests_without_a_profiler():
    clear_span_table()
    with span("outer"):
        with span("inner"):
            pass
    with span("inner"):
        pass
    t = span_table()
    assert t["pi.outer"][0] == 1 and t["pi.inner"][0] == 2
    assert t["pi.inner"][1] <= t["pi.outer"][1] + t["pi.inner"][2]
    clear_span_table()
    assert span_table() == {}
