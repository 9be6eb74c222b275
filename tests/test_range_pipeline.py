"""Range serving tier: RANGE ops through collect → WAL → dispatch.

Contract under test (DESIGN.md §9): a RANGE(lo, hi) arrival admitted
through the collection window must produce exactly the (count, sum)
aggregate a scalar ``range_agg`` oracle replay produces against the
pre-window index state — across coalescing, intervening window writes,
rebuilds, sharded fan-out, WAL recovery, and both descent backends — and
the whole serving run must compile the range executor exactly once.
"""
import struct
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from faultpoints import SimulatedCrash, crash_at
from repro.core import (INSERT, RANGE, SEARCH, PIConfig, RefIndex, build,
                        build_sharded)
from repro.analysis.runtime import trace_guard
from repro.core import index as pi_index
from repro.pipeline import (ArrivalConfig, Collector, Dispatcher, Durability,
                            OverloadConfig, PipelineMetrics, WindowConfig,
                            execute_ranges, execute_ranges_sharded,
                            make_arrivals, range_trace_count, read_wal,
                            record_window, recover)
from repro.pipeline.overload import (AdmissionController, SHED_RANGE,
                                     SHED_RANGE_SUB, SHED_SEARCH,
                                     SHED_SEARCH_DUP, SHED_WRITE)
from repro.pipeline.wal import (MAGIC_V1, WalWriter, _HEADER, _payload_len)
from repro import data as data_mod


def i32(x) -> int:
    """Wrap to int32, matching the device's modular aggregation."""
    return int(np.array(int(x), np.int64).astype(np.int32))


def ref_range(ref: RefIndex, lo: int, hi: int):
    """(count, int32-wrapped sum) the serving tier must reproduce."""
    items = ref.range(lo, hi)
    return len(items), i32(sum(v for _, v in items))


def mixed_stream(n, rng, *, key_space=2000, range_frac=0.3, max_hspan=300,
                 write_frac=0.3):
    """Arrival-order op arrays with a RANGE / write / SEARCH mix."""
    ops = np.full(n, SEARCH, np.int32)
    keys = rng.integers(0, key_space, n).astype(np.int32)
    keys2 = np.zeros(n, np.int32)
    vals = rng.integers(0, 1 << 20, n).astype(np.int32)
    r = rng.random(n)
    is_r = r < range_frac
    ops[is_r] = RANGE
    keys2[is_r] = keys[is_r] + rng.integers(0, max_hspan, n)[is_r]
    ops[(r >= range_frac) & (r < range_frac + write_frac)] = INSERT
    return ops, keys, keys2, vals


def replay_windows(disp, col, ops, keys, keys2, vals, ref):
    """Drive the stream window-by-window, checking every retired window's
    RANGE slots against the RefIndex *pre-window* state before folding
    the window's writes into the oracle."""
    n = len(ops)
    point_results, range_results = {}, {}
    n_ranges_checked = 0

    def drain(retired):
        nonlocal n_ranges_checked
        for res in retired:
            w = res.window
            occ = w.occupancy
            for slot in range(occ):
                if w.ops[slot] == RANGE:
                    lo, hi = int(w.keys[slot]), int(w.keys2[slot])
                    ec, es = ref_range(ref, lo, hi)
                    assert int(res.rcnt[slot]) == ec, (slot, lo, hi)
                    assert i32(res.rsum[slot]) == es, (slot, lo, hi)
                    n_ranges_checked += 1
            ref.execute(np.asarray(w.ops[:occ]), np.asarray(w.keys[:occ]),
                        np.asarray(w.vals[:occ]))
            point_results.update(res.per_arrival())
            range_results.update(res.per_arrival_ranges())

    step = col.cfg.batch
    for s in range(0, n, step):
        e = min(n, s + step)
        _, sealed = col.offer_many(np.full(e - s, float(s)), ops[s:e],
                                   keys[s:e], vals[s:e], np.arange(s, e),
                                   keys2=keys2[s:e])
        for w in sealed:
            drain(disp.submit(w))
    tail = col.take(float(n))
    if tail is not None:
        drain(disp.submit(tail))
    drain(disp.flush())
    return point_results, range_results, n_ranges_checked


# ---------------------------------------------------------------------------
# range_agg span budget (the kernel-level fix under the tier)
# ---------------------------------------------------------------------------

def test_range_agg_span_budget_counts_live_keys_not_slots():
    """Regression: slack slots must not consume the max_span budget.

    A heavily gapped layout (seg_width 16, ~25% occupancy) holds the same
    60 keys as a dense one; with max_span=64 > 60 both must return the
    full aggregate.  The pre-fix walk advanced slot-by-slot, so gapped
    runs burned the budget on sentinel slack and truncated early.
    """
    keys = np.arange(0, 600, 10, dtype=np.int32)          # 60 live keys
    vals = (keys * 3).astype(np.int32)
    lo = np.array([0], np.int32)
    hi = np.array([600], np.int32)
    outs = {}
    for label, seg in (("gapped", 16), ("dense", 1024)):
        cfg = PIConfig(capacity=1024, pending_capacity=32, fanout=4,
                       seg_width=seg, backend="xla")
        idx = build(cfg, jnp.asarray(keys), jnp.asarray(vals))
        cnt, sm = pi_index.range_agg(idx, jnp.asarray(lo), jnp.asarray(hi),
                                     64)
        outs[label] = (int(cnt[0]), int(sm[0]))
    assert outs["dense"] == (60, i32(vals.sum()))
    assert outs["gapped"] == outs["dense"], \
        "slack consumed the span budget in the gapped layout"


def test_range_agg_truncation_parity_gapped_vs_dense():
    """When max_span < live keys, both layouts truncate at the same key
    rank — the budget is defined over occupied ranks, not slots."""
    keys = np.arange(0, 400, 4, dtype=np.int32)           # 100 live keys
    vals = np.ones(100, np.int32)
    lo, hi = np.array([0], np.int32), np.array([400], np.int32)
    outs = []
    for seg in (16, 1024):
        cfg = PIConfig(capacity=1024, pending_capacity=32, fanout=4,
                       seg_width=seg, backend="xla")
        idx = build(cfg, jnp.asarray(keys), jnp.asarray(vals))
        cnt, sm = pi_index.range_agg(idx, jnp.asarray(lo), jnp.asarray(hi),
                                     17)
        outs.append((int(cnt[0]), int(sm[0])))
    assert outs[0] == outs[1] == (17, 17)


def test_range_agg_backend_parity():
    """xla and pallas-interpret produce bit-identical aggregates (int32
    aggregation is exact, so parity is equality, not tolerance)."""
    rng = np.random.default_rng(5)
    keys = np.unique(rng.integers(0, 5000, 400).astype(np.int32))
    vals = rng.integers(-(1 << 20), 1 << 20, keys.shape[0]).astype(np.int32)
    lo = rng.integers(0, 4000, 32).astype(np.int32)
    hi = (lo + rng.integers(0, 2000, 32)).astype(np.int32)
    outs = []
    for backend in ("xla", "pallas-interpret"):
        cfg = PIConfig(capacity=1024, pending_capacity=64, fanout=4,
                       seg_width=64, backend=backend)
        idx = build(cfg, jnp.asarray(keys), jnp.asarray(vals))
        cnt, sm = pi_index.range_agg(idx, jnp.asarray(lo), jnp.asarray(hi),
                                     512)
        outs.append((np.asarray(cnt), np.asarray(sm)))
    assert np.array_equal(outs[0][0], outs[1][0])
    assert np.array_equal(outs[0][1], outs[1][1])


# ---------------------------------------------------------------------------
# the pipeline oracle replay (tentpole contract)
# ---------------------------------------------------------------------------

def test_pipeline_ranges_match_oracle_replay_across_rebuilds():
    """RANGE results == scalar pre-window oracle, through window writes
    and the rebuilds they trigger, from ONE compiled range execute."""
    rng = np.random.default_rng(11)
    keys0 = np.unique(rng.integers(0, 2000, 300).astype(np.int32))
    vals0 = rng.integers(0, 1 << 20, keys0.shape[0]).astype(np.int32)
    cfg = PIConfig(capacity=2048, pending_capacity=64, fanout=4,
                   seg_width=64, backend="xla")
    idx = build(cfg, jnp.asarray(keys0), jnp.asarray(vals0))
    ref = RefIndex.build(keys0, vals0)
    met = PipelineMetrics()
    col = Collector(WindowConfig(batch=64))
    disp = Dispatcher(idx, depth=2, metrics=met, max_span=4096,
                      clock=lambda: 0.0)
    ops, keys, keys2, vals = mixed_stream(1500, rng)

    base = range_trace_count()
    points, ranges, n_checked = replay_windows(disp, col, ops, keys, keys2,
                                               vals, ref)
    trace_guard("pipeline.ranges").expect(base, 1, "windowed range replay")
    assert n_checked > 100
    assert met.n_rebuilds > 0, "stream too small to trigger a rebuild"
    # every RANGE arrival got a result, and it matches its window slot
    for i in np.nonzero(ops == RANGE)[0]:
        assert i in ranges
    # point results stay correct alongside (ranges don't perturb them)
    ref2 = RefIndex.build(keys0, vals0)
    # arrival-order scalar oracle for points only is the window replay
    # already checked above via per-window execute; spot-check misses
    assert len(points) == int(np.count_nonzero(ops != RANGE))


def test_pre_window_semantics_writes_in_same_window_invisible():
    """A RANGE sealed into the same window as a covering INSERT must NOT
    see it — every range observes the state at the window boundary."""
    cfg = PIConfig(capacity=256, pending_capacity=32, fanout=4,
                   seg_width=16, backend="xla")
    idx = build(cfg, jnp.asarray(np.array([10, 20], np.int32)),
                jnp.asarray(np.array([1, 2], np.int32)))
    col = Collector(WindowConfig(batch=8))
    disp = Dispatcher(idx, depth=0, max_span=256)
    # INSERT 15 arrives BEFORE the range in the same window
    ops = np.array([INSERT, RANGE], np.int32)
    keys = np.array([15, 0], np.int32)
    keys2 = np.array([0, 100], np.int32)
    vals = np.array([7, 0], np.int32)
    _, sealed = col.offer_many(np.zeros(2), ops, keys, vals, np.arange(2),
                               keys2=keys2)
    assert not sealed
    (res,) = disp.submit(col.take(0.0))
    cnt, sm = res.per_arrival_ranges()[1]
    assert (cnt, sm) == (2, 3)          # pre-window state: {10:1, 20:2}
    # the next window DOES see the insert
    _, sealed = col.offer_many(np.ones(1), np.array([RANGE], np.int32),
                               np.array([0], np.int32),
                               np.array([0], np.int32), np.array([2]),
                               keys2=np.array([100], np.int32))
    (res2,) = disp.submit(col.take(1.0))
    assert res2.per_arrival_ranges()[2] == (3, 10)


# ---------------------------------------------------------------------------
# collection-window coalescing
# ---------------------------------------------------------------------------

def test_exact_range_pairs_share_one_slot():
    """Equal (lo, hi) arrivals coalesce into one result slot; a strictly
    contained range gets its own slot (its aggregate differs) but is
    flagged by range_covered — the shed-first class."""
    col = Collector(WindowConfig(batch=16))
    met = PipelineMetrics()
    idx = build(PIConfig(capacity=256, pending_capacity=32, fanout=4,
                         seg_width=16, backend="xla"),
                jnp.asarray(np.arange(0, 100, 5, np.int32)),
                jnp.asarray(np.arange(20, dtype=np.int32)))
    disp = Dispatcher(idx, depth=0, metrics=met, max_span=256)
    ops = np.full(5, RANGE, np.int32)
    los = np.array([10, 10, 30, 12, 10], np.int32)
    his = np.array([50, 50, 40, 48, 50], np.int32)
    cov = col.range_covered(los, his)
    assert not cov.any(), "empty window covers nothing"
    _, sealed = col.offer_many(np.zeros(5), ops, los,
                               np.zeros(5, np.int32), np.arange(5),
                               keys2=his)
    assert not sealed
    w = col.take(0.0)
    assert w.occupancy == 3              # (10,50) shared by 3 arrivals
    assert w.slots[0] == w.slots[1] == w.slots[4]
    assert len({int(s) for s in w.slots}) == 3
    # containment probe: [12,48] and [30,40] are inside queued [10,50]
    col2 = Collector(WindowConfig(batch=16))
    col2.offer(0.0, RANGE, 10, 0, 0, key2=50)
    cov = col2.range_covered(np.array([12, 30, 5, 10], np.int32),
                             np.array([48, 40, 20, 50], np.int32))
    assert cov.tolist() == [True, True, False, True]
    # retire through the dispatcher: metrics see 5 arrivals, 3 slots
    (res,) = disp.submit(w)
    assert met.range_admitted == 5
    assert met.range_slots == 3
    assert met.range_coalesce_hits == 2
    pr = res.per_arrival_ranges()
    assert pr[0] == pr[1] == pr[4]       # shared slot, shared result
    assert pr[3] != pr[2]


def test_offer_scalar_vs_bulk_bitwise_equal_with_ranges(rng):
    """offer() loop and offer_many() build byte-identical range windows."""
    ops, keys, keys2, vals = mixed_stream(400, rng, key_space=300,
                                          max_hspan=80)
    t = np.cumsum(rng.random(400) * 0.01)
    windows = [[], []]
    for mode in (0, 1):
        col = Collector(WindowConfig(batch=32))
        if mode == 0:
            for i in range(400):
                while not col.offer(float(t[i]), int(ops[i]), int(keys[i]),
                                    int(vals[i]), i, key2=int(keys2[i])):
                    windows[mode].append(col.take(float(t[i])))
        else:
            _, sealed = col.offer_many(t, ops, keys, vals, np.arange(400),
                                       keys2=keys2)
            windows[mode].extend(sealed)
        tail = col.take(float(t[-1]))
        if tail is not None:
            windows[mode].append(tail)
    assert len(windows[0]) == len(windows[1])
    for a, b in zip(windows[0], windows[1]):
        assert np.array_equal(a.ops, b.ops)
        assert np.array_equal(a.keys, b.keys)
        assert np.array_equal(a.keys2, b.keys2)
        assert np.array_equal(a.vals, b.vals)
        assert a.occupancy == b.occupancy
        assert list(a.qids) == list(b.qids)
        assert np.array_equal(a.slots, b.slots)


def test_range_admission_validation():
    col = Collector(WindowConfig(batch=8))
    sent = np.iinfo(np.int32).max
    with pytest.raises(ValueError, match="lower bound"):
        col.offer(0.0, RANGE, 10, 0, 0, key2=5)
    with pytest.raises(ValueError):
        col.offer(0.0, RANGE, 10, 0, 0, key2=sent)
    # bulk admission validates atomically: nothing admitted on failure
    with pytest.raises(ValueError):
        col.offer_many(np.zeros(2), np.array([SEARCH, RANGE], np.int32),
                       np.array([1, 10], np.int32), np.zeros(2, np.int32),
                       np.arange(2), keys2=np.array([0, 3], np.int32))
    assert col.take(0.0) is None


# ---------------------------------------------------------------------------
# sharded fan-out
# ---------------------------------------------------------------------------

def test_sharded_fanout_parity_and_oracle(rng):
    keys = np.unique(rng.integers(0, 100_000, 2500).astype(np.int32))
    vals = rng.integers(0, 1 << 20, keys.shape[0]).astype(np.int32)
    cfg = PIConfig(capacity=2048, pending_capacity=64, fanout=4,
                   seg_width=64, backend="xla")
    # four shards on one device: each device reduces over the shards it
    # holds before the cross-device psum
    mesh = jax.make_mesh((1,), ("data",))
    state = build_sharded(cfg, 4, keys, vals, mesh=mesh)
    single = build(PIConfig(capacity=8192, pending_capacity=64, fanout=4,
                            seg_width=64, backend="xla"),
                   jnp.asarray(keys), jnp.asarray(vals))
    ref = RefIndex.build(keys, vals)
    B = 64
    ops = np.full(B, SEARCH, np.int32)
    los = np.zeros(B, np.int32)
    his = np.zeros(B, np.int32)
    for i in range(48):                  # many spans crossing shard fences
        lo = int(rng.integers(0, 90_000))
        ops[i] = RANGE
        los[i] = lo
        his[i] = lo + int(rng.integers(0, 50_000))
    base = range_trace_count()
    cnt_s, sum_s = execute_ranges_sharded(state, mesh, jnp.asarray(ops),
                                          jnp.asarray(los),
                                          jnp.asarray(his), 8192)
    execute_ranges_sharded(state, mesh, jnp.asarray(ops), jnp.asarray(los),
                           jnp.asarray(his), 8192)
    trace_guard("pipeline.ranges").expect(base, 1, "repeated sharded call")
    cnt_1, sum_1 = execute_ranges(single, jnp.asarray(ops),
                                  jnp.asarray(los), jnp.asarray(his), 8192)
    assert np.array_equal(np.asarray(cnt_s), np.asarray(cnt_1))
    assert np.array_equal(np.asarray(sum_s), np.asarray(sum_1))
    for i in range(48):
        ec, es = ref_range(ref, int(los[i]), int(his[i]))
        assert int(cnt_s[i]) == ec
        assert i32(sum_s[i]) == es
    assert not np.asarray(cnt_s)[48:].any()
    assert not np.asarray(sum_s)[48:].any()


# ---------------------------------------------------------------------------
# WAL + recovery
# ---------------------------------------------------------------------------

def _drive_durable(d, n_windows=6, seed=0, fsync="per_window", crash=None):
    """Build an index + durability pair and push range-bearing windows."""
    rng = np.random.default_rng(seed)
    cfg = PIConfig(capacity=1024, pending_capacity=64, fanout=4,
                   seg_width=64, backend="xla")
    k0 = np.arange(0, 400, 4, dtype=np.int32)
    idx = build(cfg, jnp.asarray(k0), jnp.asarray((k0 * 2).astype(np.int32)))
    dur = Durability(d, idx, fsync=fsync)
    col = Collector(WindowConfig(batch=16), on_seal=dur.on_seal)
    disp = Dispatcher(idx, depth=0, durability=dur, max_span=2048)
    ops, keys, keys2, vals = mixed_stream(16 * n_windows, rng,
                                          key_space=500, max_hspan=80)
    n_windows_out = 0
    for s in range(0, len(ops), 16):
        _, sealed = col.offer_many(np.full(16, float(s)), ops[s:s + 16],
                                   keys[s:s + 16], vals[s:s + 16],
                                   np.arange(s, s + 16),
                                   keys2=keys2[s:s + 16])
        for w in sealed:
            disp.submit(w)
            n_windows_out += 1
    tail = col.take(float(len(ops)))
    if tail is not None:
        disp.submit(tail)
        n_windows_out += 1
    disp.flush()
    dur.close()
    return disp.index, n_windows_out


def test_recovery_replays_range_windows_bit_identically(tmp_path):
    d = str(tmp_path / "dur")
    live, n_windows = _drive_durable(d)
    rec_index, replayed = recover(d)
    assert len(replayed) == n_windows >= 5
    assert any((r.ops == RANGE).any() for r in replayed)
    for r in replayed:
        assert r.keys2 is not None
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(live),
                    jax.tree_util.tree_leaves(rec_index)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_crash_mid_append_recovers_range_prefix(tmp_path):
    """A crash tearing a RANGE-bearing record leaves the durable prefix
    replayable: recovery lands on the window boundary before the tear."""
    d = str(tmp_path / "dur")
    with crash_at("wal.mid_append", hit=4):
        with pytest.raises(SimulatedCrash):
            _drive_durable(d)
    rec_index, replayed = recover(d)
    assert len(replayed) == 3            # windows 1-3 durable, 4 torn
    assert any((r.ops == RANGE).any() for r in replayed)
    # the repaired log accepts new range windows (writer reopens cleanly)
    live, _ = _drive_durable(d + "2")
    assert live is not None


def test_wal_v1_legacy_records_decode_with_zero_keys2(tmp_path):
    """Pre-range (PIW1) segments still decode; their keys2 lane is zeros."""
    occ, n_arr, batch = 3, 3, 8
    ops = np.array([INSERT, SEARCH, SEARCH], np.int32)
    keys = np.array([10, 20, 30], np.int32)
    vals = np.array([7, 0, 0], np.int32)
    payload = b"".join((ops.tobytes(), keys.tobytes(), vals.tobytes(),
                        np.array([1, 2, 3], np.int64).tobytes(),
                        np.array([0, 1, 2], np.int32).tobytes()))
    assert len(payload) == _payload_len(occ, n_arr, 4, version=1)
    head0 = _HEADER.pack(MAGIC_V1, 1, batch, occ, n_arr, len(payload), 0, 0)
    crc = zlib.crc32(payload, zlib.crc32(head0))
    blob = _HEADER.pack(MAGIC_V1, 1, batch, occ, n_arr, len(payload), 0,
                        crc) + payload
    wal_dir = tmp_path / "wal"
    wal_dir.mkdir()
    (wal_dir / f"wal-{1:016d}.seg").write_bytes(blob)
    (rec,) = read_wal(str(wal_dir))
    assert rec.keys2 is not None and not rec.keys2.any()
    w = record_window(rec)
    assert w.keys2 is not None and not w.keys2.any()
    # a v2 writer resumes a v1 log and the mixed log reads back in order
    wr = WalWriter(str(wal_dir))
    assert wr.last_seq == 1
    wr.append(record_window(rec_with_range()))
    wr.close()
    recs = read_wal(str(wal_dir))
    assert [r.seq for r in recs] == [1, 2]
    assert recs[1].keys2.any()


def rec_with_range():
    """A WalRecord-shaped window carrying one RANGE op (seq unset)."""
    from repro.pipeline.wal import WalRecord
    return WalRecord(seq=2, batch=8,
                     ops=np.array([RANGE], np.int32),
                     keys=np.array([5], np.int32),
                     vals=np.array([0], np.int32),
                     qids=np.array([9], np.int64),
                     slots=np.array([0], np.int32),
                     keys2=np.array([50], np.int32))


def test_group_commit_amortizes_fsync_and_bounds_frontier(tmp_path):
    """Under fsync='interval', the durable frontier advances every
    group_commit appends even when the time interval never elapses."""
    from repro.pipeline.collector import Window
    wr = WalWriter(str(tmp_path / "wal"), fsync="interval",
                   fsync_interval=1e9, group_commit=3)
    frontier = []
    for i in range(7):
        sent = np.iinfo(np.int32).max
        w = Window(ops=np.full(4, SEARCH, np.int32),
                   keys=np.full(4, sent, np.int32),
                   vals=np.zeros(4, np.int32), occupancy=0, qids=[],
                   slots=np.zeros(0, np.int32), t_open=0.0,
                   t_enq=np.zeros(0), trigger="flush")
        frontier.append((wr.append(w), wr.durable_seq))
    assert frontier == [(1, 0), (2, 0), (3, 3), (4, 3), (5, 3), (6, 6),
                        (7, 6)]
    assert wr.n_fsyncs == 2
    wr.close()                           # final close syncs the tail
    assert wr.durable_seq == 7
    with pytest.raises(ValueError, match="group_commit"):
        WalWriter(str(tmp_path / "wal2"), group_commit=0)


# ---------------------------------------------------------------------------
# workload + shed ladder
# ---------------------------------------------------------------------------

def test_workload_scan_mix_validation_and_shape():
    keys = np.arange(0, 100_000, 7, dtype=np.int32)
    acfg = ArrivalConfig(n_arrivals=4000, range_frac=0.25, span_min=2,
                         span_max=50, seed=9)
    stream = make_arrivals(acfg, data_mod.YCSBConfig(write_ratio=0.1),
                           keys)
    is_r = stream.ops == RANGE
    frac = np.count_nonzero(is_r) / len(stream)
    assert 0.2 < frac < 0.3
    spans = stream.keys2[is_r].astype(np.int64) - stream.keys[is_r] + 1
    assert spans.min() >= 2 and spans.max() <= 50
    assert not stream.keys2[~is_r].any()
    # clamping mirrors hot_frac; bad span geometry raises like hot_keys
    assert ArrivalConfig(range_frac=1.7).range_frac == 1.0
    assert ArrivalConfig(range_frac=-0.5).range_frac == 0.0
    with pytest.raises(ValueError, match="span"):
        ArrivalConfig(span_min=0)
    with pytest.raises(ValueError, match="span"):
        ArrivalConfig(span_min=10, span_max=5)
    # range_frac=0 keeps the point-only contract (keys2 is None)
    assert make_arrivals(ArrivalConfig(n_arrivals=64),
                         data_mod.YCSBConfig(), keys).keys2 is None


def test_shed_ladder_ranges_before_searches():
    """Ladder order: subsumed ranges < dup searches < all ranges < all
    searches < writes; read-only mode keeps serving ranges (reads)."""
    cfg = OverloadConfig()

    class FakeRes:
        def __init__(self, f):
            self.pending_fill = f

    ops = np.array([SEARCH, SEARCH, RANGE, RANGE, INSERT], np.int32)
    dup = np.array([False, True, False, False, False])
    cov = np.array([False, False, False, True, False])

    def at(p):
        a = AdmissionController(cfg)
        a.observe(FakeRes(p))
        return a.plan(ops, dup, covered=cov)

    keep, m = at(0.45)
    assert m[SHED_RANGE_SUB].tolist() == [0, 0, 0, 1, 0]
    assert keep.tolist() == [1, 1, 1, 0, 1]
    keep, m = at(0.6)
    assert m[SHED_SEARCH_DUP].tolist() == [0, 1, 0, 0, 0]
    assert keep.tolist() == [1, 0, 1, 0, 1]
    keep, m = at(0.75)
    assert m[SHED_RANGE].tolist() == [0, 0, 1, 1, 0]
    assert not m[SHED_RANGE_SUB].any()
    assert keep.tolist() == [1, 0, 0, 0, 1]
    keep, m = at(0.85)
    assert m[SHED_SEARCH].tolist() == [1, 1, 0, 0, 0]
    assert keep.tolist() == [0, 0, 0, 0, 1]
    keep, m = at(0.99)
    assert m[SHED_WRITE].tolist() == [0, 0, 0, 0, 1]
    assert not keep.any()
    keep, _ = AdmissionController(cfg).plan(ops, dup, covered=cov,
                                            read_only=True)
    assert keep.tolist() == [1, 1, 1, 1, 0]
    with pytest.raises(ValueError, match="range_sub"):
        OverloadConfig(shed_range_sub_at=0.6, shed_dup_at=0.5)
    with pytest.raises(ValueError, match="range"):
        OverloadConfig(shed_range_at=0.9, shed_search_at=0.8)
