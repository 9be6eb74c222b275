"""Query pipeline: workload → collector → dispatcher vs the RefIndex oracle.

The pipeline's correctness contract: replaying an interleaved arrival
stream through collection windows (with coalescing, deadline-triggered
short batches and double-buffered dispatch) must produce exactly the
per-query results and final index state of a sequential, arrival-order
replay against ``core.ref.RefIndex``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DELETE, INSERT, SEARCH, PIConfig, RefIndex, build,
                        build_sharded, rebuild)
from repro.analysis.runtime import trace_guard
from repro.core import index as pi_index
from repro.pipeline import (ArrivalConfig, Collector, DispatchOverflowError,
                            Dispatcher, PendingOverflowError, PipelineMetrics,
                            TRIGGER_DEADLINE, TRIGGER_SIZE, WindowConfig,
                            make_arrivals)
from repro import data as data_mod


# ---------------------------------------------------------------------------
# replay harness
# ---------------------------------------------------------------------------

def replay_stream(disp, col, t, ops, keys, vals):
    """Push a whole stream through collector+dispatcher; qid → (found, val)."""
    results = {}

    def drain(retired):
        for r in retired:
            results.update(r.per_arrival())

    for i in range(len(ops)):
        while not col.offer(float(t[i]), int(ops[i]), int(keys[i]),
                            int(vals[i]), i):
            drain(disp.submit(col.take(float(t[i]))))
    tail = col.take()
    if tail is not None:
        drain(disp.submit(tail))
    drain(disp.flush())
    return results


def check_against_oracle(results, ref_results, ops):
    for i in range(len(ops)):
        found, val = results[i]
        if ops[i] == SEARCH:
            assert (val if found else None) == ref_results[i], f"query {i}"
        elif ops[i] == DELETE:
            assert found == (ref_results[i] is not None), f"delete {i}"


def final_pairs(index):
    """Live (key, val) dict of a PIIndex after folding the pending buffer.

    Uses the occupancy-based ``live_items`` (the segmented gapped storage
    has no dense ``[:n]`` prefix) and checks the layout invariants on the
    folded state while it's at it.
    """
    fin = rebuild(index)
    assert pi_index.validate_layout(fin)
    k, v = pi_index.live_items(fin)
    return dict(zip(k.tolist(), v.tolist()))


def make_stream(n=600, key_space=40, seed=0):
    """Interleaved ops over few keys: duplicates guaranteed to straddle
    windows.  Times alternate dense bursts (size trigger) with sparse
    stretches (deadline trigger)."""
    rng = np.random.default_rng(seed)
    ops = rng.integers(0, 3, n).astype(np.int32)
    keys = rng.integers(0, key_space, n).astype(np.int32)
    vals = rng.integers(0, 1000, n).astype(np.int32)
    # structured bursts: 40 dense arrivals (fills a 32-slot window well
    # inside the deadline → size trigger) then 5 sparse ones (deadline)
    block = np.concatenate([np.full(40, 0.01), np.full(5, 3.0)])
    gaps = np.tile(block, n // len(block) + 1)[:n]
    return np.cumsum(gaps), ops, keys, vals


def seeded_index(cfg, key_space=40, n0=20, seed=1):
    rng = np.random.default_rng(seed)
    keys0 = rng.choice(key_space, n0, replace=False).astype(np.int32)
    vals0 = rng.integers(0, 1000, n0).astype(np.int32)
    idx = build(cfg, jnp.asarray(keys0), jnp.asarray(vals0))
    return idx, RefIndex.build(keys0, vals0)


# ---------------------------------------------------------------------------
# oracle replay (the tentpole contract)
# ---------------------------------------------------------------------------

def test_pipeline_matches_oracle_replay():
    cfg = PIConfig(capacity=256, pending_capacity=128, fanout=4)
    idx, ref = seeded_index(cfg)
    t, ops, keys, vals = make_stream()
    mets = PipelineMetrics()
    col = Collector(WindowConfig(batch=32, deadline=5.0, coalesce=True))
    disp = Dispatcher(idx, depth=2, metrics=mets, clock=lambda: 0.0)

    results = replay_stream(disp, col, t, ops, keys, vals)
    check_against_oracle(results, ref.execute(ops, keys, vals), ops)
    assert final_pairs(disp.index) == ref.data

    # the stream must actually have exercised the policy surface
    assert TRIGGER_SIZE in mets.triggers, "no size-triggered window"
    assert TRIGGER_DEADLINE in mets.triggers, "no deadline-triggered window"
    s = mets.summary()
    assert s["coalesced"] > 0, "no duplicate SEARCH was coalesced"
    assert s["arrivals"] == len(ops)
    assert s["executed_queries"] < s["arrivals"]


@pytest.mark.parametrize("coalesce", [False, True])
def test_depth_is_semantics_free(coalesce):
    """depth 0 (sync) and depth 3 (deep double-buffer) agree bit-for-bit."""
    cfg = PIConfig(capacity=256, pending_capacity=128, fanout=4)
    t, ops, keys, vals = make_stream(seed=7)
    outs = []
    for depth in (0, 3):
        idx, _ = seeded_index(cfg)
        col = Collector(WindowConfig(batch=32, deadline=5.0,
                                     coalesce=coalesce))
        disp = Dispatcher(idx, depth=depth, clock=lambda: 0.0)
        results = replay_stream(disp, col, t, ops, keys, vals)
        outs.append((results, final_pairs(disp.index)))
    assert outs[0] == outs[1]


def test_sharded_dispatch_matches_oracle():
    """Windows routed through the fence-partitioned executor == oracle."""
    cfg = PIConfig(capacity=256, pending_capacity=128, fanout=4)
    rng = np.random.default_rng(3)
    keys0 = rng.choice(40, 20, replace=False).astype(np.int32)
    vals0 = rng.integers(0, 1000, 20).astype(np.int32)
    mesh = jax.make_mesh((1,), ("data",))
    state = build_sharded(cfg, 1, keys0, vals0, mesh=mesh)
    ref = RefIndex.build(keys0, vals0)
    t, ops, keys, vals = make_stream(n=300, seed=5)
    col = Collector(WindowConfig(batch=32, deadline=5.0))
    disp = Dispatcher(state, mesh=mesh, depth=1, clock=lambda: 0.0)
    results = replay_stream(disp, col, t, ops, keys, vals)
    check_against_oracle(results, ref.execute(ops, keys, vals), ops)
    shard0 = jax.tree.map(lambda x: x[0], disp.index.shards)
    assert final_pairs(shard0) == ref.data


def test_sharded_dispatch_surfaces_routing_drops():
    """A fence bucket overflowing its send capacity must raise, not lose
    queries silently — while harmless padding drops must NOT raise."""
    cfg = PIConfig(capacity=256, pending_capacity=128, fanout=4)
    keys0 = np.arange(0, 64, 2, dtype=np.int32)
    mesh = jax.make_mesh((1,), ("data",))
    state = build_sharded(cfg, 1, keys0, keys0, mesh=mesh)
    # capacity_factor 0.25: a full 32-slot window offers 32 queries to the
    # single shard but only ceil(32*0.25)=8 survive routing
    disp = Dispatcher(state, mesh=mesh, depth=0, capacity_factor=0.25,
                      clock=lambda: 0.0)
    col = Collector(WindowConfig(batch=32, coalesce=False))
    for i in range(32):
        assert col.offer(float(i), SEARCH, int(keys0[i % len(keys0)]), 0, i)
    with pytest.raises(DispatchOverflowError, match="fence routing"):
        disp.submit(col.take())

    # mostly-padding short batch under the same tight capacity: the pads
    # overflow the bucket, the real queries survive → no error
    state2 = build_sharded(cfg, 1, keys0, keys0, mesh=mesh)
    disp2 = Dispatcher(state2, mesh=mesh, depth=0, capacity_factor=0.25,
                       clock=lambda: 0.0)
    col2 = Collector(WindowConfig(batch=32, coalesce=False))
    for i in range(4):
        assert col2.offer(float(i), SEARCH, int(keys0[i]), 0, i)
    (res,) = disp2.submit(col2.take())
    assert res.per_arrival() == {i: (True, int(keys0[i])) for i in range(4)}


def test_sharded_dispatch_requires_mesh():
    cfg = PIConfig(capacity=64, pending_capacity=32, fanout=4)
    state = build_sharded(cfg, 1, np.arange(4, dtype=np.int32),
                          np.arange(4, dtype=np.int32),
                          mesh=jax.make_mesh((1,), ("data",)))
    with pytest.raises(ValueError, match="mesh"):
        Dispatcher(state)


# ---------------------------------------------------------------------------
# rebuild-path oracle replay (segmented two-tier rebuild)
# ---------------------------------------------------------------------------

def test_rebuild_with_tombstoned_pending_entries():
    """Keys inserted then deleted again before any rebuild leave tombstoned
    pending slots; both rebuild tiers must drop them, not resurrect them."""
    cfg = PIConfig(capacity=256, pending_capacity=128, fanout=4)
    idx, ref = seeded_index(cfg)
    rng = np.random.default_rng(11)
    newk = (100 + rng.choice(100, 24, replace=False)).astype(np.int32)
    stream_ops, stream_keys, stream_vals = [], [], []
    for i, k in enumerate(newk):
        stream_ops += [INSERT, DELETE] if i % 2 else [INSERT]
        stream_keys += [k, k] if i % 2 else [k]
        stream_vals += [i, 0] if i % 2 else [i]
    ops = np.array(stream_ops, np.int32)
    keys = np.array(stream_keys, np.int32)
    vals = np.array(stream_vals, np.int32)
    t = np.arange(len(ops), dtype=np.float64) * 0.01
    col = Collector(WindowConfig(batch=8, deadline=5.0))
    disp = Dispatcher(idx, depth=1, clock=lambda: 0.0)
    results = replay_stream(disp, col, t, ops, keys, vals)
    check_against_oracle(results, ref.execute(ops, keys, vals), ops)
    assert final_pairs(disp.index) == ref.data


def test_rebuild_with_pending_deletes_of_storage_keys():
    """Deletes of built keys ride as storage tombstones across windows;
    rebuilds (incremental: only in dirty segments) must compact them."""
    cfg = PIConfig(capacity=256, pending_capacity=128, fanout=4,
                   rebuild_frac=0.05)  # trip rebuilds often
    idx, ref = seeded_index(cfg, key_space=40, n0=30)
    rng = np.random.default_rng(13)
    built = np.array(sorted(ref.data), np.int32)
    dels = rng.choice(built, 20, replace=False).astype(np.int32)
    fresh = (200 + np.arange(10)).astype(np.int32)
    ops = np.concatenate([np.full(20, DELETE), np.full(10, INSERT),
                          np.full(20, SEARCH)]).astype(np.int32)
    keys = np.concatenate([dels, fresh, dels]).astype(np.int32)
    vals = np.concatenate([np.zeros(20), np.arange(10),
                           np.zeros(20)]).astype(np.int32)
    t = np.arange(len(ops), dtype=np.float64) * 0.01
    col = Collector(WindowConfig(batch=8, deadline=5.0, coalesce=False))
    disp = Dispatcher(idx, depth=1, clock=lambda: 0.0)
    results = replay_stream(disp, col, t, ops, keys, vals)
    check_against_oracle(results, ref.execute(ops, keys, vals), ops)
    assert final_pairs(disp.index) == ref.data


def test_back_to_back_rebuilds_across_sealed_windows():
    """An aggressive threshold forces a rebuild after nearly every sealed
    window; the replay must stay bit-faithful to the oracle through many
    consecutive incremental/full rebuilds, and the layout invariants must
    hold on the final state."""
    cfg = PIConfig(capacity=256, pending_capacity=128, fanout=4,
                   rebuild_frac=0.01)
    idx, ref = seeded_index(cfg)
    t, ops, keys, vals = make_stream(n=450, seed=21)
    mets = PipelineMetrics()
    col = Collector(WindowConfig(batch=16, deadline=5.0))
    disp = Dispatcher(idx, depth=2, metrics=mets, clock=lambda: 0.0)
    results = replay_stream(disp, col, t, ops, keys, vals)
    check_against_oracle(results, ref.execute(ops, keys, vals), ops)
    assert final_pairs(disp.index) == ref.data
    assert mets.n_rebuilds >= 5, "threshold never tripped — test is vacuous"
    assert pi_index.validate_layout(disp.index)


def test_incremental_tier_taken_under_localized_churn():
    """A window of clustered inserts on a large index must take the
    incremental tier (visible in the metrics), and the post-rebuild state
    must match a forced full repack key-for-key."""
    cfg = PIConfig(capacity=4096, pending_capacity=256, fanout=4,
                   rebuild_frac=0.01)
    rng = np.random.default_rng(17)
    keys0 = rng.choice(1_000_000, 3000, replace=False).astype(np.int32)
    vals0 = np.arange(3000, dtype=np.int32)
    idx = build(cfg, jnp.asarray(keys0), jnp.asarray(vals0))
    # clustered churn: all new keys land in a narrow key range
    newk = np.setdiff1d((500_000 + np.arange(64) * 3).astype(np.int32),
                        keys0)[:48].astype(np.int32)
    ops = np.full(len(newk), INSERT, np.int32)
    t = np.arange(len(ops), dtype=np.float64) * 0.01
    mets = PipelineMetrics()
    col = Collector(WindowConfig(batch=64, deadline=5.0))
    disp = Dispatcher(idx, depth=0, metrics=mets, clock=lambda: 0.0)
    replay_stream(disp, col, t, ops, newk, np.arange(len(newk), dtype=np.int32))
    assert mets.n_rebuilds >= 1
    assert mets.n_rebuilds_incremental >= 1, \
        "localized churn should take the segmented incremental tier"
    assert pi_index.validate_layout(disp.index)
    want = dict(zip(np.concatenate([keys0, newk]).tolist(),
                    np.concatenate([vals0,
                                    np.arange(len(newk))]).tolist()))
    assert final_pairs(disp.index) == want


# ---------------------------------------------------------------------------
# bulk admission (offer_many): windows must be bit-identical to the offer loop
# ---------------------------------------------------------------------------

def windows_sequential(col, t, ops, keys, vals):
    """The driver loop offer_many is defined against; list of sealed
    windows (the residual open window stays in the collector)."""
    wins = []
    for i in range(len(ops)):
        while not col.offer(float(t[i]), int(ops[i]), int(keys[i]),
                            int(vals[i]), i):
            wins.append(col.take(float(t[i])))
    return wins


def assert_window_identical(a, b):
    assert a.trigger == b.trigger
    assert a.occupancy == b.occupancy
    assert a.ops.dtype == b.ops.dtype and np.array_equal(a.ops, b.ops)
    assert a.keys.dtype == b.keys.dtype and np.array_equal(a.keys, b.keys)
    assert a.vals.dtype == b.vals.dtype and np.array_equal(a.vals, b.vals)
    assert a.qids == b.qids
    assert a.slots.dtype == b.slots.dtype and np.array_equal(a.slots, b.slots)
    assert a.t_open == b.t_open
    assert np.array_equal(a.t_enq, b.t_enq)


def bulk_stream(n, key_space, write_ratio, seed, gap_choices):
    rng = np.random.default_rng(seed)
    ops = np.where(rng.random(n) < write_ratio,
                   rng.integers(1, 3, n), 0).astype(np.int32)
    keys = rng.integers(0, key_space, n).astype(np.int32)
    vals = rng.integers(0, 1000, n).astype(np.int32)
    t = np.cumsum(rng.choice(gap_choices, n))
    return t, ops, keys, vals


@pytest.mark.parametrize("coalesce", [False, True])
@pytest.mark.parametrize("deadline", [np.inf, 0.5])
@pytest.mark.parametrize("write_ratio", [0.0, 0.4])
@pytest.mark.parametrize("key_space", [3, 500])
def test_offer_many_equivalent_to_offer_loop(coalesce, deadline,
                                             write_ratio, key_space):
    """Bulk ≡ sequential across coalescing × deadline splits × op mixes,
    for whole-run, chunked, and scalar-interleaved admission."""
    t, ops, keys, vals = bulk_stream(500, key_space, write_ratio, seed=11,
                                     gap_choices=[0.0, 0.01, 1.0])
    cfg = WindowConfig(batch=32, deadline=deadline, coalesce=coalesce)
    qids = np.arange(len(ops))

    ref_col = Collector(cfg)
    ref_wins = windows_sequential(ref_col, t, ops, keys, vals)
    # a read-only few-key coalescing stream with no deadline legitimately
    # never seals (3 slots serve everything) — the residual-window compare
    # below still exercises equivalence there
    if not (coalesce and write_ratio == 0.0 and key_space == 3
            and deadline == np.inf):
        assert ref_wins, "stream too tame: no window ever sealed"

    # whole run in one call
    col = Collector(cfg)
    n_adm, wins = col.offer_many(t, ops, keys, vals, qids)
    assert n_adm == len(ops)
    assert len(wins) == len(ref_wins)
    for a, b in zip(ref_wins, wins):
        assert_window_identical(a, b)

    # chunked calls (residual open-window state carried between calls)
    col2 = Collector(cfg)
    wins2 = []
    for s in range(0, len(ops), 13):
        e = min(len(ops), s + 13)
        _, ws = col2.offer_many(t[s:e], ops[s:e], keys[s:e], vals[s:e],
                                qids[s:e])
        wins2 += ws
    for a, b in zip(ref_wins, wins2):
        assert_window_identical(a, b)

    # scalar offers interleaved after a bulk prefix (lazy carry sync)
    col3 = Collector(cfg)
    half = len(ops) // 2
    _, wins3 = col3.offer_many(t[:half], ops[:half], keys[:half],
                               vals[:half], qids[:half])
    wins3 = list(wins3)
    for i in range(half, len(ops)):
        while not col3.offer(float(t[i]), int(ops[i]), int(keys[i]),
                             int(vals[i]), i):
            wins3.append(col3.take(float(t[i])))
    for a, b in zip(ref_wins, wins3):
        assert_window_identical(a, b)

    # identical residual windows too
    tails = [c.take() for c in (ref_col, col, col2, col3)]
    assert all((x is None) == (tails[0] is None) for x in tails)
    if tails[0] is not None:
        for x in tails[1:]:
            assert_window_identical(tails[0], x)


def test_offer_many_oracle_replay_through_dispatcher_run():
    """Dispatcher.run (bulk admission + double-buffered submit) == oracle."""
    cfg = PIConfig(capacity=256, pending_capacity=128, fanout=4)
    idx, ref = seeded_index(cfg)
    t, ops, keys, vals = make_stream()
    disp = Dispatcher(idx, depth=2, clock=lambda: 0.0)

    class _Stream:
        pass

    stream = _Stream()
    stream.t, stream.ops, stream.keys, stream.vals = t, ops, keys, vals
    results = {}
    for res in disp.run(stream, WindowConfig(batch=32, deadline=5.0,
                                             coalesce=True)):
        results.update(res.per_arrival())
    check_against_oracle(results, ref.execute(ops, keys, vals), ops)
    assert final_pairs(disp.index) == ref.data


def test_offer_many_matches_scalar_replay_results():
    """Same per-query results whether the harness admits one arrival at a
    time or in bulk chunks (replay-level equivalence, depth 1)."""
    cfg = PIConfig(capacity=256, pending_capacity=128, fanout=4)
    t, ops, keys, vals = make_stream(seed=9)
    outs = []
    for bulk in (False, True):
        idx, _ = seeded_index(cfg)
        col = Collector(WindowConfig(batch=32, deadline=5.0))
        disp = Dispatcher(idx, depth=1, clock=lambda: 0.0)
        if bulk:
            results = {}
            qids = np.arange(len(ops))
            for s in range(0, len(ops), 50):
                e = min(len(ops), s + 50)
                _, wins = col.offer_many(t[s:e], ops[s:e], keys[s:e],
                                         vals[s:e], qids[s:e])
                for w in wins:
                    for r in disp.submit(w):
                        results.update(r.per_arrival())
            tail = col.take()
            if tail is not None:
                for r in disp.submit(tail):
                    results.update(r.per_arrival())
            for r in disp.flush():
                results.update(r.per_arrival())
        else:
            results = replay_stream(disp, col, t, ops, keys, vals)
        outs.append((results, final_pairs(disp.index)))
    assert outs[0] == outs[1]


def test_offer_many_atomic_on_sentinel():
    """A raising offer_many admits nothing — not even the valid prefix."""
    col = Collector(WindowConfig(batch=8, deadline=1.0))
    assert col.offer(0.0, SEARCH, 5, 0, 0)
    sent = np.iinfo(np.int32).max
    t = np.array([0.1, 0.2, 0.3])
    keys = np.array([7, sent, 9], np.int32)
    zeros = np.zeros(3, np.int32)
    with pytest.raises(ValueError, match="sentinel"):
        col.offer_many(t, zeros, keys, zeros, np.arange(3))
    assert col.pending == 1  # only the pre-existing arrival
    w = col.take()
    assert w.occupancy == 1 and w.qids == [0]


def test_offer_many_rejects_bad_shapes_and_times():
    col = Collector(WindowConfig(batch=8))
    zeros = np.zeros(3, np.int32)
    with pytest.raises(ValueError, match="shape"):
        col.offer_many(np.zeros(2), zeros, zeros, zeros, np.arange(3))
    with pytest.raises(ValueError, match="nondecreasing"):
        col.offer_many(np.array([1.0, 0.5, 2.0]), zeros, zeros, zeros,
                       np.arange(3))
    assert col.pending == 0


def test_offer_many_empty_run_is_noop():
    col = Collector(WindowConfig(batch=8))
    e = np.array([], np.int32)
    assert col.offer_many(np.array([], np.float64), e, e, e, e) == (0, [])
    assert col.pending == 0 and col.take() is None


# ---------------------------------------------------------------------------
# collector policy
# ---------------------------------------------------------------------------

def test_collector_size_trigger_and_backpressure():
    col = Collector(WindowConfig(batch=4, coalesce=False))
    for i in range(4):
        assert col.offer(float(i), SEARCH, 10 + i, 0, i)
    # full: refuses (backpressure), nothing dropped
    assert not col.offer(4.0, SEARCH, 99, 0, 4)
    w = col.take()
    assert w.trigger == TRIGGER_SIZE
    assert w.occupancy == 4 and w.n_arrivals == 4
    # the refused arrival was never admitted; re-offering now succeeds
    assert col.offer(4.0, SEARCH, 99, 0, 4)
    assert col.pending == 1


def test_collector_deadline_trigger_short_batch():
    col = Collector(WindowConfig(batch=8, deadline=1.0))
    assert col.offer(0.0, INSERT, 5, 50, 0)
    assert col.offer(0.5, SEARCH, 5, 0, 1)
    # past the deadline: refuse, seal, short batch padded to shape 8
    assert not col.offer(1.5, SEARCH, 6, 0, 2)
    assert col.ready(1.5)
    w = col.take(1.5)
    assert w.trigger == TRIGGER_DEADLINE
    assert w.occupancy == 2
    assert w.ops.shape == (8,)
    sent = np.iinfo(np.int32).max
    assert (w.keys[2:] == sent).all() and (w.ops[2:] == SEARCH).all()


def test_collector_coalesces_read_runs_only():
    col = Collector(WindowConfig(batch=8, coalesce=True))
    assert col.offer(0.0, SEARCH, 7, 0, 0)   # slot 0
    assert col.offer(0.1, SEARCH, 7, 0, 1)   # coalesced into slot 0
    assert col.offer(0.2, INSERT, 7, 42, 2)  # write: slot 1, breaks the run
    assert col.offer(0.3, SEARCH, 7, 0, 3)   # post-write read: new slot 2
    assert col.offer(0.4, SEARCH, 7, 0, 4)   # coalesced into slot 2
    w = col.take()
    assert w.occupancy == 3
    assert w.slots.tolist() == [0, 0, 1, 2, 2]


def test_collector_rejects_sentinel_key():
    col = Collector(WindowConfig(batch=4))
    with pytest.raises(ValueError, match="sentinel"):
        col.offer(0.0, SEARCH, np.iinfo(np.int32).max, 0, 0)


def test_rejected_sentinel_leaves_no_stale_deadline():
    """Regression: offer used to set _t_open before validating the key, so
    a rejected sentinel arrival on an empty window left a stale open
    timestamp and the next real window could seal short on a phantom
    deadline expiry."""
    col = Collector(WindowConfig(batch=8, deadline=1.0))
    with pytest.raises(ValueError, match="sentinel"):
        col.offer(0.0, SEARCH, np.iinfo(np.int32).max, 0, 0)
    # collector unchanged: nothing admitted, no open window
    assert col.pending == 0
    assert col.take() is None
    # a real window opening much later must NOT be expired by the ghost
    assert col.offer(100.0, SEARCH, 1, 0, 0)
    assert col.offer(100.5, SEARCH, 2, 0, 1), \
        "phantom deadline expiry from the rejected arrival's timestamp"
    assert col.pending == 2
    w = col.take()
    assert w.occupancy == 2 and w.t_open == 100.0


def test_collector_empty_take_is_none():
    assert Collector(WindowConfig(batch=4)).take() is None


# ---------------------------------------------------------------------------
# overflow surfacing (data loss must be loud)
# ---------------------------------------------------------------------------

def _overflowing_window_setup():
    # pending capacity 8, one window of 32 distinct net inserts: the core
    # clamps pn and raises its overflow flag — the pipeline must escalate
    cfg = PIConfig(capacity=64, pending_capacity=8, fanout=4)
    idx = build(cfg, jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32))
    col = Collector(WindowConfig(batch=32))
    for i in range(32):
        assert col.offer(float(i), INSERT, 100 + i, i, i)
    return idx, col.take()


def test_dispatcher_raises_on_pending_overflow():
    idx, window = _overflowing_window_setup()
    disp = Dispatcher(idx, depth=0)
    with pytest.raises(PendingOverflowError):
        disp.submit(window)


def test_dispatcher_overflow_check_is_optional():
    idx, window = _overflowing_window_setup()
    disp = Dispatcher(idx, depth=0, check_overflow=False)
    (res,) = disp.submit(window)  # policy off: no raise, results delivered
    assert res.found.shape == (32,)


def test_failed_retirement_poisons_dispatcher():
    """Regression: a retirement failure used to pop and lose the failing
    window while the index already reflected the lossy execute — a caller
    catching the error could keep submitting on corrupted state.  Now the
    failure is latched, the undrained windows ride on the exception, and
    further submit/flush re-raise."""
    idx, window = _overflowing_window_setup()
    disp = Dispatcher(idx, depth=0)
    with pytest.raises(PendingOverflowError) as exc:
        disp.submit(window)
    # the failing window is surfaced, not lost
    assert exc.value.windows == [window]
    assert disp.poisoned is exc.value
    # the dispatcher refuses to continue on corrupted state
    col = Collector(WindowConfig(batch=32))
    assert col.offer(0.0, SEARCH, 5, 0, 0)
    with pytest.raises(PendingOverflowError):
        disp.submit(col.take())
    with pytest.raises(PendingOverflowError):
        disp.flush()


def test_poisoned_flush_surfaces_all_inflight_windows():
    """With depth > 0 the failure appears at flush; every queued window —
    failing one first — must ride on the exception."""
    idx, window = _overflowing_window_setup()
    disp = Dispatcher(idx, depth=2)
    assert disp.submit(window) == []      # queued, not yet retired
    col = Collector(WindowConfig(batch=32))
    assert col.offer(0.0, SEARCH, 200, 0, 0)
    second = col.take()
    assert disp.submit(second) == []
    with pytest.raises(PendingOverflowError) as exc:
        disp.flush()
    assert exc.value.windows == [window, second]
    with pytest.raises(PendingOverflowError):
        disp.flush()


# ---------------------------------------------------------------------------
# workload generators
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("process", ["poisson", "bursty", "diurnal",
                                     "hotkey"])
def test_arrival_streams_are_well_formed(process):
    keys = np.arange(1000, dtype=np.int32)
    acfg = ArrivalConfig(process=process, rate=1e4, n_arrivals=2048)
    stream = make_arrivals(acfg, data_mod.YCSBConfig(write_ratio=0.2), keys)
    assert len(stream) == 2048
    assert (np.diff(stream.t) >= 0).all(), "times must be nondecreasing"
    assert stream.t[-1] > 0
    assert set(np.unique(stream.ops)) <= {SEARCH, INSERT}
    # mean rate within 2x of nominal (loose: modulated processes wander)
    mean_rate = len(stream) / stream.t[-1]
    assert 0.5 * acfg.rate < mean_rate < 2.0 * acfg.rate


def test_hotkey_stream_is_adversarially_skewed():
    keys = np.arange(1000, dtype=np.int32)
    acfg = ArrivalConfig(process="hotkey", n_arrivals=4096, hot_keys=4,
                         hot_frac=0.8)
    stream = make_arrivals(acfg, data_mod.YCSBConfig(), keys)
    _, counts = np.unique(stream.keys, return_counts=True)
    top4 = np.sort(counts)[-4:].sum()
    assert top4 > 0.7 * len(stream), "hot set should dominate the stream"


def test_unknown_process_rejected():
    with pytest.raises(ValueError, match="unknown arrival process"):
        ArrivalConfig(process="flat")


def test_hotkey_hot_set_larger_than_dataset_rejected():
    """Regression: hot_keys > len(keys) used to crash inside rng.choice
    with an opaque numpy error; it must be a clear config error."""
    keys = np.arange(8, dtype=np.int32)
    acfg = ArrivalConfig(process="hotkey", n_arrivals=64, hot_keys=9)
    with pytest.raises(ValueError, match="hot_keys <= len"):
        make_arrivals(acfg, data_mod.YCSBConfig(), keys)


def test_hot_frac_is_clamped():
    assert ArrivalConfig(process="hotkey", hot_frac=1.5).hot_frac == 1.0
    assert ArrivalConfig(process="hotkey", hot_frac=-0.2).hot_frac == 0.0
    with pytest.raises(ValueError, match="hot_keys"):
        ArrivalConfig(process="hotkey", hot_keys=0)
    # clamped to "everything hot": the whole stream hits the hot set
    keys = np.arange(1000, dtype=np.int32)
    acfg = ArrivalConfig(process="hotkey", n_arrivals=512, hot_keys=2,
                         hot_frac=2.0)
    stream = make_arrivals(acfg, data_mod.YCSBConfig(), keys)
    assert len(np.unique(stream.keys)) <= 2


# ---------------------------------------------------------------------------
# serving through the pipeline
# ---------------------------------------------------------------------------

def test_server_runs_from_one_execute_compilation():
    """The whole ycsb_serve-style workload = ONE compiled execute.

    Every scheduler tick is padded to the static tick_width by the
    collector, so admits/lookups/completes of any mix hit the same
    executable.  The counter increments once per *trace* of execute_impl.
    """
    from repro import optim
    from repro.configs import get_config, smoke
    from repro.launch import serve as serve_mod
    from repro.models import init_train_state

    cfg = smoke(get_config("phi3-mini-3.8b"))
    params, _ = init_train_state(cfg, optim.OptConfig(), jax.random.key(0))
    srv = serve_mod.Server(cfg, params, n_slots=4, max_len=32)
    rng = np.random.default_rng(0)
    reqs = [serve_mod.Request(rid=100 + i,
                              prompt=rng.integers(0, cfg.vocab, 4),
                              max_new=3) for i in range(6)]
    jax.clear_caches()  # fresh jit caches: the delta below counts traces
    base = pi_index.execute_trace_count()
    srv.admit(reqs[:4])
    done = set()
    for _ in range(12):
        done.update(srv.tick())
        if len(done) == 4:
            break
    srv.admit(reqs[4:])  # admit + lookup + complete ticks all happened
    assert done == {100, 101, 102, 103}
    trace_guard("core.execute").expect(
        base, 1, "server ticks (one shared compiled execute)")
    s = srv.pipeline_metrics.summary()
    assert s["arrivals"] == srv.queries_processed
    assert s["windows"] >= 3


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_latency_histogram_percentiles_are_ordered():
    from repro.pipeline import LatencyHistogram
    h = LatencyHistogram()
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-6.0, sigma=1.0, size=10_000)
    h.record(samples)
    p50, p95, p99 = (h.percentile(q) for q in (50, 95, 99))
    assert p50 <= p95 <= p99
    # within histogram resolution of the exact quantiles
    assert abs(np.log(p50) - np.log(np.quantile(samples, 0.5))) < 0.35
    assert h.count == 10_000


def test_empty_histogram_is_nan():
    from repro.pipeline import LatencyHistogram
    assert np.isnan(LatencyHistogram().percentile(50))
