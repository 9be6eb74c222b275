"""Kill-and-restore: the durability tier's acked-prefix contract.

For every named crash point, a pipeline driven to death mid-operation and
then recovered from disk must land on state **bit-identical** to a fresh
pipeline that executed exactly the recovered window prefix — and that
prefix must (a) contain every *acknowledged* window (``per_window`` fsync:
``append`` returning == acked), (b) be a prefix of the sealed sequence
(no holes, no reordering), and (c) never include a torn tail record.

The semantic layer reuses the query-pipeline oracle: the recovered index's
live pairs must equal a sequential ``RefIndex`` replay of the same durable
prefix.  Both the single-``PIIndex`` and the sharded path are covered at
every crash point.
"""
import contextlib
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import PIConfig, RefIndex, build, build_sharded
from repro.core import distributed as dist
from repro.pipeline import (Collector, Dispatcher, Durability,
                            PipelineMetrics, RecoveryError, Window,
                            WindowConfig, recover)
from repro import faults
from faultpoints import FAULT_POINTS, SimulatedCrash, crash_at
from test_query_pipeline import final_pairs

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

CFG = PIConfig(capacity=1024, pending_capacity=128, fanout=4)
KEY_SPACE = 40
KINDS = ("single", "sharded")


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

def seeded(kind):
    """Deterministic initial build (JAX build is bit-reproducible, so two
    calls give bit-identical seeds for the crashed and reference runs)."""
    rng = np.random.default_rng(5)
    keys0 = np.unique(rng.integers(1, KEY_SPACE, 25).astype(np.int32))
    vals0 = rng.integers(0, 1000, keys0.size).astype(np.int32)
    if kind == "sharded":
        mesh = jax.make_mesh((1,), ("data",))
        state = build_sharded(CFG, 1, keys0, vals0, mesh=mesh)
        return state, mesh, (keys0, vals0)
    idx = build(CFG, jnp.asarray(keys0), jnp.asarray(vals0))
    return idx, None, (keys0, vals0)


def mk_stream(n, seed):
    rng = np.random.default_rng(seed)
    return (np.arange(n, dtype=np.float64),
            rng.integers(0, 3, n).astype(np.int32),
            rng.integers(1, KEY_SPACE, n).astype(np.int32),
            rng.integers(0, 1000, n).astype(np.int32))


def copy_window(w: Window) -> Window:
    return Window(ops=w.ops.copy(), keys=w.keys.copy(), vals=w.vals.copy(),
                  occupancy=w.occupancy, qids=list(w.qids),
                  slots=w.slots.copy(), t_open=w.t_open,
                  t_enq=w.t_enq.copy(), trigger=w.trigger)


def trees_equal(a, b) -> bool:
    def unwrap(x):
        # ShardedPIIndex is not a registered pytree: compare its parts
        if isinstance(x, dist.ShardedPIIndex):
            return (x.shards, x.fences)
        return x
    la = jax.tree_util.tree_leaves(unwrap(a))
    lb = jax.tree_util.tree_leaves(unwrap(b))
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def drive(d, kind, *, crash_point=None, hit=1, snapshot_every=4,
          n=300, batch=16, fsync="per_window", seed=11):
    """Run a stream through a durable pipeline, optionally dying mid-way.

    Returns (sealed window copies in seal order, acked seq list, crashed?,
    metrics).  With ``per_window`` fsync, ``on_seal`` returning *is* the
    acknowledgment — the copy is taken before the WAL sees the window, so
    a crash inside ``append`` leaves the window sealed-but-unacked.
    """
    index, mesh, _ = seeded(kind)
    t, ops, keys, vals = mk_stream(n, seed)
    met = PipelineMetrics()
    sealed, acked = [], []
    crashed = False
    ctx = (crash_at(crash_point, hit) if crash_point
           else contextlib.nullcontext())
    try:
        with ctx:
            dur = Durability(d, index, fsync=fsync,
                             snapshot_every=snapshot_every, metrics=met)

            def hook(win):
                sealed.append(copy_window(win))
                acked.append(dur.on_seal(win))

            col = Collector(WindowConfig(batch=batch), on_seal=hook)
            disp = Dispatcher(index, mesh=mesh, depth=1, durability=dur)
            qids = np.arange(n)
            for s in range(0, n, batch):
                e = min(n, s + batch)
                _, sl = col.offer_many(t[s:e], ops[s:e], keys[s:e],
                                       vals[s:e], qids[s:e])
                for w in sl:
                    disp.submit(w)
            tail = col.take()
            if tail is not None:
                disp.submit(tail)
            disp.flush()
            dur.close()
    except SimulatedCrash:
        crashed = True
    return sealed, acked, crashed, met


def fresh_replay(kind, window_prefix):
    """The never-crashed reference: execute exactly ``window_prefix``."""
    index, mesh, _ = seeded(kind)
    disp = Dispatcher(index, mesh=mesh, depth=0)
    for w in window_prefix:
        disp.submit(copy_window(w))
    return disp.index


def ref_replay_pairs(kind, window_prefix):
    """Sequential RefIndex oracle over the same prefix, window by window
    (each window executes under the batch semantics, as live did)."""
    _, _, (keys0, vals0) = seeded(kind)
    ref = RefIndex.build(keys0, vals0)
    for w in window_prefix:
        occ = w.occupancy
        ref.execute(w.ops[:occ], w.keys[:occ], w.vals[:occ])
    return ref.data


def check_recovery_contract(d, kind, sealed, acked, crash_point):
    """The acked-prefix contract, shared by every crash-point test."""
    step = CheckpointManager(os.path.join(d, "ckpt")).latest_step()
    index, replayed = recover(d)
    assert [r.seq for r in replayed] == \
        list(range(step + 1, step + 1 + len(replayed)))
    n_applied = step + len(replayed)           # windows 1..n_applied
    acked_max = acked[-1] if acked else 0
    # (a) every acknowledged window survived
    assert n_applied >= acked_max
    # (b) the recovered set is a prefix of the sealed sequence
    assert n_applied <= len(sealed)
    if crash_point == "wal.mid_append":
        # (c) the torn record is excluded: recovery == acked, exactly
        assert n_applied == acked_max
    elif crash_point in ("wal.after_append", "wal.pre_sync"):
        # fully written but unsynced (pre_sync dies inside the fsync
        # itself — same on-disk class): standard WAL semantics allow the
        # one unacked suffix record to survive (it did — Python-level
        # death can't unwrite unbuffered bytes), never more
        assert n_applied <= acked_max + 1
    else:
        # ckpt crash points die inside snapshot(), after the window's
        # append acked — the whole sealed prefix is durable
        assert n_applied == acked_max == len(sealed)
    # bit-identical to never having crashed
    assert trees_equal(index, fresh_replay(kind, sealed[:n_applied]))
    return index, n_applied


# ---------------------------------------------------------------------------
# the crash-point matrix (the tentpole)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("point", FAULT_POINTS)
def test_crash_point_recovery(tmp_path, kind, point):
    # wal points fire once per append — hit 3 dies on window 3, before
    # the first periodic snapshot (recovery = initial snapshot + replay).
    # ckpt points fire once per save — hit 2 dies in the first periodic
    # snapshot (the initial step-0 snapshot is hit 1).
    hit = 3 if point.startswith("wal.") else 2
    d = str(tmp_path)
    sealed, acked, crashed, _ = drive(d, kind, crash_point=point, hit=hit)
    assert crashed, f"fault point {point} was never reached"
    index, n_applied = check_recovery_contract(d, kind, sealed, acked, point)
    assert n_applied > 0                       # the test isn't vacuous


@pytest.mark.parametrize("kind", KINDS)
def test_crash_after_snapshot_replays_only_tail(tmp_path, kind):
    """A crash past a periodic snapshot recovers from that snapshot plus a
    short WAL tail — not from the initial build."""
    d = str(tmp_path)
    sealed, acked, crashed, _ = drive(d, kind, crash_point="wal.mid_append",
                                      hit=7, snapshot_every=4)
    assert crashed
    step = CheckpointManager(os.path.join(d, "ckpt")).latest_step()
    assert step >= 4                           # periodic snapshot landed
    met = PipelineMetrics()
    index, replayed = recover(d, metrics=met)
    assert met.recovery_replayed == len(replayed) == 6 - step
    assert trees_equal(index, fresh_replay(kind, sealed[:6]))


def test_semantic_oracle_on_durable_prefix(tmp_path):
    """Recovered live pairs == sequential RefIndex replay of the prefix."""
    d = str(tmp_path)
    sealed, acked, crashed, _ = drive(d, "single",
                                      crash_point="wal.mid_append", hit=5)
    assert crashed
    index, n_applied = check_recovery_contract(d, "single", sealed, acked,
                                               "wal.mid_append")
    assert final_pairs(index) == ref_replay_pairs("single",
                                                  sealed[:n_applied])


def test_sharded_semantic_oracle(tmp_path):
    d = str(tmp_path)
    sealed, acked, crashed, _ = drive(d, "sharded",
                                      crash_point="wal.after_append", hit=4)
    assert crashed
    index, n_applied = check_recovery_contract(d, "sharded", sealed, acked,
                                               "wal.after_append")
    shard0 = jax.tree_util.tree_map(lambda x: x[0], index.shards)
    assert final_pairs(shard0) == ref_replay_pairs("sharded",
                                                   sealed[:n_applied])


# ---------------------------------------------------------------------------
# crash-free + resume paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_crashfree_roundtrip_is_bit_identical(tmp_path, kind):
    d = str(tmp_path)
    sealed, acked, crashed, met = drive(d, kind, snapshot_every=5)
    assert not crashed
    assert met.wal_appends == len(sealed) == acked[-1]
    assert met.wal_fsyncs >= met.wal_appends   # per_window acks every seal
    rmet = PipelineMetrics()
    index, replayed = recover(d, metrics=rmet)
    assert rmet.recovery_replayed == len(replayed)
    assert trees_equal(index, fresh_replay(kind, sealed))
    s = rmet.summary()
    assert s["recovery_replayed"] == len(replayed)


def test_recover_after_crash_then_resume(tmp_path):
    """recover → new Durability over the same dir → keep serving → the
    second recovery sees one continuous history (seq resumes, torn tail
    repaired, no replayed window lost or doubled)."""
    d = str(tmp_path)
    sealed, acked, crashed, _ = drive(d, "single",
                                      crash_point="wal.mid_append", hit=4)
    assert crashed
    index, replayed = recover(d)
    n1 = CheckpointManager(os.path.join(d, "ckpt")).latest_step() \
        + len(replayed)
    assert n1 == acked[-1]
    # second life: resume the log with the recovered index
    dur = Durability(d, index, fsync="per_window", snapshot_every=0)
    assert dur.wal.last_seq == n1              # seq continues, tear gone
    sealed2 = []

    def hook(win):
        sealed2.append(copy_window(win))
        dur.on_seal(win)

    col = Collector(WindowConfig(batch=16), on_seal=hook)
    disp = Dispatcher(index, depth=0, durability=dur)
    t, ops, keys, vals = mk_stream(120, seed=77)
    _, sl = col.offer_many(t, ops, keys, vals, np.arange(120))
    for w in sl:
        disp.submit(w)
    tail = col.take()
    if tail is not None:
        disp.submit(tail)
    disp.flush()
    dur.close()
    final = disp.index
    index2, replayed2 = recover(d)
    assert trees_equal(index2, final)
    assert trees_equal(index2,
                       fresh_replay("single", sealed[:n1] + sealed2))


def test_recover_empty_dir_raises(tmp_path):
    with pytest.raises(RecoveryError, match="durability.json"):
        recover(str(tmp_path))


def test_recover_without_snapshot_raises(tmp_path):
    """Metadata written but the initial snapshot never completed: nothing
    was ever acknowledged, and recovery says so loudly."""
    d = str(tmp_path)
    with crash_at("ckpt.mid_write", hit=1):
        with pytest.raises(SimulatedCrash):
            index, _, _ = seeded("single")
            Durability(d, index)
    with pytest.raises(RecoveryError, match="snapshot"):
        recover(d)


def test_fsync_off_recovery_still_prefix_consistent(tmp_path):
    """With fsync=off nothing is ever *guaranteed*, but what does survive
    a Python-level crash must still be a clean prefix."""
    d = str(tmp_path)
    sealed, acked, crashed, met = drive(d, "single",
                                        crash_point="wal.mid_append", hit=5,
                                        fsync="off")
    assert crashed
    assert met.wal_fsyncs == 0                 # nothing was ever guaranteed
    step = CheckpointManager(os.path.join(d, "ckpt")).latest_step()
    index, replayed = recover(d)
    n_applied = step + len(replayed)
    assert n_applied <= len(sealed)
    assert trees_equal(index, fresh_replay("single", sealed[:n_applied]))


# ---------------------------------------------------------------------------
# async snapshots (the serving path's non-stalling maybe_snapshot)
# ---------------------------------------------------------------------------

SNAP_SLEEP = 0.5  # how long each snapshot write is forced to take


@contextlib.contextmanager
def slow_ckpt_writes(delay: float = SNAP_SLEEP):
    """Stretch every snapshot write to ``delay`` seconds — in whichever
    thread performs it.  This is the probe that separates a blocking save
    (the triggering submit eats the delay) from a background one (the
    submit returns immediately; close() joins the writer later)."""
    def hook(point):
        if point == "ckpt.mid_write":
            time.sleep(delay)
    prev = faults.set_fault_hook(hook)
    try:
        yield
    finally:
        faults.set_fault_hook(prev)


def _drive_timed_snapshots(d, *, async_snapshots, n=96, batch=16,
                           snapshot_every=4):
    """Drive a durable pipeline under slow snapshot writes.

    Returns (per-seq submit wall times, sealed window copies, final
    index).  Geometry: 6 windows, exactly one periodic snapshot (seq 4) —
    the next multiple (8) is past the stream, so no later submit can
    stall joining the background save; only ``close()`` does.
    """
    index, _, _ = seeded("single")
    t, ops, keys, vals = mk_stream(n, seed=23)
    dur = Durability(d, index, fsync="per_window",
                     snapshot_every=snapshot_every,
                     async_snapshots=async_snapshots)
    sealed = []

    def hook(win):
        sealed.append(copy_window(win))
        dur.on_seal(win)

    col = Collector(WindowConfig(batch=batch), on_seal=hook)
    disp = Dispatcher(index, depth=0, durability=dur)
    times = {}

    def timed_submit(w):
        t0 = time.perf_counter()
        disp.submit(w)
        times[w.seq] = time.perf_counter() - t0

    with slow_ckpt_writes():
        for s in range(0, n, batch):
            e = min(n, s + batch)
            _, sl = col.offer_many(t[s:e], ops[s:e], keys[s:e],
                                   vals[s:e], np.arange(s, e))
            for w in sl:
                timed_submit(w)
        tail = col.take()
        if tail is not None:
            timed_submit(tail)
        disp.flush()
        dur.close()
    return times, sealed, disp.index


def test_async_snapshot_does_not_stall_the_serving_tick(tmp_path):
    """The satellite contract: with ``async_snapshots`` the submit that
    triggers a periodic snapshot returns without eating the write, while
    the blocking mode demonstrably stalls that same submit — and the
    background snapshot still lands intact (recovery is bit-identical)."""
    d_async = str(tmp_path / "async")
    times, sealed, final = _drive_timed_snapshots(d_async,
                                                  async_snapshots=True)
    assert times[4] < SNAP_SLEEP / 2, \
        f"snapshot-triggering submit stalled {times[4]:.3f}s in async mode"
    index, replayed = recover(d_async)
    assert trees_equal(index, final)
    assert trees_equal(index, fresh_replay("single", sealed))

    d_block = str(tmp_path / "block")
    times_b, _, _ = _drive_timed_snapshots(d_block, async_snapshots=False)
    assert times_b[4] >= SNAP_SLEEP, \
        "blocking mode should have eaten the snapshot write in submit"


def test_async_snapshot_error_surfaces_at_close_and_loses_nothing(tmp_path):
    """A background snapshot failure is latched, re-raised at the next
    wait point (close), and — because WAL truncation is deferred until a
    later save confirms the previous one landed — costs zero durability:
    the full tail still replays over the intact initial snapshot."""
    d = str(tmp_path)
    index, _, _ = seeded("single")
    t, ops, keys, vals = mk_stream(96, seed=29)
    # create first: the initial step-0 snapshot is blocking and must
    # succeed before the failing hook goes in
    dur = Durability(d, index, fsync="per_window", snapshot_every=4,
                     async_snapshots=True)
    sealed = []

    def seal_hook(win):
        sealed.append(copy_window(win))
        dur.on_seal(win)

    col = Collector(WindowConfig(batch=16), on_seal=seal_hook)
    disp = Dispatcher(index, depth=0, durability=dur)

    def fail_hook(point):
        if point == "ckpt.mid_write":
            raise SimulatedCrash(point)

    prev = faults.set_fault_hook(fail_hook)
    try:
        for s in range(0, 96, 16):
            _, sl = col.offer_many(t[s:s + 16], ops[s:s + 16],
                                   keys[s:s + 16], vals[s:s + 16],
                                   np.arange(s, s + 16))
            for w in sl:
                disp.submit(w)   # seq-4 snapshot fails in the background
        tail = col.take()
        if tail is not None:
            disp.submit(tail)
        disp.flush()
        with pytest.raises(SimulatedCrash):
            dur.close()
    finally:
        faults.set_fault_hook(prev)
    step = CheckpointManager(os.path.join(d, "ckpt")).latest_step()
    assert step == 0, "the failed background snapshot must not publish"
    index2, replayed = recover(d)
    assert len(replayed) == len(sealed)
    assert trees_equal(index2, fresh_replay("single", sealed))


# ---------------------------------------------------------------------------
# serving-path integration
# ---------------------------------------------------------------------------

def test_server_session_table_recovers(tmp_path):
    from repro import optim
    from repro.configs import get_config, smoke
    from repro.launch import serve as serve_mod
    from repro.models import init_train_state

    cfg = smoke(get_config("phi3-mini-3.8b"))
    params, _ = init_train_state(
        cfg, optim.OptConfig(lr=1e-3, warmup_steps=2, total_steps=50),
        jax.random.key(0))
    d = str(tmp_path)
    srv = serve_mod.Server(cfg, params, n_slots=4, max_len=32,
                           wal_dir=d, snapshot_every=0)
    rng = np.random.default_rng(0)
    reqs = [serve_mod.Request(rid=100 + i,
                              prompt=rng.integers(0, cfg.vocab, 4),
                              max_new=3) for i in range(4)]
    srv.admit(reqs)
    for _ in range(6):
        srv.tick()
    srv.close()
    assert srv.pipeline_metrics.wal_appends > 0
    table, replayed = recover(d)
    assert len(replayed) == srv.pipeline_metrics.wal_appends
    assert trees_equal(table, srv.table)


# ---------------------------------------------------------------------------
# randomized interleavings vs the oracle (hypothesis when available, plus
# a deterministic seeded sweep that always runs)
# ---------------------------------------------------------------------------

def fuzz_scenario(seed, point, hit, snapshot_every, n):
    """One random life: drive → crash (maybe) → recover → full contract."""
    with tempfile.TemporaryDirectory() as d:
        sealed, acked, crashed, _ = drive(
            d, "single", crash_point=point, hit=hit,
            snapshot_every=snapshot_every, n=n, seed=seed)
        if point is not None and not crashed:
            return                             # stream ended before the hit
        try:
            index, n_applied = check_recovery_contract(
                d, "single", sealed, acked,
                point if crashed else "ckpt.none")
        except RecoveryError:
            # died before the initial snapshot finished: nothing was ever
            # acknowledged, so an unrecoverable dir honors the contract
            assert not acked
            return
        assert final_pairs(index) == ref_replay_pairs(
            "single", sealed[:n_applied])


FUZZ_CASES = [
    (1, "wal.mid_append", 2, 3), (2, "wal.after_append", 6, 4),
    (3, "ckpt.mid_write", 1, 2), (4, "ckpt.pre_rename", 2, 5),
    (5, None, 1, 3), (6, "wal.mid_append", 9, 2),
]


@pytest.mark.parametrize("seed,point,hit,every", FUZZ_CASES)
def test_fuzz_deterministic_sweep(seed, point, hit, every):
    fuzz_scenario(seed, point, hit, every, n=200)


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None,
              suppress_health_check=list(HealthCheck))
    @given(seed=st.integers(0, 2 ** 16),
           point=st.sampled_from(list(FAULT_POINTS) + [None]),
           hit=st.integers(1, 10),
           every=st.integers(0, 6))
    def test_fuzz_random_interleavings(seed, point, hit, every):
        fuzz_scenario(seed, point, hit, every, n=200)
else:
    def test_fuzz_random_interleavings():
        pytest.importorskip("hypothesis")
