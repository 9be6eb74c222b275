"""Segmented gapped storage: layout invariants + two-tier rebuild.

Covers the segmented-layout contract end to end:
* geometry resolution and ``seg_width`` validation,
* layout invariants (L1-L5) after build / execute / both rebuild tiers,
* incremental merge == full-sort repack on the live key set (deterministic
  and hypothesis-fuzzed),
* the overflow satellite: repack must *flag* capacity truncation,
* the threshold satellite: integer-exact ``needs_rebuild`` beyond the
  float32 integer range,
* per-shard dirty tracking: a not-due shard keeps its state bit-for-bit
  when a sibling rebuilds.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.core import (
    PIConfig, build, build_sharded, delete_batch, incremental_fits,
    insert_batch, live_items, lookup, maybe_rebuild_shards, needs_rebuild,
    rebuild, validate_layout, with_backend,
)
from repro.core import index as pi_index


# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------

def test_auto_seg_width_is_fanout_power_dividing_capacity():
    for cap, fanout in [(1 << 16, 4), (1024, 4), (256, 4), (512, 8),
                        (300, 2), (326, 16), (2, 4)]:
        cfg = PIConfig(capacity=cap, pending_capacity=32, fanout=fanout)
        w, s = cfg.seg_width_eff, cfg.num_segments
        assert w * s == cap
        if w != cap:  # power-of-fanout invariant L5 (unless degenerate)
            j = w
            while j > 1:
                assert j % fanout == 0
                j //= fanout
        assert 1 <= cfg.max_dirty <= s


def test_explicit_seg_width_validated():
    PIConfig(capacity=1024, pending_capacity=32, fanout=4, seg_width=64)
    PIConfig(capacity=1024, pending_capacity=32, fanout=4, seg_width=1024)
    with pytest.raises(ValueError, match="divide"):
        PIConfig(capacity=1024, pending_capacity=32, fanout=4, seg_width=48)
    with pytest.raises(ValueError, match="power of fanout"):
        PIConfig(capacity=1024, pending_capacity=32, fanout=4, seg_width=128)


# ---------------------------------------------------------------------------
# invariants across mutation paths
# ---------------------------------------------------------------------------

CFG = PIConfig(capacity=1024, pending_capacity=128, fanout=4)


def mk(rng, n=400, key_space=100_000, cfg=CFG):
    keys = rng.choice(key_space, size=n, replace=False).astype(np.int32)
    vals = np.arange(n, dtype=np.int32)
    return build(cfg, jnp.asarray(keys), jnp.asarray(vals)), keys


def test_build_satisfies_layout_invariants(rng):
    idx, _ = mk(rng)
    assert validate_layout(idx)


def test_both_rebuild_tiers_preserve_invariants_and_live_set(rng):
    idx, keys = mk(rng)
    # localized churn -> incremental tier
    newk = np.setdiff1d((60_000 + np.arange(40) * 3).astype(np.int32),
                        keys)[:32]
    idx, _ = insert_batch(idx, jnp.asarray(newk),
                          jnp.asarray(np.full(len(newk), 7, np.int32)))
    assert bool(incremental_fits(idx))
    inc = rebuild(idx)
    assert validate_layout(inc)
    # force the full repack on the same pre-rebuild state
    rep = pi_index._rebuild_repack(idx)
    assert validate_layout(rep)
    ki, vi = live_items(inc)
    kr, vr = live_items(rep)
    np.testing.assert_array_equal(ki, kr)
    np.testing.assert_array_equal(vi, vr)
    assert int(inc.n) >= len(ki)  # clean-segment tombstones may linger


def test_incremental_compacts_dirty_segment_tombstones(rng):
    idx, keys = mk(rng)
    sk = np.sort(keys)
    # delete a clustered run, then insert into the same key region so the
    # victim segment is dirty at rebuild time
    victims = sk[100:120]
    idx, _ = delete_batch(idx, jnp.asarray(victims))
    newk = np.setdiff1d(victims + 1, keys)[:10].astype(np.int32)
    idx, _ = insert_batch(idx, jnp.asarray(newk),
                          jnp.asarray(np.zeros(len(newk), np.int32)))
    n_before = int(idx.n)
    assert bool(incremental_fits(idx))
    idx2 = rebuild(idx)
    assert validate_layout(idx2)
    # at least the dirty segments' tombstones were reclaimed: occupancy
    # grew by strictly less than the pending count
    assert int(idx2.n) < n_before + len(newk)
    k2, _ = live_items(idx2)
    want = np.sort(np.concatenate([np.setdiff1d(sk, victims), newk]))
    np.testing.assert_array_equal(k2, want)


def test_wide_churn_falls_back_to_repack(rng):
    idx, keys = mk(rng)
    # churn scattered across the whole key space dirties > max_dirty segs
    newk = np.setdiff1d(
        rng.choice(100_000, 120, replace=False).astype(np.int32), keys)[:100]
    idx, _ = insert_batch(idx, jnp.asarray(newk),
                          jnp.asarray(np.zeros(len(newk), np.int32)))
    assert not bool(incremental_fits(idx))
    idx2 = rebuild(idx)
    assert validate_layout(idx2)
    k2, _ = live_items(idx2)
    np.testing.assert_array_equal(k2, np.sort(np.concatenate([keys, newk])))


def test_probe_parity_and_lookup_after_incremental_rebuilds(rng):
    """Backends stay bit-identical on the post-incremental gapped layout."""
    idx, keys = mk(rng)
    ref = {int(k): i for i, k in enumerate(np.sort(keys))}
    vals_by_key = dict(zip(np.sort(keys).tolist(), range(len(keys))))
    rng2 = np.random.default_rng(5)
    for round_ in range(3):
        lo = 10_000 + 25_000 * round_
        newk = np.setdiff1d(lo + np.arange(60) * 2,
                            np.array(list(vals_by_key))).astype(np.int32)[:24]
        idx, _ = insert_batch(idx, jnp.asarray(newk),
                              jnp.asarray(np.full(len(newk), round_,
                                                  np.int32)))
        for k in newk:
            vals_by_key[int(k)] = round_
        idx = rebuild(idx)
        assert validate_layout(idx)
        q = np.concatenate([newk, rng2.integers(0, 110_000, 64)]) \
            .astype(np.int32)
        f_x, v_x = lookup(idx, jnp.asarray(q))
        f_p, v_p = lookup(with_backend(idx, "pallas-interpret"),
                          jnp.asarray(q))
        np.testing.assert_array_equal(np.asarray(f_x), np.asarray(f_p))
        np.testing.assert_array_equal(np.asarray(v_x), np.asarray(v_p))
        for i, k in enumerate(q):
            want = vals_by_key.get(int(k))
            assert bool(f_x[i]) == (want is not None)
            if want is not None:
                assert int(v_x[i]) == want


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------

def test_repack_flags_capacity_truncation():
    """live+pending > capacity must raise ``overflow``, not silently drop
    the largest keys (the old ``[:C]`` truncation)."""
    cfg = PIConfig(capacity=64, pending_capacity=64, fanout=4)
    keys = (np.arange(60, dtype=np.int32) * 7)
    idx = build(cfg, jnp.asarray(keys),
                jnp.asarray(np.arange(60, dtype=np.int32)))
    newk = (np.arange(10, dtype=np.int32) * 7 + 1)
    idx, _ = insert_batch(idx, jnp.asarray(newk),
                          jnp.asarray(np.arange(10, dtype=np.int32)))
    assert not bool(idx.overflow)
    idx2 = rebuild(idx)          # 70 live > 64 slots
    assert bool(idx2.overflow), "capacity truncation must be flagged"
    assert int(idx2.n) == 64
    assert validate_layout(idx2)
    k2, _ = live_items(idx2)
    all_sorted = np.sort(np.concatenate([keys, newk]))
    np.testing.assert_array_equal(k2, all_sorted[:64])  # largest dropped
    # the flag makes the next needs_rebuild fire; the rebuild after the
    # truncation operates on an in-capacity set and clears it
    assert bool(needs_rebuild(idx2))
    idx3 = rebuild(idx2)
    assert not bool(idx3.overflow)


def test_needs_rebuild_integer_precision():
    """float32 rounds n = 2**25 + 2 down to 2**25, under-counting the
    threshold; the integer arithmetic must not."""
    cfg = PIConfig(capacity=256, pending_capacity=64, fanout=4,
                   rebuild_frac=0.5)
    idx = build(cfg, jnp.asarray(np.arange(8, dtype=np.int32)),
                jnp.asarray(np.arange(8, dtype=np.int32)))
    big_n = (1 << 25) + 2
    exact_thresh = -(-big_n // 2)      # ceil(n * 0.5), exactly
    below = dataclasses.replace(
        idx, n=jnp.array(big_n, jnp.int32),
        n_updates=jnp.array(exact_thresh - 1, jnp.int32))
    at = dataclasses.replace(
        below, n_updates=jnp.array(exact_thresh, jnp.int32))
    # the float32 computation would trip `below` (2**24 >= f32-thresh)
    assert float(np.float32(big_n) * np.float32(0.5)) <= exact_thresh - 1
    assert not bool(needs_rebuild(below))
    assert bool(needs_rebuild(at))


# ---------------------------------------------------------------------------
# per-shard dirty tracking
# ---------------------------------------------------------------------------

def test_not_due_shard_keeps_state_bit_for_bit(rng):
    cfg = PIConfig(capacity=256, pending_capacity=64, fanout=4)
    keys = rng.choice(10_000, 200, replace=False).astype(np.int32)
    state = build_sharded(cfg, 2, keys, np.arange(200, dtype=np.int32),
                          mesh=jax.make_mesh((1,), ("data",)))
    # give BOTH shards pending churn, but only shard 0 enough to be due
    s0 = jax.tree.map(lambda x: x[0], state.shards)
    s1 = jax.tree.map(lambda x: x[1], state.shards)
    lo_new = np.setdiff1d(np.arange(40, dtype=np.int32), keys)[:40]
    s0, _ = pi_index.insert_batch(s0, jnp.asarray(lo_new),
                                  jnp.asarray(np.zeros(len(lo_new),
                                                       np.int32)))
    hi_new = np.setdiff1d(9_000 + np.arange(3, dtype=np.int32), keys)
    s1, _ = pi_index.insert_batch(s1, jnp.asarray(hi_new),
                                  jnp.asarray(np.zeros(len(hi_new),
                                                       np.int32)))
    assert bool(needs_rebuild(s0)) and not bool(needs_rebuild(s1))
    stacked = jax.tree.map(lambda a, b: jnp.stack([a, b]), s0, s1)
    shards, ovf, due, _ = maybe_rebuild_shards(stacked)
    assert due.tolist() == [True, False] and not ovf.any()
    out0 = jax.tree.map(lambda x: x[0], shards)
    out1 = jax.tree.map(lambda x: x[1], shards)
    assert int(out0.pn) == 0, "due shard must have rebuilt"
    # not-due shard: every leaf unchanged (pending churn kept buffered)
    for got, want in zip(jax.tree.leaves(out1), jax.tree.leaves(s1)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(out1.pn) == len(hi_new)


# ---------------------------------------------------------------------------
# hypothesis fuzz: segmented merge vs full-sort reference
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(data=st.data())
    def test_property_segmented_merge_matches_full_sort(data):
        rng = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
        fanout = data.draw(st.sampled_from([2, 4, 8]))
        cap = data.draw(st.sampled_from([256, 512, 1024]))
        cfg = PIConfig(capacity=cap, pending_capacity=128, fanout=fanout,
                       max_dirty_frac=data.draw(
                           st.sampled_from([0.25, 1.0])))
        n0 = data.draw(st.integers(0, cap // 2))
        keyspace = data.draw(st.sampled_from([500, 100_000]))
        keys = rng.choice(keyspace, size=min(n0, keyspace),
                          replace=False).astype(np.int32)
        idx = build(cfg, jnp.asarray(keys),
                    jnp.asarray(np.arange(len(keys), dtype=np.int32)))
        ref = {int(k): i for i, k in enumerate(keys)}
        # a few mixed batches, rebuilding in between
        for _ in range(data.draw(st.integers(1, 3))):
            B = data.draw(st.sampled_from([8, 32]))
            ops = rng.integers(0, 3, B).astype(np.int32)
            ks = rng.integers(0, keyspace, B).astype(np.int32)
            vs = rng.integers(0, 100, B).astype(np.int32)
            idx, _ = pi_index.execute(idx, jnp.asarray(ops), jnp.asarray(ks),
                                      jnp.asarray(vs))
            for o, k, v in zip(ops, ks, vs):
                if o == 1:
                    ref[int(k)] = int(v)
                elif o == 2:
                    ref.pop(int(k), None)
            pre = idx
            idx = rebuild(idx)
            assert validate_layout(idx)
            # two-tier == full-sort reference on the live set
            rep = pi_index._rebuild_repack(pre)
            ki, vi = live_items(idx)
            kr, vr = live_items(rep)
            np.testing.assert_array_equal(ki, kr)
            np.testing.assert_array_equal(vi, vr)
            refk = np.array(sorted(ref), dtype=np.int64)
            np.testing.assert_array_equal(ki.astype(np.int64), refk)
            np.testing.assert_array_equal(
                vi, np.array([ref[int(k)] for k in refk]))
else:
    def test_property_segmented_merge_matches_full_sort():
        pytest.importorskip("hypothesis")
