"""Sharded PI: multi-device oracle equivalence + the Alg. 3 fidelity check.

Device-count-sensitive parts run in a subprocess (see conftest) so the main
suite keeps the default single CPU device.
"""
import numpy as np

from conftest import run_with_devices
from repro.core import alg3, RefIndex
from repro.core.batch import SEARCH, INSERT, DELETE

SHARDED_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import *

rng = np.random.default_rng(1)
cfg = PIConfig(capacity=1024, pending_capacity=256, fanout=4)
keys = rng.choice(100_000, size=1000, replace=False).astype(np.int32)
vals = np.arange(1000, dtype=np.int32)
S = 8
mesh = jax.make_mesh((S,), ("data",))
state = build_sharded(cfg, S, keys, vals, mesh=mesh)
for s, shard in enumerate(state.shards.keys.addressable_shards):
    assert shard.index[0] == slice(s, s + 1) and shard.device == mesh.devices[s]
ref = RefIndex.build(keys, vals)
B = 512
for trial in range(3):
    ops = rng.integers(0, 3, size=B).astype(np.int32)
    ks = rng.choice(np.concatenate([keys, rng.integers(0, 100_000, 500).astype(np.int32)]), size=B).astype(np.int32)
    vs = rng.integers(0, 1000, size=B).astype(np.int32)
    state, (rf, rv), load, dropped = execute_sharded(
        state, mesh, jnp.asarray(ops), jnp.asarray(ks), jnp.asarray(vs))
    assert int(np.sum(np.asarray(dropped))) == 0
    expected = ref.execute(ops, ks, vs)
    rf, rv = np.asarray(rf), np.asarray(rv)
    for i in range(B):
        got = int(rv[i]) if bool(rf[i]) else None
        assert got == expected[i], (trial, i)
k2, v2 = collect_pairs(state)
refk = np.array(sorted(ref.data)); refv = np.array([ref.data[k] for k in refk])
assert np.array_equal(k2, refk) and np.array_equal(v2, refv)
state = rebuild_sharded(state, mesh)
k3, v3 = collect_pairs(state)
assert np.array_equal(k3, refk) and np.array_equal(v3, refv)
print("OK")
"""

REBALANCE_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import *

rng = np.random.default_rng(1)
cfg = PIConfig(capacity=1024, pending_capacity=128, fanout=4)
keys = rng.choice(100_000, size=1000, replace=False).astype(np.int32)
mesh = jax.make_mesh((8,), ("data",))
state = build_sharded(cfg, 8, keys, np.arange(1000, dtype=np.int32), mesh=mesh)
zeros = jnp.zeros(4096, jnp.int32)
zipf = (np.random.default_rng(2).zipf(1.5, size=4096) % 100_000).astype(np.int32)
state, _, load, _ = execute_sharded(state, mesh, zeros, jnp.asarray(zipf), zeros)
i0 = load_imbalance(np.asarray(load))
f2 = rebalance_from_load(np.asarray(state.fences), np.asarray(load),
                         smoothing=1.0, key_lo=0, key_hi=100_000)
kk, vv = collect_pairs(state)
state2 = build_sharded(cfg, 8, kk, vv, fences=f2, mesh=mesh)
state2, _, load2, _ = execute_sharded(state2, mesh, zeros, jnp.asarray(zipf), zeros)
assert load_imbalance(np.asarray(load2)) < i0
print("OK")
"""


def test_sharded_matches_oracle_8_devices():
    out = run_with_devices(SHARDED_SCRIPT, 8)
    assert "OK" in out


def test_rebalance_reduces_imbalance_8_devices():
    out = run_with_devices(REBALANCE_SCRIPT, 8)
    assert "OK" in out


# ---------------------------------------------------------------------------
# Alg. 3 protocol fidelity (pure python; no devices needed)
# ---------------------------------------------------------------------------

def test_alg3_ownership_disjoint_and_semantics_match(rng):
    keys = rng.choice(1000, size=80, replace=False).astype(np.int32)
    init = {int(k): int(i) for i, k in enumerate(keys)}
    for n_threads in (2, 4, 8):
        for trial in range(5):
            B = 128
            ops = rng.integers(0, 3, B).astype(np.int32)
            # heavy duplication so interceptions collide across threads
            ks = rng.choice(keys, B).astype(np.int32)
            vs = rng.integers(0, 100, B).astype(np.int32)
            res = alg3.run_threads(init, ops, ks, vs, n_threads)
            # (a) latch-freedom invariant: interception sets pairwise disjoint
            for a in range(n_threads):
                for b in range(a + 1, n_threads):
                    assert not (res.ownership[a] & res.ownership[b]), \
                        (n_threads, trial)
            # (b) protocol == oracle batch semantics
            ref = RefIndex.build(list(init), list(init.values()))
            want = ref.execute(ops, ks, vs)
            assert res.results == want
            assert res.state == ref.data


def test_alg3_handoff_occurs(rng):
    """With many duplicate keys the protocol must actually move queries."""
    init = {i * 10: i for i in range(50)}
    ks = np.array([105] * 64, np.int32)  # all intercept the same node
    ops = np.zeros(64, np.int32)
    vs = np.zeros(64, np.int32)
    res = alg3.run_threads(init, ops, ks, vs, 4)
    assert res.handoffs > 0
    owners = [t for t, o in enumerate(res.ownership) if o]
    assert len(owners) == 1  # exactly one thread owns the hot node
