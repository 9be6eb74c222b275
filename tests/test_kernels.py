"""Pallas kernel sweeps: shapes × dtypes × fanouts vs pure-jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import bitonic_sort_op, pi_search_op, sort_queries_kernel
from repro.kernels.ref import bitonic_sort_ref, pi_search_ref


def make_storage(rng, C, dt, fill=0.9):
    if np.issubdtype(dt, np.integer):
        sent = np.iinfo(dt).max
        keys = np.sort(rng.choice(C * 10, size=int(C * fill),
                                  replace=False)).astype(dt)
    else:
        sent = np.inf
        keys = np.unique(rng.uniform(0, 1e6, size=int(C * fill)).astype(dt))
    storage = np.full(C, sent, dt)
    storage[:len(keys)] = keys
    return storage


@pytest.mark.parametrize("C", [64, 1000, 4096, 65536])
@pytest.mark.parametrize("fanout", [4, 8, 16])
@pytest.mark.parametrize("dt", [np.int32, np.float32])
def test_pi_search_sweep(rng, C, fanout, dt):
    storage = make_storage(rng, C, dt)
    q = rng.uniform(-10, C * 10 + 10, size=512).astype(dt)
    got = np.asarray(pi_search_op(jnp.asarray(storage), jnp.asarray(q),
                                  fanout=fanout, tile_q=256, interpret=True))
    want = np.asarray(pi_search_ref(jnp.asarray(storage), jnp.asarray(q)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("tile_q", [64, 128, 512])
def test_pi_search_tile_sizes(rng, tile_q):
    storage = make_storage(rng, 2048, np.int32)
    q = rng.integers(0, 20_000, size=1024).astype(np.int32)
    got = np.asarray(pi_search_op(jnp.asarray(storage), jnp.asarray(q),
                                  fanout=8, tile_q=tile_q, interpret=True))
    want = np.asarray(pi_search_ref(jnp.asarray(storage), jnp.asarray(q)))
    np.testing.assert_array_equal(got, want)


def test_pi_search_exact_hits(rng):
    """Queries exactly on stored keys land on their own slot."""
    storage = make_storage(rng, 1024, np.int32)
    n = int(np.sum(storage != np.iinfo(np.int32).max))
    take = rng.choice(n, 256, replace=False)
    got = np.asarray(pi_search_op(jnp.asarray(storage),
                                  jnp.asarray(storage[take]), fanout=8,
                                  interpret=True))
    np.testing.assert_array_equal(got, take)


def test_pi_search_below_min(rng):
    storage = make_storage(rng, 256, np.int32)
    q = jnp.asarray(np.full(256, storage[0] - 1, np.int32))
    got = np.asarray(pi_search_op(jnp.asarray(storage), q, fanout=4,
                                  interpret=True))
    assert np.all(got == -1)


@pytest.mark.parametrize("B", [16, 64, 256, 2048])
@pytest.mark.parametrize("dt", [np.int32, np.float32])
def test_bitonic_sweep(rng, B, dt):
    k = rng.integers(0, max(4, B // 4), size=B).astype(dt)  # many ties
    v = np.arange(B, dtype=np.int32)
    gk, gv = map(np.asarray, bitonic_sort_op(jnp.asarray(k), jnp.asarray(v),
                                            interpret=True))
    wk, wv = map(np.asarray, bitonic_sort_ref(jnp.asarray(k), jnp.asarray(v)))
    np.testing.assert_array_equal(gk, wk)
    np.testing.assert_array_equal(gv, wv)


def test_bitonic_already_sorted_and_reversed():
    k = jnp.arange(128, dtype=jnp.int32)
    v = jnp.arange(128, dtype=jnp.int32)
    gk, gv = bitonic_sort_op(k, v, interpret=True)
    np.testing.assert_array_equal(np.asarray(gk), np.arange(128))
    gk, gv = bitonic_sort_op(k[::-1], v, interpret=True)
    np.testing.assert_array_equal(np.asarray(gk), np.arange(128))
    np.testing.assert_array_equal(np.asarray(gv), np.arange(128)[::-1])


def test_sort_queries_kernel_is_stable(rng):
    B = 128
    ops = rng.integers(0, 3, B).astype(np.int32)
    keys = rng.integers(0, 9, B).astype(np.int32)
    vals = rng.integers(0, 50, B).astype(np.int32)
    perm, so, sk, sv = sort_queries_kernel(
        jnp.asarray(ops), jnp.asarray(keys), jnp.asarray(vals),
        interpret=True)
    sk, perm = np.asarray(sk), np.asarray(perm)
    assert np.array_equal(sk, np.sort(keys))
    for key in np.unique(keys):
        sub = perm[sk == key]
        assert np.array_equal(sub, np.sort(sub))
    # payload integrity
    np.testing.assert_array_equal(np.asarray(so), ops[perm])
    np.testing.assert_array_equal(np.asarray(sv), vals[perm])
