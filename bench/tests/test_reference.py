"""The benchmark's reference agrees with ``core.ref.RefIndex``."""
import numpy as np
import pytest

from bench import reference
from bench.ycsb import DELETE, INSERT, RANGE, SEARCH


def windowed_stream(seed, n_base=300, n_ops=2000, key_space=1000):
    rng = np.random.default_rng(seed)
    bk = rng.choice(key_space, n_base, replace=False)
    bv = rng.integers(0, 1 << 30, n_base)
    ops = rng.choice([SEARCH, INSERT, DELETE, RANGE], n_ops,
                     p=[0.4, 0.3, 0.1, 0.2]).astype(np.int32)
    keys = rng.integers(0, key_space, n_ops)
    keys2 = np.where(ops == RANGE, keys + rng.integers(0, 60, n_ops), 0)
    vals = np.where(ops == INSERT, rng.integers(0, 1 << 30, n_ops), 0)
    window = np.sort(rng.integers(0, 40, n_ops))
    return bk, bv, ops, keys, keys2, vals, window


def ref_index_answers(bk, bv, ops, keys, keys2, vals, window):
    """RefIndex, one window at a time: ranges on the pre-window map."""
    from repro.core import RefIndex

    ref = RefIndex.build(bk, bv)
    n = len(ops)
    found = np.zeros(n, bool)
    val = np.zeros(n, np.int64)
    cnt = np.zeros(n, np.int64)
    sm = np.zeros(n, np.int64)
    for w in np.unique(window):
        idx = np.flatnonzero(window == w)
        for i in idx[ops[idx] == RANGE]:
            rows = ref.range(keys[i], keys2[i])
            cnt[i] = len(rows)
            sm[i] = sum(v for _, v in rows)
        pt = idx[ops[idx] != RANGE]
        # RefIndex sorts by (key, position), i.e. admission order per key
        res = ref.execute(ops[pt], keys[pt], vals[pt])
        for i, r in zip(pt, res):
            if ops[i] == SEARCH:
                found[i], val[i] = r is not None, (r or 0)
            elif ops[i] == DELETE:
                found[i], val[i] = r is not None, (1 if r else 0)
    items = sorted(ref.data.items())
    return found, val, cnt, reference.wrap32(sm), items


@pytest.mark.parametrize("seed", [0, 1, 2, 2**31 + 3])
def test_agrees_with_ref_index(seed):
    bk, bv, ops, keys, keys2, vals, window = windowed_stream(seed)
    want_f, want_v, want_c, want_s, items = ref_index_answers(
        bk, bv, ops, keys, keys2, vals, window)
    base = reference.Base(bk, bv)
    f, v = reference.point_answers(base, ops, keys, vals)
    point = (ops == SEARCH) | (ops == DELETE)
    np.testing.assert_array_equal(f[point], want_f[point])
    np.testing.assert_array_equal(v[point], want_v[point])
    c, s = reference.Windows(base).answers(ops, keys, keys2, vals, window)
    r = ops == RANGE
    np.testing.assert_array_equal(c[r], want_c[r])
    np.testing.assert_array_equal(s[r], want_s[r])
    k, fv = reference.final_items(base, ops, keys, vals)
    assert list(zip(k.tolist(), fv.tolist())) == items


def test_sums_wrap_like_int32():
    big = np.array([2**30, 2**30, 2**30], np.int64)
    base = reference.Base(np.array([1, 2, 3]), big)
    c, s = base.ranges(np.array([1]), np.array([3]))
    assert c[0] == 3 and reference.wrap32(s)[0] == 3 * 2**30 - 2**32
