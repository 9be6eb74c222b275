"""The traffic generator: YCSB's own, seeded, in the stated proportions."""
import numpy as np
import pytest

from bench import ycsb

SEED = 2**31 + 12345


def stream(mix_name, n_records=1 << 14, seed=SEED):
    data = ycsb.Dataset(n_records, 30, seed)
    return data, ycsb.OpStream(ycsb.load_traffic(mix_name), data)


@pytest.mark.parametrize("mix", ["ycsb-a", "ycsb-e"])
def test_same_seed_same_stream_however_consumed(mix):
    _, s1 = stream(mix)
    _, s2 = stream(mix)
    a = s1.next(100_000)
    parts = [s2.next(n) for n in (1, 8191, 30_000, 61_808)]
    b = tuple(np.concatenate(x) for x in zip(*parts))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    _, s3 = stream(mix, seed=SEED + 1)
    assert not np.array_equal(a[1], s3.next(100_000)[1])


def test_records_distinct_and_seeded():
    d = ycsb.Dataset(1 << 18, 30, SEED)
    assert len(np.unique(d.keys)) == len(d.keys)
    assert d.keys.min() >= 0 and d.keys.max() < (1 << 30)
    assert not np.array_equal(d.keys, ycsb.Dataset(1 << 18, 30, 3).keys)
    np.testing.assert_array_equal(d.vals, ycsb.Dataset(1 << 18, 30,
                                                       SEED).vals)


def test_workload_a_proportions():
    data, s = stream("ycsb-a")
    ops, keys, keys2, vals = s.next(200_000)
    assert set(np.unique(ops)) == {ycsb.SEARCH, ycsb.INSERT}
    assert abs((ops == ycsb.INSERT).mean() - 0.5) < 0.01
    # updates only: every key is an existing record
    assert np.isin(keys, data.keys).all()
    assert (keys2 == 0).all()


def test_workload_e_proportions_and_scan_lengths():
    data, s = stream("ycsb-e")
    ops, keys, keys2, vals = s.next(200_000)
    assert set(np.unique(ops)) == {ycsb.RANGE, ycsb.INSERT}
    assert abs((ops == ycsb.INSERT).mean() - 0.05) < 0.005
    ins = ops == ycsb.INSERT
    # inserts are new keys, each once
    assert not np.isin(keys[ins], data.keys).any()
    assert len(np.unique(keys[ins])) == ins.sum()
    # scans start on a record and cover 1..100 of the initial records
    sk = data.sorted_keys
    r = ops == ycsb.RANGE
    i0 = np.searchsorted(sk, keys[r])
    assert (sk[i0] == keys[r]).all()
    covered = np.searchsorted(sk, keys2[r], side="right") - i0
    assert covered.min() >= 1 and covered.max() <= 100
    full = i0 + 100 <= len(sk)       # scans not cut by the last record
    assert covered[full].min() == 1 and covered[full].max() == 100
    assert abs(covered[full].mean() - 50.5) < 1.0


def test_scrambled_zipfian_is_ycsbs():
    z = ycsb.ScrambledZipfian(1 << 20)
    u = np.random.default_rng(0).random(1_000_000)
    ranks = z.ranks(u)
    # item 0 takes 1/zetan of the draws, item 1 the next 0.5^0.99/zetan
    assert abs((ranks == 0).mean() - 1 / ycsb.ZETAN) < 0.002
    assert abs((ranks == 1).mean() - 0.5 ** 0.99 / ycsb.ZETAN) < 0.002
    recs = z.sample(u)
    assert recs.min() >= 0 and recs.max() < (1 << 20)
    # scrambled: the hottest record is FNV(0) mod n, not record 0
    hot = np.bincount(recs).argmax()
    assert hot == ycsb.fnvhash64(np.array([0]))[0] % (1 << 20)


def test_fnvhash64_matches_java():
    # Utils.fnvhash64(0) and (1), computed with Java long arithmetic
    def java(v):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h ^= v & 0xFF
            v >>= 8
            h = (h * 1099511628211) & 0xFFFFFFFFFFFFFFFF
        h = h - (1 << 64) if h >= 1 << 63 else h
        return abs(h)
    vals = np.array([0, 1, 12345, 10**10], np.int64)
    assert ycsb.fnvhash64(vals).tolist() == [java(int(v)) for v in vals]
