"""YCSB's core-workload generators, copied so the yardstick cannot move.

Follows github.com/brianfrankcooper/YCSB, ``core/src/main/java/site/ycsb``:

* ``generator/ZipfianGenerator`` and ``ScrambledZipfianGenerator``: a
  zipfian over 10^10 items with the precomputed ``ZETAN`` for the constant
  0.99, hashed with ``Utils.fnvhash64`` and taken modulo the record count;
* ``workloads/CoreWorkload``: the operation mix (``readproportion``,
  ``updateproportion``, ``insertproportion``, ``scanproportion``), scan
  lengths uniform in ``1..maxscanlength``, and ``insertorder=hashed``
  (record ``i``'s key is a hash of ``i``; inserts continue the sequence).

Departures, each forced by the program (int32 keys, int32 values, scans
that aggregate): a record's key is a *bijective* 30-bit hash of its number
(FNV-64 modulo a 2^30 key space would collide), a record's value is an
int32 standing for a reference to its fields, and a scan ``[lo, hi]``
takes ``hi`` from the initial sorted keys so that it covers the drawn
number of records.  Scan starts follow the zipfian over the initial
records; YCSB widens that range by its expected inserts, which a run of
fixed length does not know.

Every draw comes from ``--seed``: a chunk of ``CHUNK`` operations is a
pure function of ``(seed, chunk number)``, so the stream is the same
however many operations a run consumes.
"""
from __future__ import annotations

import json
import os

import numpy as np

# repro.core.batch op codes, restated so the yardstick imports nothing
SEARCH, INSERT, DELETE, RANGE = 0, 1, 2, 3

FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)

ZIPFIAN_CONSTANT = 0.99
ITEM_COUNT = 10_000_000_000          # ScrambledZipfianGenerator.ITEM_COUNT
ZETAN = 26.46902820178302            # zeta(ITEM_COUNT, 0.99), YCSB's constant

CHUNK = 1 << 16                      # operations drawn per generator step

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")

_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def fnvhash64(val: np.ndarray) -> np.ndarray:
    """``Utils.fnvhash64`` on an int64 array (Java long arithmetic)."""
    v = np.asarray(val, np.int64).view(np.uint64).copy()
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        v >>= np.uint64(8)
        h *= FNV_PRIME_64                  # wraps mod 2^64, as in Java
    return np.abs(h.view(np.int64))        # Math.abs


class ScrambledZipfian:
    """``ScrambledZipfianGenerator(0, n_items - 1)`` at the constant 0.99."""

    def __init__(self, n_items: int, theta: float = ZIPFIAN_CONSTANT):
        if theta != ZIPFIAN_CONSTANT:
            raise ValueError("YCSB precomputes zetan for 0.99 only")
        self.n_items = int(n_items)
        self.items = ITEM_COUNT + 1        # ZipfianGenerator(0, ITEM_COUNT)
        self.theta = theta
        self.alpha = 1.0 / (1.0 - theta)
        zeta2 = 1.0 + 0.5 ** theta
        self.eta = ((1.0 - (2.0 / self.items) ** (1.0 - theta))
                    / (1.0 - zeta2 / ZETAN))

    def ranks(self, u: np.ndarray) -> np.ndarray:
        """``ZipfianGenerator.nextLong`` for uniform draws ``u`` in [0, 1)."""
        uz = u * ZETAN
        far = (self.items * np.power(self.eta * u - self.eta + 1.0,
                                     self.alpha)).astype(np.int64)
        return np.where(uz < 1.0, 0,
                        np.where(uz < 1.0 + 0.5 ** self.theta, 1, far))

    def sample(self, u: np.ndarray) -> np.ndarray:
        """Record numbers in [0, n_items): hash of the zipfian rank."""
        return fnvhash64(self.ranks(u)) % self.n_items


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer on uint64 (wrapping), in place."""
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return x


def seed_words(seed: int, n: int = 4) -> np.ndarray:
    """``n`` uint64 words drawn from ``seed`` (any non-negative integer)."""
    ss = np.random.SeedSequence(int(seed) % (1 << 128))
    return ss.generate_state(n, np.uint64)


def record_keys(seq: np.ndarray, key_bits: int, salt: int) -> np.ndarray:
    """Key of each record number: a bijection of [0, 2^key_bits).

    ``insertorder=hashed`` with distinct keys: two odd multiplications and
    xor-shifts are each invertible modulo 2^key_bits, as is the salt xor,
    so distinct record numbers always get distinct keys.
    """
    if not 2 <= key_bits <= 31:
        raise ValueError("keys are int32: key_bits must lie in 2..31")
    seq = np.asarray(seq, np.int64)
    if len(seq) and (seq.min() < 0 or seq.max() >> key_bits):
        raise ValueError(f"record number beyond the 2^{key_bits} key space")
    # uint32 products wrap modulo 2^32, which 2^key_bits divides
    m = np.uint32((1 << key_bits) - 1)
    x = seq.astype(np.uint32)
    x *= np.uint32(0x2C1B3C6D)
    x &= m
    x ^= x >> np.uint32(key_bits // 2)
    x *= np.uint32(0x297A2D39)
    x &= m
    x ^= x >> np.uint32(key_bits // 2 - 1)
    x ^= np.uint32(int(salt) & int(m))
    return x.view(np.int32)


def record_vals(seq: np.ndarray, salt: int) -> np.ndarray:
    """Initial value of each record: 30 hashed bits of (record, seed)."""
    x = np.asarray(seq, np.int64).astype(np.uint64)
    x += np.uint64(salt)
    x = _mix64(x)
    x >>= np.uint64(34)
    return x.astype(np.int32)


def load_traffic(name: str) -> dict:
    """The traffic mix ``traffic/<name>.json``."""
    path = os.path.join(TRAFFIC_DIR, f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    props = [mix.get(k, 0.0) for k in
             ("readproportion", "updateproportion", "insertproportion",
              "scanproportion")]
    if any(p < 0 for p in props) or abs(sum(props) - 1.0) > 1e-9:
        raise ValueError(f"{path}: operation proportions must sum to 1")
    if mix.get("requestdistribution") != "zipfian":
        raise ValueError(f"{path}: only requestdistribution=zipfian")
    if mix.get("scanlengthdistribution", "uniform") != "uniform":
        raise ValueError(f"{path}: only scanlengthdistribution=uniform")
    if mix.get("insertorder", "hashed") != "hashed":
        raise ValueError(f"{path}: only insertorder=hashed")
    return mix


class Dataset:
    """The initial records of a configuration, made from the seed."""

    def __init__(self, n_records: int, key_bits: int, seed: int):
        w = seed_words(seed)
        self.n_records = int(n_records)
        self.key_bits = int(key_bits)
        self.key_salt = int(w[0] & np.uint64((1 << key_bits) - 1))
        self.val_salt = int(w[1])
        self.op_seed = int(w[2])
        seq = np.arange(self.n_records, dtype=np.int64)
        self.keys = record_keys(seq, key_bits, self.key_salt)
        self.vals = record_vals(seq, self.val_salt)
        self._sorted_keys = None

    def key_of(self, seq: np.ndarray) -> np.ndarray:
        return record_keys(seq, self.key_bits, self.key_salt)

    @property
    def sorted_keys(self) -> np.ndarray:
        if self._sorted_keys is None:
            self._sorted_keys = np.sort(self.keys)
        return self._sorted_keys


class OpStream:
    """The operations clients issue, in issue order.

    ``next(n)`` returns the next ``n`` operations as ``(ops, keys, keys2,
    vals)`` int32 arrays in ``repro.core`` op codes: read -> SEARCH,
    update and insert -> INSERT (on a present and on a new key), scan ->
    RANGE ``[keys, keys2]``.
    """

    def __init__(self, mix: dict, data: Dataset):
        self.mix = mix
        self.data = data
        self.cum = np.cumsum([mix.get("readproportion", 0.0),
                              mix.get("updateproportion", 0.0),
                              mix.get("insertproportion", 0.0)])
        self.max_scan = int(mix.get("maxscanlength", 100))
        self.zipf = ScrambledZipfian(data.n_records,
                                     float(mix["zipfianconstant"]))
        self.next_insert = data.n_records   # insert sequence continues
        self._n_chunks = 0
        self._buf = [np.zeros(0, np.int32)] * 4

    def _make_chunk(self):
        n = CHUNK
        rng = np.random.default_rng([self.data.op_seed, self._n_chunks])
        self._n_chunks += 1
        u_op = rng.random(n)
        rec = self.zipf.sample(rng.random(n))
        length = rng.integers(1, self.max_scan + 1, n)
        vals = rng.integers(0, 1 << 30, n).astype(np.int32)
        kind = np.searchsorted(self.cum, u_op, side="right")  # 0..3
        ins = kind == 2
        n_ins = int(ins.sum())
        seq = rec.copy()
        seq[ins] = self.next_insert + np.arange(n_ins)
        self.next_insert += n_ins
        keys = self.data.key_of(seq)
        ops = np.array([SEARCH, INSERT, INSERT, RANGE], np.int32)[kind]
        keys2 = np.zeros(n, np.int32)
        scan = kind == 3
        if scan.any():
            sk = self.data.sorted_keys
            start = np.searchsorted(sk, keys[scan])
            end = np.minimum(start + length[scan] - 1, len(sk) - 1)
            keys2[scan] = sk[end]
        vals = np.where((kind == 1) | ins, vals, 0).astype(np.int32)
        return [ops, keys, keys2, vals]

    def next(self, n: int):
        while len(self._buf[0]) < n:
            fresh = self._make_chunk()
            self._buf = [np.concatenate([b, f])
                         for b, f in zip(self._buf, fresh)]
        out = [b[:n] for b in self._buf]
        self._buf = [b[n:] for b in self._buf]
        return tuple(out)
