"""The comparison that decides ``correct``: the served run vs the reference.

Every number here counts disagreements, so every limit is 0 (an exact
comparison).  The numbers:

* ``point``      SEARCH answers (found, value) and DELETE answers (found)
                 that differ from the reference;
* ``scan``       RANGE answers (count, sum) that differ;
* ``unanswered`` operations issued in the run that never got an answer;
* ``live``       (key, value) pairs of the index after the run that are
                 missing, extra or hold another value;
* ``wal_lost``   acknowledged windows that the WAL, read back from disk
                 with ``read_wal``, lacks or holds with other contents;
* ``unsynced``   acknowledged windows whose WAL record had not been
                 fsynced when their answers were handed back (the stated
                 guarantee: an acknowledged operation is a durable one).
"""
from __future__ import annotations

import zlib

import numpy as np

from bench import reference
from bench.ycsb import DELETE, RANGE, SEARCH

LIMITS = {"point": 0, "scan": 0, "unanswered": 0, "live": 0,
          "wal_lost": 0, "unsynced": 0}


def window_digest(ops, keys, keys2, vals, occ: int) -> int:
    """crc32 of a window's occupied slots, as the benchmark sealed it."""
    h = 0
    for a in (ops, keys, keys2, vals):
        h = zlib.crc32(np.ascontiguousarray(np.asarray(a)[:occ]).tobytes(),
                       h)
    return h


def live_mismatch(k, v, want_k, want_v) -> int:
    """Pairs in one live set and not in the other, by (key, value)."""
    if len(k) == len(want_k) and np.array_equal(k, want_k) \
            and np.array_equal(v, want_v):
        return 0
    a = np.asarray(k, np.int64) * (1 << 32) + np.asarray(v, np.int64)
    b = np.asarray(want_k, np.int64) * (1 << 32) + \
        np.asarray(want_v, np.int64)
    common = np.intersect1d(a, b, assume_unique=True).size
    return int(len(a) + len(b) - 2 * common)


def compare(base: reference.Base, ops, keys, keys2, vals, window_of,
            answered, found, val, rcnt, rsum, live_k, live_v) -> dict:
    """Counts of disagreement for the operations and the final state.

    Arrays are indexed by operation id (admission order); ``answered``
    marks the operations whose answers came back.
    """
    ops = np.asarray(ops)
    want_f, want_v = reference.point_answers(base, ops, keys, vals)
    is_s = ops == SEARCH
    is_d = ops == DELETE
    bad_point = (is_s & ((found != want_f) | (val != want_v))) | \
        (is_d & (found != want_f))
    is_r = ops == RANGE
    bad_scan = np.zeros(len(ops), bool)
    if is_r.any():
        wc, ws = reference.Windows(base).answers(ops, keys, keys2, vals,
                                                 window_of)
        bad_scan = is_r & ((rcnt != wc) | (rsum != ws))
    bad_point &= answered
    bad_scan &= answered
    want_k, want_lv = reference.final_items(base, ops, keys, vals)
    return dict(point=int(bad_point.sum()), scan=int(bad_scan.sum()),
                unanswered=int((~answered).sum()),
                live=live_mismatch(live_k, live_v, want_k, want_lv),
                failed=int((bad_point | bad_scan | ~answered).sum()))


def wal_lost(records, digests: dict, acked) -> int:
    """Acknowledged windows missing from, or altered in, the WAL."""
    got = {}
    for r in records:
        got[r.seq] = window_digest(r.ops, r.keys, r.keys2, r.vals,
                                   r.occupancy)
    return sum(1 for s in acked if got.get(s) != digests[s])


def unsynced(seals: dict, fsyncs: list, acks: dict) -> int:
    """Acknowledged windows whose record was not on disk when acked.

    ``seals[seq] = (path, end)``: the segment file holding the record and
    the byte offset where it ends.  ``fsyncs``: ``(time, path, size)``
    per fsync of a WAL file.  ``acks[seq]``: when its answers came back.
    """
    by_path = {}
    for t, path, size in fsyncs:
        by_path.setdefault(path, []).append((t, size))
    bad = 0
    for seq, t_ack in acks.items():
        path, end = seals[seq]
        ok = any(t <= t_ack and size >= end
                 for t, size in by_path.get(path, ()))
        bad += not ok
    return bad
