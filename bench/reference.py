"""The plain reference: sorted base arrays plus the written keys.

Semantics (DESIGN.md §9, and ``core.ref.RefIndex`` within a window):
windows apply in order; inside a window every RANGE reads the state
*before* the window, and point operations apply one by one in admission
order, each seeing the earlier writes to its key.  Across the whole run
the point operations are therefore one sequence in admission order, which
is how ``point_answers`` computes them, vectorized: the answer to a
SEARCH (or DELETE) is the last earlier write to its key, or the base.

The base is the initial records as sorted numpy arrays; nothing here
imports the program or uses anything it made.
"""
from __future__ import annotations

import numpy as np

from bench.ycsb import DELETE, INSERT, RANGE, SEARCH


def wrap32(x: np.ndarray) -> np.ndarray:
    """int64 sums wrapped to int32, as the device's int32 sum wraps."""
    return ((np.asarray(x, np.int64) + (1 << 31)) % (1 << 32)) - (1 << 31)


class Base:
    """The initial (key, value) records, sorted by key."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        order = np.argsort(keys, kind="stable")
        self.keys = np.asarray(keys, np.int64)[order]
        self.vals = np.asarray(vals, np.int64)[order]
        if np.any(np.diff(self.keys) <= 0):
            raise ValueError("base keys must be distinct")
        self.csum = np.concatenate([[0], np.cumsum(self.vals)])

    def lookup(self, keys: np.ndarray):
        """(found, value) of each key in the base."""
        keys = np.asarray(keys, np.int64)
        pos = np.searchsorted(self.keys, keys)
        pc = np.minimum(pos, len(self.keys) - 1)
        found = (pos < len(self.keys)) & (self.keys[pc] == keys) \
            if len(self.keys) else np.zeros(keys.shape, bool)
        return found, np.where(found, self.vals[pc] if len(self.keys)
                               else 0, 0)

    def ranges(self, lo: np.ndarray, hi: np.ndarray):
        """(count, int64 sum) of the base records in each [lo, hi]."""
        i0 = np.searchsorted(self.keys, np.asarray(lo, np.int64), "left")
        i1 = np.searchsorted(self.keys, np.asarray(hi, np.int64), "right")
        i1 = np.maximum(i1, i0)
        return i1 - i0, self.csum[i1] - self.csum[i0]


def _last_write(keys, is_write, order_key):
    """For each op (in ``order_key`` order), the index of the last write
    to its key strictly before it, or -1."""
    n = len(keys)
    order = np.lexsort((order_key, keys))        # by key, then sequence
    k = keys[order]
    w = is_write[order]
    idx = np.where(w, np.arange(n), -1)
    # last write at or before each sorted position, then strictly before
    upto = np.maximum.accumulate(idx) if n else idx
    prev = np.concatenate([[-1], upto[:-1]]) if n else upto
    same = prev >= 0
    same[same] = k[prev[same]] == k[same]
    prev = np.where(same, prev, -1)
    out = np.full(n, -1, np.int64)
    out[order] = np.where(prev >= 0, order[np.maximum(prev, 0)], -1)
    return out


def point_answers(base: Base, ops, keys, vals):
    """(found, value) each op would return, ops in admission order.

    SEARCH: the key's visible value.  DELETE: found = the key was visible
    (value 1 when found, as the program reports it).  INSERT and RANGE
    rows read (False, 0) here; RANGE answers come from ``Windows``.
    """
    ops = np.asarray(ops)
    keys = np.asarray(keys, np.int64)
    vals = np.asarray(vals, np.int64)
    point = (ops == SEARCH) | (ops == INSERT) | (ops == DELETE)
    is_write = (ops == INSERT) | (ops == DELETE)
    n = len(ops)
    # RANGE rows are parked on a key no point op has, so they are inert
    k = np.where(point, keys, np.int64(-1) - np.arange(n))
    lw = _last_write(k, is_write & point, np.arange(n))
    has = lw >= 0
    lwc = np.maximum(lw, 0)
    bfound, bval = base.lookup(keys)
    vis = np.where(has, ops[lwc] == INSERT, bfound)
    vval = np.where(has, vals[lwc], bval)
    found = np.where((ops == SEARCH) | (ops == DELETE), vis, False)
    val = np.where(ops == SEARCH, np.where(vis, vval, 0),
                   np.where((ops == DELETE) & vis, 1, 0))
    return found, val


def final_items(base: Base, ops, keys, vals):
    """Live (key, value) pairs after every op, sorted by key."""
    ops = np.asarray(ops)
    w = (ops == INSERT) | (ops == DELETE)
    wk = np.asarray(keys, np.int64)[w]
    wv = np.asarray(vals, np.int64)[w]
    wins = ops[w] == INSERT
    # last write per key: unique over the reversed sequence
    uk, first = np.unique(wk[::-1], return_index=True)
    last = len(wk) - 1 - first
    live = wins[last]
    keep = np.ones(len(base.keys), bool)
    pos = np.searchsorted(base.keys, uk)
    inb = pos < len(base.keys)
    inb[inb] = base.keys[pos[inb]] == uk[inb]
    keep[pos[inb]] = False
    k = np.concatenate([base.keys[keep], uk[live]])
    v = np.concatenate([base.vals[keep], wv[last][live]])
    order = np.argsort(k, kind="stable")
    return k[order], v[order]


class Windows:
    """RANGE answers: each window's ranges read the pre-window state.

    The state before window ``w`` is the base overlaid with the last
    write to each key among the ops of earlier windows.  The overlay is
    kept as sorted arrays and brought forward only to windows that hold
    a RANGE.
    """

    def __init__(self, base: Base):
        self.base = base

    def answers(self, ops, keys, keys2, vals, window_of):
        """(count, int32-wrapped sum) per op; zeros on non-RANGE rows.

        ``window_of`` is each op's window number, non-decreasing in
        admission order."""
        ops = np.asarray(ops)
        keys = np.asarray(keys, np.int64)
        keys2 = np.asarray(keys2, np.int64)
        vals = np.asarray(vals, np.int64)
        window_of = np.asarray(window_of)
        n = len(ops)
        cnt = np.zeros(n, np.int64)
        sm = np.zeros(n, np.int64)
        is_r = ops == RANGE
        if not is_r.any():
            return cnt, sm
        is_w = (ops == INSERT) | (ops == DELETE)
        starts = np.searchsorted(window_of, np.unique(window_of[is_r]))
        ends = np.searchsorted(window_of, window_of[starts], side="right")
        ok = np.zeros(0, np.int64)          # overlay keys (sorted)
        olive = np.zeros(0, bool)
        oval = np.zeros(0, np.int64)
        done = 0
        for s, e in zip(starts, ends):
            # bring the overlay forward through the ops before window s
            if s > done:
                sel = np.flatnonzero(is_w[done:s]) + done
                if sel.size:
                    uk, first = np.unique(keys[sel][::-1],
                                          return_index=True)
                    last = sel[::-1][first]
                    pos = np.searchsorted(ok, uk)
                    hit = pos < len(ok)
                    hit[hit] = ok[pos[hit]] == uk[hit]
                    olive[pos[hit]] = ops[last[hit]] == INSERT
                    oval[pos[hit]] = vals[last[hit]]
                    new = ~hit
                    at = pos[new]
                    ok = np.insert(ok, at, uk[new])
                    olive = np.insert(olive, at, ops[last[new]] == INSERT)
                    oval = np.insert(oval, at, vals[last[new]])
                done = s
            r = np.flatnonzero(is_r[s:e]) + s
            lo, hi = keys[r], keys2[r]
            c, t = self.base.ranges(lo, hi)
            # overlay correction: each overlaid key replaces its base entry
            bfound, bval = self.base.lookup(ok)
            dc = np.concatenate([[0], np.cumsum(olive.astype(np.int64)
                                                - bfound)])
            ds = np.concatenate([[0], np.cumsum(np.where(olive, oval, 0)
                                                - np.where(bfound, bval,
                                                           0))])
            j0 = np.searchsorted(ok, lo, "left")
            j1 = np.maximum(np.searchsorted(ok, hi, "right"), j0)
            cnt[r] = c + dc[j1] - dc[j0]
            sm[r] = t + ds[j1] - ds[j0]
        return cnt, wrap32(sm)
