"""PIIndex — the paper's two-layer skip-list index, adapted to dense arrays.

Layout (see DESIGN.md §2 for the CPU→TPU mapping):

* **Storage layer**: a *segmented gapped* key array (+ ``vals``, tombstone
  bitmap ``tomb``) of static capacity ``C = S * W``: ``S`` fixed-width
  segments of ``W`` slots, each holding a sorted run followed by
  ``KSENT``-padded slack (a BS-tree-style gapped layout).  This is the
  paper's bottom linked list; the linked-list *pointer* is the array
  successor within a run, and the slack is what lets a rebuild touch only
  the segments that changed.  Deletes are tombstones (the paper's
  ``F_del``), compacted at rebuild time, exactly as in §3.2.3/§4.3.5.

  Layout invariants (checked by ``validate_layout``; DESIGN.md §2a):
    L1  every segment is a sorted run prefix + a KSENT slack tail;
    L2  runs are strictly increasing (keys unique);
    L3  runs are ordered across segments (run s  <  run s+1 elementwise);
    L4  empty segments appear only at the global tail;
    L5  ``W`` is a power of the fanout ``F`` (or W == C, one segment).
  Under L1–L5 the *dense-array* descent is already correct on the gapped
  array: every index level gathers strided keys with KSENT fill, KSENT
  sorts after all real keys, and because ``stride = F**l`` either divides
  ``W`` or is a multiple of it, no F-key child group ever straddles a
  partially-filled segment out of order.  The engines and the Pallas
  kernels therefore run UNCHANGED on this layout — positions returned by
  ``traverse`` are gapped *slot* indices, not dense ranks.
* **Index layer**: ``levels[l]`` (l = 1..H) holds every ``F**l``-th storage
  key, contiguous per level (the paper stores each level's entries in one
  contiguous area, §4.1).  An *entry* is an aligned group of ``F`` keys; the
  per-entry *routing table* degenerates to rank arithmetic
  (``child = pos*F + rank``) because levels are dense — same semantics,
  zero memory.
* **Pending buffer**: sorted ``pkeys/pvals/ptomb`` of capacity ``PC`` holds
  keys inserted since the last rebuild (the paper's between-rebuild
  linked-list inserts: visible to search immediately, invisible to the
  index layer until the deferred rebuild, §3.2.3).

Everything is a fixed-shape pytree → jit/shard_map friendly.  The batch
semantics (sorted query set, intra-batch visibility, last-writer-wins) are
resolved with the segmented scans in ``core.batch`` and validated against
``core.ref.RefIndex``.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.runtime import trace_guard
from repro.core.batch import SEARCH, INSERT, DELETE, seg_last_write_scan, sort_queries
from repro.core.engine import BACKENDS, get_engine, sentinel_for

KSENT_I32 = jnp.iinfo(jnp.int32).max  # padding key: sorts after every real key

_sentinel = sentinel_for


@dataclasses.dataclass(frozen=True)
class PIConfig:
    """Static geometry of one PI shard.

    fanout F plays the role of both the promotion probability (P = 1/F) and
    the entry width M: the paper uses P=0.25, M=4 (one 128-bit SSE vector);
    on TPU an "entry" should fill VPU lanes, so benchmarks also use F=8/16.
    """

    capacity: int = 1 << 16          # C  — max live+tombstoned storage slots
    pending_capacity: int = 1 << 12  # PC — max inserts between rebuilds
    fanout: int = 4                  # F  — keys per entry == 1/P
    key_dtype: str = "int32"
    rebuild_frac: float = 0.15       # paper: rebuild after 15% of N updates
    backend: str = "xla"             # search engine: xla|pallas|pallas-interpret
    tile_q: int = 256                # Pallas query-tile width (grid step)
    seg_width: int = 0               # W — slots per gapped segment (0 = auto)
    max_dirty_frac: float = 0.25     # incremental rebuild cap: dirty/S ratio

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"backend {self.backend!r} not in {BACKENDS}")
        if self.seg_width:
            w = self.seg_width
            if self.capacity % w:
                raise ValueError(
                    f"seg_width {w} must divide capacity {self.capacity}")
            if w != self.capacity:
                j = w
                while j > 1 and j % self.fanout == 0:
                    j //= self.fanout
                if j != 1 or w < self.fanout:
                    raise ValueError(
                        f"seg_width {w} must be a power of fanout "
                        f"{self.fanout} (invariant L5) or == capacity")

    @property
    def num_levels(self) -> int:
        """H: number of index-layer levels (levels 1..H above storage)."""
        h = 0
        size = self.capacity
        while size > self.fanout:
            size = -(-size // self.fanout)
            h += 1
        return h

    def level_size(self, lvl: int) -> int:
        size = self.capacity
        for _ in range(lvl):
            size = -(-size // self.fanout)
        return size

    @property
    def seg_width_eff(self) -> int:
        """W: slots per gapped segment.

        Auto (``seg_width == 0``) picks the largest power of ``fanout``
        that is <= min(256, capacity // fanout) and divides ``capacity``;
        if no such power exists the layout degenerates to one
        capacity-wide segment — exactly the old monolithic array, with
        every rebuild a full repack.
        """
        if self.seg_width:
            return self.seg_width
        target = min(256, max(self.fanout, self.capacity // self.fanout))
        w = self.fanout
        while w * self.fanout <= target:
            w *= self.fanout
        while w >= self.fanout and self.capacity % w:
            w //= self.fanout
        return w if w >= self.fanout else self.capacity

    @property
    def num_segments(self) -> int:
        """S: segment count (C == S * W)."""
        return self.capacity // self.seg_width_eff

    @property
    def max_dirty(self) -> int:
        """D: static bound on segments one incremental rebuild may touch."""
        s = self.num_segments
        return max(1, min(s, int(s * self.max_dirty_frac)))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PIIndex:
    """One PI shard (one 'NUMA node' in the paper)."""

    # storage layer (segmented gapped: S sorted runs + KSENT slack tails)
    keys: jnp.ndarray   # (C,)  = (S*W,), invariants L1-L5 (module docstring)
    vals: jnp.ndarray   # (C,)  int32 value "pointers"
    tomb: jnp.ndarray   # (C,)  bool F_del
    n: jnp.ndarray      # ()    occupied (non-KSENT) slots: live + tombstoned
    # index layer (levels 1..H, contiguous per level)
    levels: Tuple[jnp.ndarray, ...]
    # pending buffer (storage-layer inserts awaiting rebuild)
    pkeys: jnp.ndarray  # (PC,) sorted, KSENT-padded
    pvals: jnp.ndarray
    ptomb: jnp.ndarray
    pn: jnp.ndarray     # ()
    # bookkeeping
    n_updates: jnp.ndarray  # () inserts+deletes since last rebuild
    overflow: jnp.ndarray   # () bool — pending buffer overflowed (data loss!)
    config: PIConfig = dataclasses.field(metadata=dict(static=True))

    # -- pytree plumbing ---------------------------------------------------
    def tree_flatten(self):
        children = (self.keys, self.vals, self.tomb, self.n, self.levels,
                    self.pkeys, self.pvals, self.ptomb, self.pn,
                    self.n_updates, self.overflow)
        return children, self.config

    @classmethod
    def tree_unflatten(cls, config, children):
        return cls(*children, config=config)

    # -- derived -----------------------------------------------------------
    @property
    def live_count(self) -> jnp.ndarray:
        sent = _sentinel(self.keys.dtype)
        main = jnp.sum((self.keys != sent) & ~self.tomb)
        pidx = jnp.arange(self.pkeys.shape[0])
        pend = jnp.sum((pidx < self.pn) & ~self.ptomb)
        return main + pend


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------

def _build_levels(cfg: PIConfig, keys: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
    """Index layer = every F**l-th storage key, per level, KSENT-padded.

    This is the paper's bottom-up O(N) rebuild (§4.1): one strided gather
    per level.  Determinism note (DESIGN.md): with contiguous levels the
    key "height" is a function of rank, not a random draw — the geometry
    (expected 1/P gap) is identical to the paper's post-rebuild layout.
    """
    sent = _sentinel(keys.dtype)
    levels = []
    for lvl in range(1, cfg.num_levels + 1):
        size = cfg.level_size(lvl)
        stride = cfg.fanout ** lvl
        src = jnp.arange(size) * stride
        levels.append(jnp.take(keys, src, mode="fill", fill_value=sent))
    return tuple(levels)


def _spread(cfg: PIConfig, sk: jnp.ndarray, sv: jnp.ndarray,
            n_keep: jnp.ndarray):
    """Distribute the first ``n_keep`` sorted keys evenly over the segments.

    Every segment receives floor(n_keep/S) keys and the first
    ``n_keep mod S`` segments take one extra, so fuller runs pack to the
    front and empty segments (if any) sit at the global tail (invariant
    L4).  ``sk``/``sv`` may be any length; slots past ``n_keep`` become
    KSENT slack.  Returns the (C,) keys and vals arrays.
    """
    W, S, C = cfg.seg_width_eff, cfg.num_segments, cfg.capacity
    kdt = sk.dtype
    sent = _sentinel(kdt)
    n_keep = n_keep.astype(jnp.int32)
    base = n_keep // S
    extra = n_keep % S
    i = jnp.arange(C, dtype=jnp.int32)
    cut = extra * (base + 1)          # keys before `cut` land base+1 per seg
    big = jnp.maximum(base + 1, 1)
    sml = jnp.maximum(base, 1)
    seg = jnp.where(i < cut, i // big, extra + (i - cut) // sml)
    off = jnp.where(i < cut, i % big, (i - cut) % sml)
    tgt = jnp.where(i < n_keep, seg * W + off, C)  # OOB => dropped
    if sk.shape[0]:
        src_k = jnp.take(sk, i, mode="fill", fill_value=sent)
        src_v = jnp.take(sv, i, mode="fill", fill_value=0)
    else:  # building from zero keys: jnp.take rejects empty source axes
        src_k = jnp.full((C,), sent, kdt)
        src_v = jnp.zeros((C,), jnp.int32)
    keys2 = jnp.full((C,), sent, kdt).at[tgt].set(src_k, mode="drop")
    vals2 = jnp.zeros((C,), jnp.int32).at[tgt].set(src_v, mode="drop")
    return keys2, vals2


def build(cfg: PIConfig, keys: jnp.ndarray, vals: jnp.ndarray) -> PIIndex:
    """Build a PI shard from (not necessarily sorted) unique keys."""
    kdt = jnp.dtype(cfg.key_dtype)
    sent = _sentinel(kdt)
    n = keys.shape[0]
    if n > cfg.capacity:
        raise ValueError(f"{n} keys > capacity {cfg.capacity}")
    order = jnp.argsort(keys)
    keys_s, vals_s = _spread(cfg, keys.astype(kdt)[order],
                             vals.astype(jnp.int32)[order],
                             jnp.array(n, jnp.int32))
    pc = cfg.pending_capacity
    return PIIndex(
        keys=keys_s,
        vals=vals_s,
        tomb=jnp.zeros((cfg.capacity,), bool),
        n=jnp.array(n, jnp.int32),
        levels=_build_levels(cfg, keys_s),
        pkeys=jnp.full((pc,), sent, kdt),
        pvals=jnp.zeros((pc,), jnp.int32),
        ptomb=jnp.zeros((pc,), bool),
        pn=jnp.array(0, jnp.int32),
        n_updates=jnp.array(0, jnp.int32),
        overflow=jnp.array(False),
        config=cfg,
    )


def empty(cfg: PIConfig) -> PIIndex:
    kdt = jnp.dtype(cfg.key_dtype)
    return build(cfg, jnp.zeros((0,), kdt), jnp.zeros((0,), jnp.int32))


# ---------------------------------------------------------------------------
# traversal (the paper's Alg. 2 — index-layer BFS descent, via the engine)
# ---------------------------------------------------------------------------

def with_backend(index: PIIndex, backend: str, tile_q: int | None = None
                 ) -> PIIndex:
    """Same index state, different search backend (zero-copy rewrap)."""
    cfg = dataclasses.replace(
        index.config, backend=backend,
        tile_q=index.config.tile_q if tile_q is None else tile_q)
    return dataclasses.replace(index, config=cfg)


def traverse(index: PIIndex, q: jnp.ndarray) -> jnp.ndarray:
    """Floor positions: the slot i whose key is the largest key <= q, or -1.

    The descent itself (vectorized Alg. 2) lives in ``core.engine``; the
    backend ``index.config.backend`` selects whether the descent runs as
    stock jnp ops or as the Pallas kernel.  The returned position is the
    paper's *interception*, which with rank-strided levels is already the
    exact storage-layer floor (no residual walk; the paper walks an
    expected (1+P)/2P nodes here).  On the segmented gapped layout the
    position is a *slot* index, not a dense rank: slots are monotone in
    the query key, but not consecutive across segment slack.
    """
    return get_engine(index.config).floor(index, q)


def _probe(index: PIIndex, q: jnp.ndarray):
    """Engine probe + the liveness gathers the engine leaves to us.

    Returns (pos, main_match, main_live, main_val, ppos, p_match, p_live):
    the per-query pre-batch view of both layers, identical across backends.
    """
    pr = get_engine(index.config).probe(index, q)
    pos_c = jnp.maximum(pr.pos, 0)
    main_live = pr.main_match & ~jnp.take(index.tomb, pos_c)
    main_val = jnp.take(index.vals, pos_c)
    p_match = pr.p_hit & (pr.ppos < index.pn)
    p_live = p_match & ~jnp.take(index.ptomb, pr.ppos)
    return pr.pos, pr.main_match, main_live, main_val, pr.ppos, p_match, \
        p_live


def lookup(index: PIIndex, q: jnp.ndarray):
    """Batched point lookup → (found, val).  found=False is the paper's null."""
    _, _, main_live, main_val, ppos, _, p_live = _probe(
        index, q.astype(index.keys.dtype))
    p_val = jnp.take(index.pvals, ppos)
    found = main_live | p_live
    val = jnp.where(p_live, p_val, main_val)
    return found, jnp.where(found, val, 0)


# ---------------------------------------------------------------------------
# batch execution (Alg. 1 = partition→traverse→redistribute→execute)
# ---------------------------------------------------------------------------

# Bumped on every *trace* of execute_impl (Python side effects run at
# trace time only): under jit this counts compilations, not calls.  The
# serving pipeline pads every tick to one static width precisely so this
# stays at 1 — suites and benchmarks assert it through the guard's
# canonical message (analysis/runtime.py; deltas via
# execute_trace_count()).
_TRACES = trace_guard("core.execute")


def execute_trace_count() -> int:
    return _TRACES.count()


def execute_impl(index: PIIndex, ops: jnp.ndarray, qkeys: jnp.ndarray,
                 qvals: jnp.ndarray):
    """Execute one query batch; returns (new_index, (found, vals)).

    Semantics == core.ref.RefIndex.execute: queries sorted by key (stable on
    arrival), each query sees earlier-arriving writes to its key segment.
    The per-thread sequential walk of Alg. 4 becomes a segmented
    last-writer scan (core.batch); the Alg. 3 ownership handoff is implicit
    in the functional bulk update — every storage slot is written by exactly
    one scatter lane (the segment tail), which *is* the paper's
    "each modified node is owned by exactly one thread" invariant.
    """
    _TRACES.bump()
    cfg = index.config
    B = ops.shape[0]
    kdt = index.keys.dtype
    sent = _sentinel(kdt)

    with jax.named_scope("pi.step.order"):
        perm, s_ops, s_keys, s_vals = sort_queries(ops, qkeys.astype(kdt),
                                                   qvals)
        newseg = jnp.concatenate(
            [jnp.ones((1,), bool), s_keys[1:] != s_keys[:-1]])
        is_write = s_ops != SEARCH
        is_del = s_ops == DELETE
        (inc_has, inc_val, inc_tomb), (exc_has, exc_val, exc_tomb) = (
            seg_last_write_scan(newseg, is_write, s_vals, is_del))

    # --- store state per query (pre-batch view, one fused engine probe) ---
    with jax.named_scope("pi.step.probe"):
        pos, main_match, main_live, main_val, ppos, p_match, p_live = \
            _probe(index, s_keys)
        pos_c = jnp.maximum(pos, 0)
        store_found = main_live | p_live
        store_val = jnp.where(p_live, jnp.take(index.pvals, ppos), main_val)

    # --- per-query results (visibility: exclusive scan > store) -----------
    with jax.named_scope("pi.step.order"):
        vis_found = jnp.where(exc_has, ~exc_tomb, store_found)
        vis_val = jnp.where(exc_has, exc_val, store_val)
        r_found = jnp.where(s_ops == SEARCH, vis_found,
                            jnp.where(is_del, vis_found, False))
        r_val = jnp.where(s_ops == SEARCH, jnp.where(vis_found, vis_val, 0),
                          jnp.where(is_del & vis_found, 1, 0))

        inv = jnp.argsort(perm)
        results = (r_found[inv], r_val[inv])

    # --- net effects: one writer per key segment (segment tails) ----------
    with jax.named_scope("pi.step.apply"):
        seg_end = jnp.concatenate([newseg[1:], jnp.ones((1,), bool)])
        apply_w = seg_end & inc_has
        # 1) key already in main storage → in-place update (Alg. 4 lines 11-15)
        upd_main = apply_w & main_match
        tgt = jnp.where(upd_main, pos_c, cfg.capacity)  # OOB ⇒ dropped
        vals2 = index.vals.at[tgt].set(
            jnp.where(inc_tomb, main_val, inc_val), mode="drop")
        tomb2 = index.tomb.at[tgt].set(inc_tomb, mode="drop")
        # 2) key in pending buffer → in-place update there
        upd_pend = apply_w & ~main_match & p_match
        ptgt = jnp.where(upd_pend, ppos, cfg.pending_capacity)
        pvals2 = index.pvals.at[ptgt].set(
            jnp.where(inc_tomb, jnp.take(index.pvals, ppos), inc_val),
            mode="drop")
        ptomb2 = index.ptomb.at[ptgt].set(inc_tomb, mode="drop")
    # 3) brand-new key, net insert → append to pending (sorted merge)
    with jax.named_scope("pi.step.merge"):
        new_ins = apply_w & ~main_match & ~p_match & ~inc_tomb
        addk = jnp.where(new_ins, s_keys, sent)
        addv = jnp.where(new_ins, inc_val, 0)
        mk = jnp.concatenate([index.pkeys, addk])
        mv = jnp.concatenate([pvals2, addv])
        mt = jnp.concatenate([ptomb2, jnp.zeros((B,), bool)])
        # hide slots beyond pn so stale tails don't resurrect
        pidx = jnp.arange(cfg.pending_capacity)
        mk = mk.at[:cfg.pending_capacity].set(
            jnp.where(pidx < index.pn, mk[:cfg.pending_capacity], sent))
        order = jnp.argsort(mk)
        mk, mv, mt = mk[order], mv[order], mt[order]
        pn2 = jnp.minimum(index.pn + jnp.sum(new_ins),
                          cfg.pending_capacity).astype(jnp.int32)
        overflow2 = index.overflow | (
            index.pn + jnp.sum(new_ins) > cfg.pending_capacity)

    n_upd = index.n_updates + jnp.sum(apply_w).astype(jnp.int32)
    new_index = PIIndex(
        keys=index.keys, vals=vals2, tomb=tomb2, n=index.n,
        levels=index.levels,
        pkeys=mk[:cfg.pending_capacity], pvals=mv[:cfg.pending_capacity],
        ptomb=mt[:cfg.pending_capacity], pn=pn2,
        n_updates=n_upd, overflow=overflow2, config=cfg)
    return new_index, results


execute = jax.jit(execute_impl, donate_argnums=0)


def needs_rebuild(index: PIIndex) -> jnp.ndarray:
    """Paper §4.3.5: daemon rebuilds after threshold (15% of N) updates.

    The threshold is exact integer arithmetic: ``rebuild_frac`` is frozen
    to a /1024 rational at trace time and ``ceil(n * num / 1024)`` is
    computed with a split multiply so it neither loses integer precision
    in float32 (n > 2**24) nor overflows int32.
    """
    num = int(round(index.config.rebuild_frac * 1024))
    q, r = jnp.divmod(index.n.astype(jnp.int32), 1024)
    thresh = jnp.maximum(q * num + (r * num + 1023) // 1024, 1)
    near_full = index.pn > (index.config.pending_capacity * 3) // 4
    return (index.n_updates >= thresh) | near_full | index.overflow


def _fresh_pending(cfg: PIConfig, kdt):
    sent = _sentinel(kdt)
    PC = cfg.pending_capacity
    return dict(
        pkeys=jnp.full((PC,), sent, kdt),
        pvals=jnp.zeros((PC,), jnp.int32),
        ptomb=jnp.zeros((PC,), bool),
        pn=jnp.array(0, jnp.int32),
        n_updates=jnp.array(0, jnp.int32))


def _route_pending(index: PIIndex):
    """Route live pending keys to their destination segments.

    A segment is *dirty* iff at least one live pending key lands in its
    range (``searchsorted`` on the segment fences ``keys[::W]``) — the
    per-segment dirty bitmap of the gapped layout, in sorted-compact form.

    Returns ``(p_live, order, n_dirty, dirty, npend)``:
      p_live : (PC,) live pending mask
      order  : (PC,) slot of the (j+1)-th live pending entry (PC past the
               live count) — live pending in ascending key order, which is
               automatically grouped by destination segment
      n_dirty: ()   number of distinct dirty segments
      dirty  : (D,) ascending distinct dirty segment ids, padded with S
      npend  : (D,) live pending keys routed to each dirty segment

    Sort- and scatter-free: the pending buffer is kept sorted, so live
    destinations are already non-decreasing and every quantity here falls
    out of cumsums, vectorized binary searches and gathers — O(PC log PC)
    compares, no O(PC log PC) sort and none of XLA:CPU's serialized
    scatters.  (The j-th live slot is recovered from the live-mask cumsum
    by binary search; the d-th distinct dirty id likewise from the
    first-occurrence cumsum.)
    """
    cfg = index.config
    W, S = cfg.seg_width_eff, cfg.num_segments
    D = min(cfg.max_dirty, cfg.pending_capacity)
    PC = cfg.pending_capacity
    pidx = jnp.arange(PC, dtype=jnp.int32)
    p_live = (pidx < index.pn) & ~index.ptomb
    fences = index.keys[::W]                       # (S,) first key per segment
    dest = jnp.searchsorted(
        fences, index.pkeys, side="right").astype(jnp.int32) - 1
    dest = jnp.where(p_live, jnp.clip(dest, 0, S - 1), S)
    c_live = jnp.cumsum(p_live.astype(jnp.int32))
    order = jnp.searchsorted(c_live, pidx + 1, side="left").astype(jnp.int32)
    d_live = jnp.take(dest, order, mode="fill", fill_value=S)  # non-decr.
    first = (d_live < S) & jnp.concatenate(
        [jnp.ones((1,), bool), d_live[1:] != d_live[:-1]])
    c_first = jnp.cumsum(first.astype(jnp.int32))
    n_dirty = c_first[-1]
    q = jnp.searchsorted(c_first, jnp.arange(1, D + 1, dtype=jnp.int32),
                         side="left")
    dirty = jnp.take(d_live, q, mode="fill", fill_value=S)
    npend = (jnp.searchsorted(d_live, dirty, side="right")
             - jnp.searchsorted(d_live, dirty, side="left")).astype(
                 jnp.int32)
    npend = jnp.where(dirty < S, npend, 0)
    return p_live, order, n_dirty, dirty, npend


def incremental_fits(index: PIIndex) -> jnp.ndarray:
    """True iff the incremental merge can absorb the pending buffer.

    Two static bounds gate the cheap path: the dirty set must fit the
    ``max_dirty`` gather width, and every dirty segment's merged run
    (live keys after tombstone compaction + routed pending keys) must fit
    its ``W`` slots — slack exhaustion falls back to the full repack,
    which re-spreads the slack evenly (the segment split/rebalance).
    """
    cfg = index.config
    W, S = cfg.seg_width_eff, cfg.num_segments
    sent = _sentinel(index.keys.dtype)
    _, _, n_dirty, dirty, npend = _route_pending(index)
    D = dirty.shape[0]
    dk = jnp.take(index.keys.reshape(S, W), dirty, axis=0,
                  mode="fill", fill_value=sent)
    dt = jnp.take(index.tomb.reshape(S, W), dirty, axis=0,
                  mode="fill", fill_value=False)
    cnt = jnp.sum((dk != sent) & ~dt, axis=1).astype(jnp.int32)
    return (n_dirty <= D) & jnp.all(cnt + npend <= W)


@jax.named_scope("pi.rebuild.incremental")
def _rebuild_incremental(index: PIIndex) -> PIIndex:
    """Churn-proportional rebuild: merge pending keys into dirty segments.

    Cost scales with the dirty set (a (D, W) gather + one batched
    fixed-width key sort + rank-arithmetic value lookups + scatter-back),
    not with capacity.  Clean segments — storage AND the index-layer
    entries above them — are untouched.  Tombstones are compacted only
    inside dirty segments; clean-segment tombstones stay until their
    segment dirties or a repack runs (they are invisible to queries
    either way).  Only callable when ``incremental_fits`` holds; dirty
    segments receive >= 1 key, so no mid-array empty segment can appear
    (invariant L4 is preserved).

    The merge avoids XLA:CPU's slow paths on purpose: keys go through a
    single-operand ``sort`` (vectorized fast path — the variadic
    key/payload comparator sort behind ``argsort`` is ~6x slower), and
    values are recovered by binary-searching each merged key back into
    its source row — legal because a segment row is sorted (L1/L2), the
    routed pending run is sorted, and pending keys never collide with
    occupied storage slots (``execute`` updates those in place).
    """
    cfg = index.config
    W, S, C = cfg.seg_width_eff, cfg.num_segments, cfg.capacity
    PC = cfg.pending_capacity
    kdt = index.keys.dtype
    sent = _sentinel(kdt)
    p_live, order, _, dirty, npend = _route_pending(index)
    D = dirty.shape[0]
    kseg = index.keys.reshape(S, W)
    vseg = index.vals.reshape(S, W)
    tseg = index.tomb.reshape(S, W)
    dk = jnp.take(kseg, dirty, axis=0, mode="fill", fill_value=sent)
    dv = jnp.take(vseg, dirty, axis=0, mode="fill", fill_value=0)
    dt = jnp.take(tseg, dirty, axis=0, mode="fill", fill_value=False)
    n_tomb = jnp.sum(dt).astype(jnp.int32)
    blank = jnp.where(dt, sent, dk)     # drop tombstones from the merge
    # gather each dirty row's routed pending run: live pending is sorted
    # by key, hence contiguous per destination segment; row d's run spans
    # live slots [start_d, start_d + npend_d)
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(npend)[:-1].astype(jnp.int32)])
    col = jnp.arange(W, dtype=jnp.int32)
    valid = col[None, :] < npend[:, None]
    slot = jnp.where(valid, start[:, None] + col[None, :], PC)
    src = jnp.take(order, slot.reshape(-1), mode="fill",
                   fill_value=PC).reshape(D, W)
    pk = jnp.where(valid, jnp.take(index.pkeys, src.reshape(-1), mode="fill",
                                   fill_value=sent).reshape(D, W), sent)
    pv = jnp.where(valid, jnp.take(index.pvals, src.reshape(-1), mode="fill",
                                   fill_value=0).reshape(D, W), 0)
    # merged keys: one single-operand sort; `incremental_fits` guarantees
    # <= W survivors per row, so the dropped tail is all-sentinel
    mk = jnp.sort(jnp.concatenate([blank, pk], axis=1), axis=1)[:, :W]
    # values by rank lookup into the two sorted sources
    vss = jax.vmap(
        lambda t, qs: jnp.searchsorted(t, qs, side="left").astype(jnp.int32))
    i = jnp.clip(vss(dk, mk), 0, W - 1)
    from_run = jnp.take_along_axis(dk, i, axis=1) == mk
    j = jnp.clip(vss(pk, mk), 0, W - 1)
    mv = jnp.where(from_run, jnp.take_along_axis(dv, i, axis=1),
                   jnp.take_along_axis(pv, j, axis=1))
    mv = jnp.where(mk != sent, mv, 0)
    keys2 = kseg.at[dirty].set(mk, mode="drop").reshape(C)
    vals2 = vseg.at[dirty].set(mv, mode="drop").reshape(C)
    tomb2 = tseg.at[dirty].set(jnp.zeros((D, W), bool),
                               mode="drop").reshape(C)
    n2 = (index.n - n_tomb + jnp.sum(p_live)).astype(jnp.int32)
    # regenerate index-layer entries above the touched segments only.
    # stride <= W: the W//stride entries inside each dirty segment.
    # stride >  W: at most one entry can read from a dirty segment (the
    # one at floor(s*W/stride)); rewriting it with the fresh storage value
    # is correct whether or not it actually moved.
    levels = []
    for lvl in range(1, cfg.num_levels + 1):
        stride = cfg.fanout ** lvl
        if stride <= W:
            per = W // stride
            p = (dirty[:, None] * per
                 + jnp.arange(per, dtype=jnp.int32)[None, :]).reshape(-1)
        else:
            p = dirty * W // stride
        ent = jnp.take(keys2, p * stride, mode="fill", fill_value=sent)
        levels.append(index.levels[lvl - 1].at[p].set(ent, mode="drop"))
    return PIIndex(
        keys=keys2, vals=vals2, tomb=tomb2, n=n2, levels=tuple(levels),
        overflow=jnp.array(False), config=cfg,
        **_fresh_pending(cfg, kdt))


@jax.named_scope("pi.rebuild.repack")
def _rebuild_repack(index: PIIndex) -> PIIndex:
    """Full repack (paper §4.1/§4.3.5, made a sort+spread).

    Compacts every tombstone, merges the pending buffer, re-spreads the
    slack evenly across all segments (the gapped layout's segment
    rebalance) and regenerates every index-layer level bottom-up.
    O(C log C) — the rare fallback; `_rebuild_incremental` is the
    churn-proportional fast path.

    If live keys exceed capacity the largest overflowing tail is dropped
    and the ``overflow`` flag is raised on the NEW state (observable data
    loss, not silent truncation); it stays up until the next rebuild,
    which by then operates on the truncated key set.
    """
    cfg = index.config
    kdt = index.keys.dtype
    sent = _sentinel(kdt)
    C, PC = cfg.capacity, cfg.pending_capacity
    m_live = (index.keys != sent) & ~index.tomb
    pidx = jnp.arange(PC)
    p_live = (pidx < index.pn) & ~index.ptomb
    allk = jnp.concatenate([jnp.where(m_live, index.keys, sent),
                            jnp.where(p_live, index.pkeys, sent)])
    allv = jnp.concatenate([index.vals, index.pvals])
    order = jnp.argsort(allk)
    n_live = (jnp.sum(m_live) + jnp.sum(p_live)).astype(jnp.int32)
    over = n_live > C
    n2 = jnp.minimum(n_live, C)
    keys2, vals2 = _spread(cfg, jnp.take(allk, order),
                           jnp.take(allv, order), n2)
    return PIIndex(
        keys=keys2, vals=vals2, tomb=jnp.zeros((C,), bool), n=n2,
        levels=_build_levels(cfg, keys2),
        overflow=over, config=cfg,
        **_fresh_pending(cfg, kdt))


@jax.jit
def rebuild(index: PIIndex) -> PIIndex:
    """Deferred rebuild, two-tier (paper §4.1/§4.3.5 + gapped segments).

    Takes the churn-proportional incremental merge when the pending keys'
    dirty segment set is small and every merged run fits its segment;
    falls back to the full repack otherwise (slack exhausted, dirty set
    too wide, or pending overflow pinned the flag).  Both tiers leave the
    pending buffer empty and the update counter at zero; both preserve
    invariants L1-L5, so the engines never see the difference.
    """
    return jax.lax.cond(
        incremental_fits(index) & ~index.overflow,
        _rebuild_incremental, _rebuild_repack, index)


def rebuild_if_due(index: PIIndex):
    """The rebuild daemon's step: rebuild iff ``needs_rebuild``.

    Returns ``(index', due, incremental)``: ``incremental`` reports which
    tier a due rebuild took (the segmented merge vs the full repack) so
    callers can attribute rebuild cost to churn, not capacity.  The tier
    probe lives inside the due-branch, so a window that does not rebuild
    pays nothing for it.
    """
    with jax.named_scope("pi.step.rebuild"):
        due = needs_rebuild(index)
        index, incr = jax.lax.cond(
            due,
            lambda i: (rebuild(i), incremental_fits(i) & ~i.overflow),
            lambda i: (i, jnp.array(False)),
            index)
    return index, due, incr


def maybe_rebuild(index: PIIndex) -> PIIndex:
    """Branchless 'daemon': rebuild iff the update threshold tripped."""
    return rebuild_if_due(index)[0]


# Sanctioned forced-repack entry (PI001): the breaker's reclaim path and
# the offline rebuild benchmarks share this one compiled program instead
# of each jitting the private internal.
repack = jax.jit(_rebuild_repack)


# ---------------------------------------------------------------------------
# range queries (paper §3.2.5 / Fig. 14)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=3)
def range_agg(index: PIIndex, lo: jnp.ndarray, hi: jnp.ndarray,
              max_span: int = 1024):
    """Batched range query → (count, sum_of_vals) over keys in [lo, hi].

    Walks up to ``max_span`` *occupied* slots from the interception of
    ``lo`` (the paper's storage-layer scan), plus a broadcast pass over
    the pending buffer.  On the segmented gapped layout the walk advances
    through occupied ranks — segment slack inside the walked window never
    consumes span budget, so ``max_span`` counts real keys exactly as it
    did on the pre-gapped dense layout (tombstoned slots keep their key
    and rank, hence still consume budget, but are gated out of the
    aggregate).  Dispatches ``SearchEngine.range_agg``: the ``xla``
    backend computes it with stock jnp; both Pallas backends fuse descent
    + rank walk + pending pass into one ``kernels.pi_range`` launch.
    """
    kdt = index.keys.dtype
    return get_engine(index.config).range_agg(
        index, lo.astype(kdt), hi.astype(kdt), max_span)


# convenience wrappers ------------------------------------------------------

def search_batch(index: PIIndex, keys: jnp.ndarray):
    ops = jnp.full(keys.shape, SEARCH, jnp.int32)
    vals = jnp.zeros(keys.shape, jnp.int32)
    return execute(index, ops, keys, vals)


def insert_batch(index: PIIndex, keys: jnp.ndarray, vals: jnp.ndarray):
    ops = jnp.full(keys.shape, INSERT, jnp.int32)
    return execute(index, ops, keys, vals)


def delete_batch(index: PIIndex, keys: jnp.ndarray):
    ops = jnp.full(keys.shape, DELETE, jnp.int32)
    vals = jnp.zeros(keys.shape, jnp.int32)
    return execute(index, ops, keys, vals)


# ---------------------------------------------------------------------------
# host-side introspection (tests / resharding / benchmarks)
# ---------------------------------------------------------------------------

def live_items(index: PIIndex):
    """All live (key, val) pairs across both layers, sorted by key (numpy).

    The occupancy test is ``key != KSENT`` — never a dense ``[:n]`` prefix,
    which the gapped layout does not have.
    """
    sent = int(jnp.asarray(_sentinel(index.keys.dtype)))
    keys = np.asarray(index.keys)
    vals = np.asarray(index.vals)
    m = (keys != sent) & ~np.asarray(index.tomb)
    pn = int(index.pn)
    pk = np.asarray(index.pkeys)[:pn]
    pv = np.asarray(index.pvals)[:pn]
    pm = ~np.asarray(index.ptomb)[:pn]
    k = np.concatenate([keys[m], pk[pm]])
    v = np.concatenate([vals[m], pv[pm]])
    order = np.argsort(k, kind="stable")
    return k[order], v[order]


def validate_layout(index: PIIndex) -> bool:
    """Assert the segmented-layout invariants L1-L5 plus bookkeeping.

    Host-side (materializes the state); raises AssertionError with the
    violated invariant, returns True otherwise.  Tests call this after
    every mutation path; production code never needs to.
    """
    cfg = index.config
    W, S = cfg.seg_width_eff, cfg.num_segments
    assert S * W == cfg.capacity, "geometry: S*W != C"
    sent = int(jnp.asarray(_sentinel(index.keys.dtype)))
    keys = np.asarray(index.keys)
    seg = keys.reshape(S, W)
    occ = seg != sent
    # L1: run prefix + slack tail (occupancy never rises within a row)
    assert not np.any(~occ[:, :-1] & occ[:, 1:]), "L1: gap inside a run"
    # L2: strictly increasing runs
    wide = seg.astype(np.int64)
    run_ok = np.diff(wide, axis=1) > 0
    assert np.all(run_ok | ~(occ[:, :-1] & occ[:, 1:])), "L2: run unsorted"
    # L3: runs ordered across segments; L4: empties only at the tail
    nonempty = occ.any(axis=1)
    ne = np.flatnonzero(nonempty)
    assert ne.size == 0 or ne[-1] == ne.size - 1, "L4: mid-array empty seg"
    lasts = [wide[s][occ[s]][-1] for s in ne]
    firsts = [wide[s][occ[s]][0] for s in ne]
    assert all(lasts[i] < firsts[i + 1] for i in range(len(ne) - 1)), \
        "L3: segments out of order"
    # bookkeeping: n counts occupied slots; tombstones only on occupied
    assert int(index.n) == int(occ.sum()), "n != occupied slots"
    assert not np.any(np.asarray(index.tomb).reshape(S, W) & ~occ), \
        "tombstone on a slack slot"
    # index layer must equal a fresh bottom-up build over these keys
    for lvl, (got, want) in enumerate(
            zip(index.levels, _build_levels(cfg, jnp.asarray(keys))), 1):
        assert np.array_equal(np.asarray(got), np.asarray(want)), \
            f"level {lvl} stale"
    # pending: sorted unique live prefix, sentinel tail
    pk = np.asarray(index.pkeys).astype(np.int64)
    pn = int(index.pn)
    assert np.all(pk[pn:] == sent), "pending tail not sentinel"
    assert np.all(np.diff(pk[:pn]) > 0), "pending prefix unsorted"
    return True
