"""SearchEngine — pluggable batched floor-search over one PI shard.

The paper's entire speedup story is the SIMD BFS descent (Alg. 2).  This
module makes that descent a *routing decision* instead of an inline loop:
every traversal consumer (``lookup``, ``execute``, ``range_agg``, the
sharded executor) asks the engine for positions, and the engine dispatches
one of three backends (DESIGN.md §3):

================  ==========================================================
backend           what runs
================  ==========================================================
``xla``           plain-jnp descent + ``jnp.searchsorted`` pending probe —
                  portable baseline, fuses fine under XLA on any device.
``pallas``        ``kernels.pi_search.pi_probe`` with the real TPU launch
                  geometry (Mosaic lowering; requires a TPU backend).
``pallas-interpret``  the same kernel in interpret mode — the exact grid
                  computation, executable (and CI-testable) on CPU.
================  ==========================================================

The engine primitive is ``probe``: ONE batched call that returns the
storage-layer floor position, the pending-buffer insertion point and the
key-equality match flags for a whole query batch.  Both Pallas backends
compute all three in a single fused kernel launch; the ``xla`` backend
computes the identical values with stock jnp ops, so backends are
bit-identical by construction and testable against ``core.ref.RefIndex``.

Liveness (tombstones, ``pn`` high-water mark) is intentionally *not* the
engine's business — those are cheap gathers the caller applies on top, and
keeping them out lets one kernel serve lookups, executes and range scans.

Segmented gapped storage (core.index module docstring, invariants L1-L5):
the descent runs UNCHANGED on the gapped layout.  Within a segment each
F-key child group is an ascending run prefix + KSENT slack, and KSENT
sorts after every real key, so the rank popcount still lands on the floor
slot; because ``W`` is a power of the fanout, a child group either lies
inside one segment or is a whole number of segments, so no group ever
straddles a partially-filled segment out of order.  Positions are gapped
*slot* indices (monotone in the key, not dense ranks).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.kernels.pi_range import pi_range
from repro.kernels.pi_search import (FLAG_MAIN_MATCH, FLAG_PENDING_HIT,
                                     pi_probe, pi_search, sentinel_for)

BACKENDS = ("xla", "pallas", "pallas-interpret")


@dataclasses.dataclass(frozen=True)
class Probe:
    """Per-query result of the fused floor-search primitive.

    ``pos`` is raw (may be −1 on underflow, or past the live region for
    sentinel queries); ``ppos`` is clipped to the pending capacity, like
    the historical ``_pending_lookup``.  ``p_hit`` is a *key* match within
    the pending array — the caller still intersects with ``ppos < pn``.
    """

    pos: jnp.ndarray         # (B,) int32 storage floor position, −1 = below
    main_match: jnp.ndarray  # (B,) bool  storage key at pos equals query
    ppos: jnp.ndarray        # (B,) int32 clipped pending insertion point
    p_hit: jnp.ndarray       # (B,) bool  pending key at ppos equals query


class SearchEngine:
    """Backend-selectable descent over index layer + pending buffer."""

    def __init__(self, backend: str = "xla", tile_q: int = 256):
        if backend not in BACKENDS:
            raise ValueError(
                f"unknown search backend {backend!r}; pick one of {BACKENDS}")
        self.backend = backend
        self.tile_q = tile_q

    def __repr__(self):
        return f"SearchEngine(backend={self.backend!r}, tile_q={self.tile_q})"

    @property
    def uses_pallas(self) -> bool:
        return self.backend != "xla"

    @property
    def interpret(self) -> bool:
        return self.backend == "pallas-interpret"

    # -- primitives --------------------------------------------------------

    def floor(self, index, q: jnp.ndarray) -> jnp.ndarray:
        """Floor positions: largest i with keys[i] <= q, else −1."""
        q = q.astype(index.keys.dtype)
        if self.uses_pallas:
            return pi_search(index.keys, q, fanout=index.config.fanout,
                             tile_q=self.tile_q, interpret=self.interpret,
                             levels=index.levels)
        pos, underflow = self._descend_xla(index, q)
        return jnp.where(underflow, jnp.int32(-1), pos)

    def probe(self, index, q: jnp.ndarray) -> Probe:
        """Fused floor + pending probe for a whole batch (the hot path)."""
        q = q.astype(index.keys.dtype)
        if self.uses_pallas:
            return self._probe_pallas(index, q)
        return self._probe_xla(index, q)

    def range_agg(self, index, lo: jnp.ndarray, hi: jnp.ndarray,
                  max_span: int):
        """Batched range aggregation → (count, sum) over keys in [lo, hi].

        The walk advances through *occupied ranks*, not raw slots: a
        rank→slot table skips segment slack so ``max_span`` counts real
        keys (live + tombstoned, matching the pre-gapped dense layout's
        budget), and tombstones are gated out of the aggregate.  Both
        Pallas backends run ``kernels.pi_range`` — descent + rank walk +
        pending pass fused into one launch; the ``xla`` path computes the
        identical values with stock jnp, so backends stay bit-identical
        (int32 aggregation is exact and order-independent).
        """
        kdt = index.keys.dtype
        sent = sentinel_for(kdt)
        lo = lo.astype(kdt)
        hi = hi.astype(kdt)
        C = index.keys.shape[0]
        # occupied-rank tables: rank[slot] = #occupied slots at-or-before
        # slot (minus one); dense2slot[r] = slot of the r-th occupied key,
        # C past the end.  Tombstoned slots keep their key => occupied.
        with jax.named_scope("pi.ranges.tables"):
            occ = index.keys != sent
            rank = jnp.cumsum(occ.astype(jnp.int32)) - 1
            tgt = jnp.where(occ, rank, C)
            dense2slot = jnp.full((C,), C, jnp.int32).at[tgt].set(
                jnp.arange(C, dtype=jnp.int32), mode="drop")
        pidx = jnp.arange(index.pkeys.shape[0])
        plive = (pidx < index.pn) & ~index.ptomb
        if self.uses_pallas:
            live = (occ & ~index.tomb).astype(jnp.int32)
            return pi_range(
                index.keys, live, index.vals, rank, dense2slot,
                index.pkeys, index.pvals, plive.astype(jnp.int32), lo, hi,
                fanout=index.config.fanout, max_span=max_span,
                tile_q=self.tile_q, interpret=self.interpret,
                levels=index.levels)
        with jax.named_scope("pi.ranges.walk"):
            pos = self.floor(index, lo)
            r0 = jnp.where(pos >= 0,
                           jnp.take(rank, jnp.clip(pos, 0, C - 1)), 0)
            r = r0[:, None] + jnp.arange(max_span, dtype=jnp.int32)[None, :]
            slot = jnp.take(dense2slot, r, mode="fill", fill_value=C)
            ks = jnp.take(index.keys, slot, mode="fill", fill_value=sent)
            ts = jnp.take(index.tomb, slot, mode="fill", fill_value=True)
            vs = jnp.take(index.vals, slot, mode="fill", fill_value=0)
            inr = (ks >= lo[:, None]) & (ks <= hi[:, None]) & ~ts \
                & (ks != sent)
            cnt = jnp.sum(inr, axis=1).astype(jnp.int32)
            sm = jnp.sum(jnp.where(inr, vs, 0), axis=1)
        # pending buffer: broadcast compare (PC is small between rebuilds)
        with jax.named_scope("pi.ranges.pending"):
            pin = (index.pkeys[None, :] >= lo[:, None]) & \
                (index.pkeys[None, :] <= hi[:, None]) & plive[None, :]
            cnt = cnt + jnp.sum(pin, axis=1).astype(jnp.int32)
            sm = sm + jnp.sum(jnp.where(pin, index.pvals[None, :], 0),
                              axis=1)
        return cnt, sm

    # -- xla backend -------------------------------------------------------

    def _descend_xla(self, index, q: jnp.ndarray):
        """Vectorized Alg. 2 in stock jnp: descend level H→1, at each level
        compare the F keys of the current entry's child group (one "SIMD
        compare") and take the rank — the routing-table lookup of Fig. 2
        done arithmetically."""
        cfg = index.config
        F = cfg.fanout
        sent = sentinel_for(index.keys.dtype)

        # top level: at most F entries -> one compare against the whole level
        top = index.levels[-1] if cfg.num_levels else index.keys
        rank = jnp.sum(top[None, :] <= q[:, None], axis=1).astype(jnp.int32) - 1
        pos = jnp.maximum(rank, 0)
        underflow = rank < 0

        for lvl in range(cfg.num_levels - 1, -1, -1):
            arr = index.levels[lvl - 1] if lvl >= 1 else index.keys
            child = pos[:, None] * F + jnp.arange(F, dtype=jnp.int32)[None, :]
            ck = jnp.take(arr, child, mode="fill", fill_value=sent)
            r = jnp.sum(ck <= q[:, None], axis=1).astype(jnp.int32) - 1
            pos = pos * F + jnp.maximum(r, 0)
        return pos, underflow

    def _probe_xla(self, index, q: jnp.ndarray) -> Probe:
        pos, underflow = self._descend_xla(index, q)
        pos = jnp.where(underflow, jnp.int32(-1), pos)
        C = index.keys.shape[0]
        pos_c = jnp.clip(pos, 0, C - 1)
        main_match = (pos >= 0) & (jnp.take(index.keys, pos_c) == q)
        PC = index.pkeys.shape[0]
        ppos = jnp.searchsorted(index.pkeys, q).astype(jnp.int32)
        ppos_c = jnp.minimum(ppos, PC - 1)
        p_hit = (index.pkeys[ppos_c] == q) & (ppos < PC)
        return Probe(pos=pos, main_match=main_match, ppos=ppos_c, p_hit=p_hit)

    # -- pallas backends ---------------------------------------------------

    def _probe_pallas(self, index, q: jnp.ndarray) -> Probe:
        mpos, ppos, flags = pi_probe(
            index.keys, index.pkeys, q, fanout=index.config.fanout,
            tile_q=self.tile_q, interpret=self.interpret,
            levels=index.levels)
        PC = index.pkeys.shape[0]
        return Probe(
            pos=mpos,
            main_match=(flags & FLAG_MAIN_MATCH) > 0,
            ppos=jnp.minimum(ppos, PC - 1),
            p_hit=(flags & FLAG_PENDING_HIT) > 0,
        )


@functools.lru_cache(maxsize=None)
def _make_engine(backend: str, tile_q: int) -> SearchEngine:
    return SearchEngine(backend=backend, tile_q=tile_q)


def get_engine(config) -> SearchEngine:
    """The (memoized) engine a ``PIConfig`` selects via ``config.backend``."""
    return _make_engine(config.backend, config.tile_q)
