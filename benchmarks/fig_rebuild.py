"""Rebuild-latency figure: churn-proportional incremental vs full repack.

The segmented gapped layout makes rebuild cost scale with the *dirty
segment set*, not with capacity.  This figure measures that directly:
a large index absorbs a clustered (localized) batch of pending inserts
sized to each churn fraction, and the SAME pre-rebuild state is timed
through three rebuild paths:

  two_tier    production ``rebuild`` — takes the incremental merge when
              the dirty set fits ``max_dirty`` and every merged run fits
              its segment, else falls back to the repack
  repack      the full repack forced on the segmented config (sort over
              C+PC, even slack re-spread, all levels regenerated)
  monolithic  the full repack on a degenerate ``seg_width == capacity``
              config — one capacity-wide segment, i.e. the pre-segmented
              monolithic storage rebuild this layout replaced

Churn is *localized* (a contiguous key range at every other stored key)
because that is the regime incremental rebuilds exist for: uniform
churn at the same fraction dirties nearly every segment and correctly
falls back to the repack — the largest churn row demonstrates exactly
that.  Acceptance targets: two_tier >= 5x cheaper than repack at <= 5%
churn, and repack within 1.2x of monolithic (the slack spread is not a
regression for the rare fallback).  Rows land in ``BENCH_rebuild.json``.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import default_backend, emit
from repro.core import PIConfig, build, insert_batch, live_items, rebuild
from repro.core import index as pi_index
from repro.compile_cache import use_compile_cache

_repack = pi_index.repack


def _timeit(fn, arg, iters: int, warmup: int = 2) -> float:
    """Median wall-clock ms of ``fn(arg)`` (device-synchronized)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(arg))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(arg))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e3


def _base_keys(n_keys: int, seed: int) -> np.ndarray:
    """Strictly increasing jittered keys with guaranteed +1 gaps free."""
    rng = np.random.default_rng(seed)
    return (np.arange(n_keys, dtype=np.int64) * 16
            + rng.integers(0, 8, n_keys)).astype(np.int32)


def _churn_keys(sk: np.ndarray, n_new: int) -> np.ndarray:
    """Clustered insertions: +1 neighbours of every other stored key in a
    contiguous range around the median — localized churn that dirties
    ~``2 * n_new / (W/2)`` adjacent segments."""
    start = max(0, len(sk) // 2 - n_new)
    picked = sk[start:start + 2 * n_new:2]
    return (picked[:n_new] + 1).astype(np.int32)


def main(n_keys: int = 1 << 17, fanout: int = 4,
         churns=(0.01, 0.02, 0.05, 0.10, 0.25), iters: int = 15,
         headroom: float = 2.0, seed: int = 0):
    backend = default_backend()
    cap = int(n_keys * headroom)
    pc = max(4096, int(0.3 * n_keys))
    cfg = PIConfig(capacity=cap, pending_capacity=pc, fanout=fanout,
                   backend=backend)
    cfg_mono = dataclasses.replace(cfg, seg_width=cap)
    sk = _base_keys(n_keys, seed)
    vals = np.arange(n_keys, dtype=np.int32)

    rows = []
    for churn in churns:
        n_new = max(1, int(churn * n_keys))
        newk = jnp.asarray(_churn_keys(sk, n_new))
        newv = jnp.asarray(np.arange(n_new, dtype=np.int32))
        # execute() donates its input buffers, so build a fresh pre-state
        # per churn point rather than reusing one donated base index
        base = build(cfg, jnp.asarray(sk), jnp.asarray(vals))
        base_m = build(cfg_mono, jnp.asarray(sk), jnp.asarray(vals))
        st, _ = insert_batch(base, newk, newv)
        st_m, _ = insert_batch(base_m, newk, newv)
        incr = bool(pi_index.incremental_fits(st)) and not bool(st.overflow)
        mode = "incremental" if incr else "repack"
        t_two = _timeit(rebuild, st, iters)
        t_rep = _timeit(_repack, st, iters)
        t_mono = _timeit(_repack, st_m, iters)
        # both tiers must agree on the surviving key/value set
        k1, v1 = live_items(rebuild(st))
        k2, v2 = live_items(_repack(st_m))
        np.testing.assert_array_equal(k1, k2)
        np.testing.assert_array_equal(v1, v2)
        rows.append([churn, n_new, mode,
                     round(t_two, 4), round(t_rep, 4), round(t_mono, 4),
                     round(t_rep / t_two, 2), round(t_rep / t_mono, 3)])
        print(f"  churn={churn:<5} mode={mode:<12} two_tier={t_two:8.3f}ms "
              f"repack={t_rep:8.3f}ms mono={t_mono:8.3f}ms "
              f"speedup={t_rep / t_two:6.2f}x", flush=True)

    emit(rows,
         header=("churn_frac", "n_new", "mode", "two_tier_ms", "repack_ms",
                 "monolithic_ms", "speedup_vs_repack", "repack_vs_mono"),
         fig="rebuild",
         config=dict(n_keys=n_keys, capacity=cap, pending_capacity=pc,
                     fanout=fanout, seg_width=cfg.seg_width_eff,
                     num_segments=cfg.num_segments, max_dirty=cfg.max_dirty,
                     iters=iters, headroom=headroom, backend=backend))
    return rows


if __name__ == "__main__":
    use_compile_cache()
    main()
