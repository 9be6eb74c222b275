"""Range serving tier: RANGE ops as first-class pipeline citizens.

The paper's §3.2.5 range machinery (``core.range_agg``, driven bare by
``benchmarks/fig14_range``) meets the serving path here.  A RANGE arrival
carries two key operands — the window's ``keys2`` lane holds the inclusive
upper bound — and flows through the same collect → WAL → dispatch stages
as point ops (DESIGN.md §9):

* **admission** — the collector coalesces exact ``(lo, hi)`` duplicates
  into one result slot; containment (``Collector.range_covered``) is a
  shed signal, not a sharing rule, because a subsumed range's aggregate
  still differs from its coverer's.
* **semantics** — every range in a window observes the **pre-window**
  index state: the dispatcher runs ``execute_ranges`` against the index
  *before* the window's point execute.  That is what makes exact-pair
  coalescing sound across intervening window writes, and it mirrors the
  paper's batch contract (reads in a batch see the pre-batch state unless
  an earlier-arriving write to the same key intervenes — a range cannot
  name "the same key", so it sees none of them).
* **execution** — one fused launch per window: the engine's ``range_agg``
  walks occupied ranks from a scan-start descent (``kernels.pi_range``
  under the Pallas backends), so ``max_span`` counts real keys, not
  gapped slots.  Non-range lanes are neutralized to ``lo = sentinel,
  hi = 0`` — inert by construction — so the launch shape is the static
  window batch and exactly one compiled range execute serves a run.
* **sharding** — a range spanning several shards fans out per-shard
  clipped subranges ``[max(lo, fence_s), min(hi, fence_{s+1} - 1)]`` and
  reduces the ``(count, sum)`` partials with one ``psum``; shards own
  disjoint key intervals, so the reduction is exact (no double counting).
  Read-only, so no ``all_to_all`` — every shard sees every query lane.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.analysis.runtime import trace_guard
from repro.core.batch import RANGE
from repro.core.distributed import AXIS
from repro.core.engine import get_engine, sentinel_for

# Bumped on every *trace* of the range executors (Python side effects run
# at trace time only): under jit this counts compilations, not calls.
# The dispatcher feeds the executors the full static window batch with
# non-range lanes neutralized, so this stays at 1 per serving run —
# suites and benchmarks assert it through the guard's canonical message
# (analysis/runtime.py; deltas via range_trace_count()).
_TRACES = trace_guard("pipeline.ranges")


def range_trace_count() -> int:
    return _TRACES.count()


def _range_lanes(ops, keys, keys2, kdt):
    """RANGE lanes pass through; everything else goes inert.

    ``lo = sentinel, hi = 0`` makes a lane's in-range mask empty in both
    the storage walk and the pending pass, so point/pad slots contribute
    exactly (0, 0) — the same trick the kernels use for tile padding.
    """
    is_r = ops == RANGE
    sent = sentinel_for(kdt)
    lo = jnp.where(is_r, keys.astype(kdt), sent)
    hi = jnp.where(is_r, keys2.astype(kdt), jnp.zeros((), kdt))
    return lo, hi


@partial(jax.jit, static_argnums=4)
def execute_ranges(index, ops: jnp.ndarray, keys: jnp.ndarray,
                   keys2: jnp.ndarray, max_span: int):
    """Serve a window's RANGE lanes against one shard → (count, sum).

    ``index`` is the **pre-window** state (call before the point
    execute).  Returns two (batch,) int32 arrays; non-range slots read
    (0, 0).  Read-only: the index is not modified (and not donated).
    """
    _TRACES.bump()
    lo, hi = _range_lanes(ops, keys, keys2, index.keys.dtype)
    return get_engine(index.config).range_agg(index, lo, hi, max_span)


def execute_ranges_sharded(state, mesh, ops: jnp.ndarray, keys: jnp.ndarray,
                           keys2: jnp.ndarray, max_span: int):
    """Sharded fan-out/reduce: per-shard subranges, summed partials.

    Shard ``s`` owns keys in ``[fences[s], fences[s+1])``, so its
    subrange is the query clipped to that interval — empty (lo > hi,
    hence inert) when the range misses the shard — and the global
    ``(count, sum)`` is the sum of partials over disjoint intervals.
    The partials are computed inside ``shard_map``, each device over the
    shard(s) it holds, and reduced with one ``psum``: one jitted program
    per (mesh, geometry), keeping the one-compile contract.  ``max_span``
    is a *per-shard* budget here, so splitting can only widen what a
    span cap would truncate, never narrow it.  ``state`` is a
    ``ShardedPIIndex`` (not a pytree — its leaves are unpacked before the
    jit boundary).
    """
    fn = _sharded_range_executor(mesh, max_span)
    return fn(state.shards, state.fences, ops, keys, keys2)


@lru_cache(maxsize=None)
def _sharded_range_executor(mesh, max_span: int):
    def body(shards, fences, lo, hi):
        k = shards.keys.shape[0]          # shards held by this device
        kdt = shards.keys.dtype
        sid = jax.lax.axis_index(AXIS) * k + jnp.arange(k)
        f_lo = fences[sid].astype(kdt)
        f_hi = (fences[sid + 1] - 1).astype(kdt)

        def partial_agg(shard, a, b):
            return get_engine(shard.config).range_agg(
                shard, jnp.maximum(lo, a), jnp.minimum(hi, b), max_span)

        cnt, sm = jax.vmap(partial_agg)(shards, f_lo, f_hi)
        return (jax.lax.psum(jnp.sum(cnt, 0), AXIS),
                jax.lax.psum(jnp.sum(sm, 0), AXIS))

    mapped = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(AXIS), P(), P(), P()),
                           out_specs=(P(), P()), check_vma=False)

    @jax.jit
    def sharded_ranges(shards, fences, ops, keys, keys2):
        _TRACES.bump()
        lo, hi = _range_lanes(ops, keys, keys2, shards.keys.dtype)
        return mapped(shards, fences, lo, hi)

    return sharded_ranges
