"""ShardedPIIndex — the paper's NUMA-aware partitioning on a device mesh.

Paper §4.3.1: the key space is range-partitioned across NUMA nodes; each
node builds an independent sub-index from its own keys; queries are routed
to the owning node and processed entirely in local memory.

TPU mapping (DESIGN.md §2):

* NUMA node        → mesh shard along the ``data`` axis
* per-node index   → one ``PIIndex`` per shard (stacked-leaf pytree whose
                     leading dim is sharded ``P("data")``: shard s lives on
                     the s-th device of the axis)
* query routing    → bucketize by fence keys + ``jax.lax.all_to_all``
* QPI hop          → one ICI all_to_all each way (the *only* cross-shard
                     traffic; execution itself is collective-free, which is
                     the paper's "no remote memory access" property)
* self-adjusted threading → capacity-factored dispatch + fence rebalancing
                     (``core.rebalance``) — TPUs cannot move cores between
                     shards, so we move the *range boundaries* instead.

Every program that touches the stacked shard leaves runs them inside
``jax.shard_map``: each device sees only its own shard(s), so nothing is
gathered onto one chip.  Host-side readers (``collect_pairs``) fetch the
leaves with an explicit ``jax.device_get``.

The dispatch machinery (sort by destination, capacity-bounded send buffers,
all_to_all, inverse routing) is deliberately the same shape as an MoE
token dispatch; ``models/moe.py`` reuses it.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import index as pi
from repro.core.batch import SEARCH
from repro.core.engine import sentinel_for

AXIS = "data"  # the mesh axis the shards are laid out along


# ---------------------------------------------------------------------------
# generic sorted all_to_all dispatch (shared with models/moe.py)
# ---------------------------------------------------------------------------

def dispatch_plan(dest: jnp.ndarray, n_dest: int, cap: int,
                  sort_key: jnp.ndarray | None = None):
    """Plan a capacity-bounded dispatch of local items to ``n_dest`` buckets.

    Items are stably sorted by (dest, sort_key) — the paper's sorted query
    batch — then the first ``cap`` items of each destination group survive;
    the rest overflow (counted, like an MoE capacity drop; the paper's
    self-adjusted threading would instead grow the thread pool).

    Returns (order, slot, keep, n_dropped):
      order : (B,) permutation applied before bucketing
      slot  : (B,) position of sorted item i inside send buffer = dest*cap+r
      keep  : (B,) mask of items that fit
    """
    B = dest.shape[0]
    if sort_key is not None:
        # dest-major, key-minor: two-pass stable argsort
        o1 = jnp.argsort(sort_key, stable=True)
        o2 = jnp.argsort(dest[o1], stable=True)
        order = o1[o2]
    else:
        order = jnp.argsort(dest, stable=True)
    d_sorted = dest[order]
    # rank within destination group: d_sorted is sorted, so each group's
    # start index is a searchsorted of the group id against itself
    idx = jnp.arange(B, dtype=jnp.int32)
    group_start = jnp.searchsorted(d_sorted, d_sorted, side="left")
    rank = idx - group_start.astype(jnp.int32)
    keep = rank < cap
    slot = jnp.where(keep, d_sorted * cap + rank, n_dest * cap)
    n_dropped = jnp.sum(~keep).astype(jnp.int32)
    return order, slot, keep, n_dropped


def scatter_to_buffer(arr: jnp.ndarray, order: jnp.ndarray, slot: jnp.ndarray,
                      n_dest: int, cap: int, fill) -> jnp.ndarray:
    """(B,)→(n_dest, cap) send buffer; dropped items vanish (mode='drop')."""
    buf = jnp.full((n_dest * cap,) + arr.shape[1:], fill, arr.dtype)
    buf = buf.at[slot].set(arr[order], mode="drop")
    return buf.reshape((n_dest, cap) + arr.shape[1:])


# ---------------------------------------------------------------------------
# sharded index state
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardedPIIndex:
    """Stacked per-shard PIIndex + replicated fence keys.

    ``shards`` leaves have leading dim S (the shard count); ``fences``
    has S+1 entries with fences[0] = dtype.min and fences[S] = sentinel.
    Shard s owns keys in [fences[s], fences[s+1]).
    """

    shards: pi.PIIndex          # stacked: every leaf (S, ...)
    fences: jnp.ndarray         # (S+1,)
    n_shards: int


def build_sharded(cfg: pi.PIConfig, n_shards: int, keys, vals,
                  fences=None, *, mesh: Mesh) -> ShardedPIIndex:
    """Host-side build: partition by fences (default: equi-depth), stack,
    and place the stacked leaves ``P(AXIS)`` on ``mesh``.

    Each device's block of shards goes straight from the host to that
    device, so shard s of an S-way axis lives on its s-th device; the
    fences are replicated.  ``n_shards`` must be a multiple of the axis
    size (routing needs exactly one shard per device; RANGE and rebuild
    also take several).
    """
    keys = np.asarray(keys)
    vals = np.asarray(vals)
    order = np.argsort(keys)
    keys, vals = keys[order], vals[order]
    kdt = np.dtype(cfg.key_dtype)
    if fences is None:
        # equi-depth split of the initial data (paper: even distribution)
        cuts = [keys[(len(keys) * s) // n_shards] for s in range(1, n_shards)] \
            if len(keys) else [0] * (n_shards - 1)
        lo = np.iinfo(kdt).min if np.issubdtype(kdt, np.integer) else -np.inf
        hi = sentinel_for(kdt)    # top fence == the engine pad key
        fences = np.array([lo, *cuts, hi], dtype=kdt)
    fences = np.asarray(fences, dtype=kdt)
    shard_trees = []
    for s in range(n_shards):
        m = (keys >= fences[s]) & (keys < fences[s + 1]) if s + 1 < n_shards \
            else (keys >= fences[s])
        shard_trees.append(pi.build(cfg, jnp.asarray(keys[m]),
                                    jnp.asarray(vals[m])))
    if n_shards % mesh.shape[AXIS]:
        raise ValueError(f"{n_shards} shards do not divide over the "
                         f"{mesh.shape[AXIS]}-way {AXIS!r} axis")
    host = jax.tree.map(lambda *xs: np.stack(xs),
                        *jax.device_get(shard_trees))
    stacked = jax.device_put(host, NamedSharding(mesh, P(AXIS)))
    return ShardedPIIndex(
        shards=stacked,
        fences=jax.device_put(fences, NamedSharding(mesh, P())),
        n_shards=n_shards)


# ---------------------------------------------------------------------------
# the shard-local bodies (run under shard_map)
# ---------------------------------------------------------------------------

@jax.jit
def maybe_rebuild_shards(shards: pi.PIIndex):
    """Per-shard rebuild daemon over stacked shard leaves.

    Each shard rebuilds iff *it* is due (``pi.rebuild_if_due``): a not-due
    shard's pending churn stays buffered for its own later — likely
    incremental — rebuild instead of being force-repacked whenever a
    sibling trips the threshold.  ``lax.map`` keeps every shard's cond a
    real branch (under ``vmap`` it would lower to a select, so every shard
    would pay both rebuild tiers).  Returns ``(shards, overflow, due,
    incremental)``, the flags one per shard; ``overflow`` is snapshot
    *before* the rebuild resets it on the state (overflow is data loss
    and must stay observable).
    """
    def one(shard):
        ovf = shard.overflow
        shard, due, incr = pi.rebuild_if_due(shard)
        return shard, ovf, due, incr

    return jax.lax.map(one, shards)


def _local_execute(shard: pi.PIIndex, fences, ops, qkeys, qvals,
                   cap: int, n_shards: int):
    """Route → execute → route back, from one shard's perspective.

    ``shard`` leaves arrive with a leading (1,) block dim from shard_map;
    ``n_shards`` is the static mesh axis size (buffers are shaped by it).
    """
    S = n_shards
    kdt = jnp.dtype(shard.keys.dtype)
    sent = sentinel_for(kdt)
    local = jax.tree.map(lambda x: x[0], shard)
    b = ops.shape[0]

    # --- outbound routing (paper: route query to owning NUMA node) --------
    dest = jnp.clip(
        jnp.searchsorted(fences[1:-1], qkeys.astype(kdt), side="right"),
        0, S - 1).astype(jnp.int32)
    order, slot, keep, _ = dispatch_plan(dest, S, cap, sort_key=qkeys)
    # drop accounting counts REAL queries only: sentinel padding routes to
    # the last shard and sorts after real keys there, so pads are evicted
    # first and their loss is free — reporting them would make every
    # mostly-padded (deadline-sealed) batch look like an overflow
    n_drop = jnp.sum(~keep & (qkeys.astype(kdt)[order] != sent)) \
        .astype(jnp.int32)
    send_ops = scatter_to_buffer(ops, order, slot, S, cap, SEARCH)
    send_keys = scatter_to_buffer(qkeys.astype(kdt), order, slot, S, cap, sent)
    send_vals = scatter_to_buffer(qvals, order, slot, S, cap, 0)
    # remember where each slot came from so results can return: the query
    # in slot[i] is sorted item i == original index order[i]
    src_pos = jnp.full((S * cap,), -1, jnp.int32).at[slot].set(
        order.astype(jnp.int32), mode="drop").reshape(S, cap)

    recv_ops = jax.lax.all_to_all(send_ops, AXIS, 0, 0, tiled=False)
    recv_keys = jax.lax.all_to_all(send_keys, AXIS, 0, 0, tiled=False)
    recv_vals = jax.lax.all_to_all(send_vals, AXIS, 0, 0, tiled=False)

    # --- local execution (collective-free: the paper's "no remote access")
    flat = lambda x: x.reshape((S * cap,) + x.shape[2:])
    new_local, (r_found, r_val) = pi.execute_impl(
        local, flat(recv_ops), flat(recv_keys), flat(recv_vals))

    # --- inbound routing of results ---------------------------------------
    rf = jax.lax.all_to_all(r_found.reshape(S, cap), AXIS, 0, 0)
    rv = jax.lax.all_to_all(r_val.reshape(S, cap), AXIS, 0, 0)
    src = src_pos.reshape(S * cap)
    tgt = jnp.where(src >= 0, src, b)
    out_found = jnp.zeros((b,), bool).at[tgt].set(rf.reshape(-1), mode="drop")
    out_val = jnp.zeros((b,), jnp.int32).at[tgt].set(rv.reshape(-1),
                                                     mode="drop")
    # per-shard load (for self-adjusted rebalancing)
    load = jnp.sum(recv_keys != sent).astype(jnp.int32)
    new_shard = jax.tree.map(lambda x: x[None], new_local)
    return new_shard, out_found, out_val, load[None], n_drop[None]


@lru_cache(maxsize=None)
def make_sharded_executor(mesh: Mesh, cfg: pi.PIConfig, batch_per_shard: int,
                          capacity_factor: float = 2.0):
    """Build (or fetch) the jitted shard_map'd batch executor for a mesh.

    Memoized by ``(mesh, cfg, batch_per_shard, capacity_factor)`` —
    re-jitting the shard_map body on every batch was the dominant dispatch
    cost.  ``cfg`` includes the search backend, so ``xla`` and ``pallas``
    executors coexist in the cache.  Returns ``(fn, cap)`` with
    ``fn(state, fences, ops, keys, vals) -> (state', found, vals, load,
    dropped)``: ops/keys/vals are global (S * batch_per_shard,) arrays
    sharded along ``AXIS``; ``load`` and ``dropped`` are (S,) per-shard
    counts of the queries each shard received and of the real queries
    routing lost.  It routes and executes only; the rebuild daemon is
    ``maybe_rebuild_sharded``.
    """
    S = mesh.shape[AXIS]
    # integer-exact ceil (PI004): the factor is frozen to a /1024 rational
    # so the lane budget cannot wobble with float rounding — the same
    # split needs_rebuild uses for its churn threshold
    num = int(round(capacity_factor * 1024))
    cap = -(-batch_per_shard * num // (S * 1024))
    body = partial(_local_execute, cap=cap, n_shards=S)
    # state and batch sharded along the axis, fences replicated
    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS), P(), P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS),) * 5, check_vma=False)

    @jax.jit
    def sharded_execute(state_shards, fences, ops, qkeys, qvals):
        return mapped(state_shards, fences, ops, qkeys, qvals)

    return sharded_execute, cap


def execute_sharded(state: ShardedPIIndex, mesh: Mesh, ops, qkeys, qvals,
                    capacity_factor: float = 2.0):
    """One routed window → ``(state', (found, val), load, dropped)``.

    The executor is fetched from the memo cache; see
    ``make_sharded_executor`` for ``load`` and ``dropped``.  Needs one
    shard per device of the axis.
    """
    B = ops.shape[0]
    S = state.n_shards
    if S != mesh.shape[AXIS]:
        raise ValueError(f"{S} shards on a {mesh.shape[AXIS]}-way {AXIS!r} "
                         f"axis: routing needs one per device")
    if B % S:
        raise ValueError(f"global batch {B} must divide the shard count {S}")
    run, _ = make_sharded_executor(
        mesh, state.shards.config, B // S, capacity_factor)
    shards, found, val, load, dropped = run(
        state.shards, state.fences, ops, qkeys, qvals)
    new_state = ShardedPIIndex(shards=shards, fences=state.fences,
                               n_shards=S)
    return new_state, (found, val), load, dropped


@lru_cache(maxsize=None)
def _sharded_maybe_rebuild(mesh: Mesh):
    def body(shards):
        pn = shards.pn  # fill high-water, before the rebuild empties it
        shards, ovf, due, incr = maybe_rebuild_shards(shards)
        return shards, dict(overflow=ovf, rebuilt=due, incremental=incr,
                            pn=pn)

    mapped = jax.shard_map(body, mesh=mesh, in_specs=P(AXIS),
                           out_specs=P(AXIS), check_vma=False)

    @jax.jit
    def sharded_maybe_rebuild(shards):
        shards, st = mapped(shards)
        rebuilt = jnp.any(st["rebuilt"])
        return shards, dict(
            overflow=jnp.any(st["overflow"]),
            rebuilt=rebuilt,
            incremental=rebuilt & jnp.all(st["incremental"] | ~st["rebuilt"]),
            pn=jnp.max(st["pn"]))

    return sharded_maybe_rebuild


def maybe_rebuild_sharded(state: ShardedPIIndex, mesh: Mesh):
    """Rebuild daemon on a mesh → ``(state', flags)``.

    Each device runs ``maybe_rebuild_shards`` over the shard(s) it holds.
    ``flags`` are scalars reduced over the shards: ``overflow`` (any
    pending overflow, before the rebuild resets it), ``rebuilt`` (any
    shard rebuilt), ``incremental`` (every rebuilt shard took the
    incremental tier) and ``pn`` (the hottest shard's pending fill
    high-water, before the rebuild).
    """
    shards, flags = _sharded_maybe_rebuild(mesh)(state.shards)
    return ShardedPIIndex(shards=shards, fences=state.fences,
                          n_shards=state.n_shards), flags


@lru_cache(maxsize=None)
def _sharded_rebuild(mesh: Mesh):
    return jax.jit(jax.shard_map(
        partial(jax.lax.map, pi.rebuild), mesh=mesh,
        in_specs=P(AXIS), out_specs=P(AXIS), check_vma=False))


def rebuild_sharded(state: ShardedPIIndex, mesh: Mesh) -> ShardedPIIndex:
    """Forced per-shard rebuild — embarrassingly parallel (paper §4.1):
    each device rebuilds only the shard(s) it holds."""
    shards = _sharded_rebuild(mesh)(state.shards)
    return ShardedPIIndex(shards=shards, fences=state.fences,
                          n_shards=state.n_shards)


def collect_pairs(state: ShardedPIIndex):
    """Host-side: pull all live (key, val) pairs (for resharding/tests).

    One explicit ``device_get`` of the stacked leaves, then a per-shard
    occupancy scan (``key != sentinel`` — the segmented gapped storage has
    no dense ``[:n]`` prefix to slice).
    """
    host = jax.device_get(state.shards)
    ks, vs = [], []
    for s in range(state.n_shards):
        k, v = pi.live_items(jax.tree.map(lambda x: x[s], host))
        ks.append(k)
        vs.append(v)
    k = np.concatenate(ks)
    v = np.concatenate(vs)
    order = np.argsort(k)
    return k[order], v[order]
