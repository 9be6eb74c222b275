"""Double-buffered dispatch: overlap window formation with device execution.

JAX dispatch is asynchronous: ``execute`` returns array futures
immediately, and the new index state — itself a bundle of futures — can be
fed straight into the *next* ``execute`` without waiting.  The dispatcher
exploits that to run the pipeline open: while the device executes window
*k*, the host is back in the collector forming window *k+1*.  Only when a
window is *retired* (its results materialized to numpy) does the host
block, and with ``depth >= 1`` that happens one window late — by which
time the device has usually finished.  ``depth=0`` degrades to the naive
form-then-execute loop (the benchmark baseline, and what the serving
scheduler uses because it needs results within the tick).

Routing: a ``PIIndex`` executes locally via the fused ``_step_single``
program; a ``ShardedPIIndex`` goes through
``core.distributed.execute_sharded``, whose fence partitioning routes each
window's per-shard slices with one ``all_to_all`` each way — the
dispatcher is the same either way.

Failure contract: the core's pending-buffer ``overflow`` flag means a net
insert was silently dropped — data loss.  The collector's backpressure
makes it unreachable under normal policy (a window can net-insert at most
``batch`` keys), but a misconfigured geometry (``batch > pending_capacity``)
can still trip it, so the dispatcher snapshots the flag after every
execute (a fresh device scalar — the rebuild that follows would reset the
flag on the state itself) and raises ``PendingOverflowError`` at
retirement.  Sharded routing has an analogous loss mode — a fence bucket
exceeding its ``capacity_factor`` drops real queries — surfaced as
``DispatchOverflowError`` the same way.  Rebuild bookkeeping rides the
same snapshot mechanism, so none of these checks force an early sync.

With an ``OverloadConfig`` installed, a pending overflow is no longer
fatal: a **circuit breaker** (DESIGN.md §8) rolls the index back to the
state before the failing window (kept for free — nothing is donated, so
the pre-execute buffers are intact), forces a full repack to reclaim the
pending space, replays the quarantined windows through the same execute
path, and resumes.  Repeated trips within a rolling interval degrade to a
read-only mode (write windows rejected with ``ReadOnlyModeError``, reads
served); only an unrecoverable replay — overflow on an *empty* pending
buffer, a geometry error — latches the legacy poisoned state.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributed as dist
from repro.core import index as pi
from repro.core.batch import RANGE, SEARCH
from repro.kernels.pi_search import sentinel_for
from repro.pipeline.collector import Collector, Window, WindowConfig
from repro.pipeline.metrics import PipelineMetrics, span
from repro.pipeline.overload import (BREAKER_CLOSED, BREAKER_POISONED,
                                     BREAKER_READ_ONLY, BREAKER_RECOVERING,
                                     OverloadConfig, ReadOnlyModeError)
from repro.pipeline.ranges import execute_ranges, execute_ranges_sharded


class PendingOverflowError(RuntimeError):
    """The index dropped net inserts: pending buffer overflowed mid-window.

    ``windows`` carries every in-flight ``Window`` at failure time — the
    failing one first — so a caller can account for exactly which arrivals
    never produced results.
    """

    windows: List[Window] = []


class DispatchOverflowError(RuntimeError):
    """Sharded routing dropped queries: a fence bucket exceeded its send
    capacity (``capacity_factor`` too small for the window's skew).

    ``windows`` carries every in-flight ``Window`` at failure time — the
    failing one first (see ``PendingOverflowError``).
    """

    windows: List[Window] = []


@jax.jit
def _step_single(index, ops, keys, vals):
    """Execute + overflow snapshot + rebuild-if-due, ONE dispatch.

    Fused so a window costs a single device program: eager ``lax.cond``
    per window was ~15x the execute itself.  Deliberately NOT donating the
    index (unlike ``core.execute``): buffer donation forces the CPU client
    into synchronous dispatch, which serializes host formation with device
    execution — the exact overlap double-buffering exists to create.  The
    price is one transient extra copy of the index state in memory.

    ``incr`` reports which tier a due rebuild took (``pi.rebuild_if_due``).
    """
    new_index, (found, val) = pi.execute_impl(index, ops, keys, vals)
    ovf = new_index.overflow
    pn = new_index.pn  # fill high-water: post-rebuild pn is ~always zero
    new_index, due, incr = pi.rebuild_if_due(new_index)
    return new_index, found, val, ovf, due, incr, pn


@jax.jit
def _step_recover(index, ops, keys, vals):
    """Breaker-replay variant of ``_step_single``: rebuild unconditionally.

    During recovery the pending buffer must end every replayed window
    empty — the quarantined windows were the ones that overflowed it, and
    the ordinary 3/4 threshold leaves enough residue to re-trip on the
    very next window.  Off the fast path by definition (it only traces
    and runs after a breaker trip), so the extra rebuilds cost nothing in
    steady state.
    """
    new_index, (found, val) = pi.execute_impl(index, ops, keys, vals)
    ovf = new_index.overflow
    pn = new_index.pn
    new_index = pi.rebuild(new_index)
    return new_index, found, val, ovf, pn


# the breaker's forced reclaim: merge the pending buffer into storage and
# re-spread the slack, leaving the full pending capacity available for the
# quarantined windows' replay
_repack = pi.repack


@dataclasses.dataclass
class WindowResult:
    """A retired window: per-slot results + the arrival→slot map to read them."""

    window: Window
    found: np.ndarray      # (batch,) bool
    val: np.ndarray        # (batch,) int32
    t_retired: float
    rebuilt: bool
    rebuilt_incremental: bool = False  # rebuild took the segmented fast tier
    pending_fill: float = float("nan")  # pn high-water / pending_capacity
    rcnt: Optional[np.ndarray] = None  # (batch,) int32 RANGE counts
    rsum: Optional[np.ndarray] = None  # (batch,) int32 RANGE value sums

    def per_arrival(self) -> Dict[int, Tuple[bool, int]]:
        """qid → (found, val) for *point* arrivals, fanning shared slots
        back out; RANGE arrivals read theirs from ``per_arrival_ranges``
        (a (count, sum) pair is not a (found, val) pair)."""
        out = {}
        ops = self.window.ops
        for qid, slot in zip(self.window.qids, self.window.slots):
            if ops[slot] != RANGE:
                out[qid] = (bool(self.found[slot]), int(self.val[slot]))
        return out

    def per_arrival_ranges(self) -> Dict[int, Tuple[int, int]]:
        """qid → (count, sum) for RANGE arrivals (coalesced pairs fan back
        out to every arrival sharing the slot)."""
        out = {}
        if self.rcnt is None:
            return out
        ops = self.window.ops
        for qid, slot in zip(self.window.qids, self.window.slots):
            if ops[slot] == RANGE:
                out[qid] = (int(self.rcnt[slot]), int(self.rsum[slot]))
        return out

    def latencies(self) -> np.ndarray:
        """Per-arrival enqueue→result latency, on the caller's time axis."""
        return self.t_retired - self.window.t_enq


@dataclasses.dataclass
class _InFlight:
    window: Window
    found: jnp.ndarray
    val: jnp.ndarray
    overflow: jnp.ndarray  # snapshot scalar, taken before the rebuild reset
    rebuilt: jnp.ndarray
    incr: Optional[jnp.ndarray]     # rebuild tier taken (None: breaker replay)
    dropped: Optional[jnp.ndarray]  # sharded routing drops (None: local)
    pn: Optional[jnp.ndarray] = None  # pending fill high-water (pre-rebuild)
    rcnt: Optional[jnp.ndarray] = None  # RANGE counts (pre-window state)
    rsum: Optional[jnp.ndarray] = None  # RANGE value sums
    # index state BEFORE this window's execute — free to keep because
    # _step_single doesn't donate; the breaker rolls back to it on a trip.
    # Only retained when the breaker is armed (it pins device memory).
    pre_index: Optional[object] = None


class Dispatcher:
    """Owns the index state; executes sealed windows against it in order."""

    def __init__(self, index, *, mesh=None, depth: int = 1,
                 check_overflow: bool = True,
                 capacity_factor: float = 2.0,
                 max_span: int = 1024,
                 metrics: Optional[PipelineMetrics] = None,
                 durability=None,
                 overload: Optional[OverloadConfig] = None,
                 clock=time.perf_counter):
        if isinstance(index, dist.ShardedPIIndex) and mesh is None:
            raise ValueError("a ShardedPIIndex needs its mesh for routing")
        self._index = index
        self._mesh = mesh
        self.depth = max(0, int(depth))
        self.check_overflow = check_overflow
        self.capacity_factor = capacity_factor
        # occupied-key scan budget per RANGE (core.range_agg's max_span);
        # static — it shapes the compiled range execute
        self.max_span = int(max_span)
        self.metrics = metrics
        # durability tier (pipeline.recovery.Durability): submit() calls
        # maybe_snapshot after each dispatched window so snapshots stamp
        # the WAL seq of the last state-affecting window; the WAL append
        # itself happens earlier, at the collector's seal hook
        self.durability = durability
        # overload tier (pipeline.overload.OverloadConfig): with a breaker
        # armed, a local pending overflow recovers (rollback + repack +
        # replay) instead of poisoning.  None keeps the legacy contract —
        # overflow latches immediately — as does the sharded path, whose
        # fence-bucket drops have no rollback point (the all_to_all already
        # scattered the window).
        self.overload = overload
        self._clock = clock
        self._inflight: List[_InFlight] = []
        self._poisoned: Optional[BaseException] = None
        self._breaker = BREAKER_CLOSED
        self._trip_times: List[float] = []
        self._read_only_since: Optional[float] = None
        self.breaker_trips = 0
        self.breaker_recoveries = 0
        cfg = index.shards.config if isinstance(index, dist.ShardedPIIndex) \
            else index.config
        self._pending_capacity = int(cfg.pending_capacity)

    @property
    def index(self):
        """Current index state (futures included — reading it may sync)."""
        return self._index

    @property
    def poisoned(self) -> Optional[BaseException]:
        """The latched retirement failure, if any (see ``_retire_front``)."""
        return self._poisoned

    @property
    def breaker_state(self) -> str:
        """Where the breaker sits in closed → recovering → read_only →
        poisoned.  ``recovering`` is only visible from within a recovery
        (e.g. a durability hook); callers see the settled state.  Reading
        the state applies the time-based read-only decay, so an admission
        tier shedding writes on this state (never submitting a write
        window) still sees the breaker close after a quiet interval."""
        if self._poisoned is not None:
            return BREAKER_POISONED
        self._read_only_active()
        return self._breaker

    def _read_only_active(self) -> bool:
        """Whether read-only mode is still in force, applying quiet decay:
        a full ``recovery_interval`` without a trip closes the breaker
        (the overload that drove the trips has passed)."""
        if self._breaker != BREAKER_READ_ONLY:
            return False
        if self._clock() - self._read_only_since \
                >= self.overload.recovery_interval:
            self.reset_breaker()
            return False
        return True

    def reset_breaker(self):
        """Operator override: close a read-only breaker and forget trips.

        A latched poisoning is *not* resettable — it means data was lost
        or recovery itself failed, so the index state cannot be trusted.
        """
        if self._poisoned is not None:
            raise RuntimeError(
                "cannot reset a poisoned dispatcher: the failure was "
                "unrecoverable, the index state is not trustworthy")
        self._breaker = BREAKER_CLOSED
        self._trip_times.clear()
        self._read_only_since = None

    # -- execution ---------------------------------------------------------

    def _step(self, ops, keys, vals):
        """One execute + rebuild-if-due → (found, val, ovf, rebuilt, incr,
        drop, pn)."""
        if isinstance(self._index, dist.ShardedPIIndex):
            state, (found, val), _, dropped = dist.execute_sharded(
                self._index, self._mesh, ops, keys, vals,
                capacity_factor=self.capacity_factor)
            # each device rebuilds its own shard iff it is due; the flags
            # arrive reduced over the shards
            self._index, f = dist.maybe_rebuild_sharded(state, self._mesh)
            return (found, val, f["overflow"], f["rebuilt"],
                    f["incremental"], jnp.sum(dropped), f["pn"])
        self._index, found, val, ovf, rebuilt, incr, pn = _step_single(
            self._index, ops, keys, vals)
        return found, val, ovf, rebuilt, incr, None, pn

    def _window_has_writes(self, window: Window) -> bool:
        occ = window.occupancy
        ops = np.asarray(window.ops[:occ])
        return bool(np.any((ops != SEARCH) & (ops != RANGE)))

    def _window_has_ranges(self, window: Window) -> bool:
        if window.keys2 is None:  # pre-range producer: no range lane
            return False
        occ = window.occupancy
        return bool(np.any(np.asarray(window.ops[:occ]) == RANGE))

    @staticmethod
    def _point_view(window: Window):
        """The window's point-op image: RANGE lanes become sentinel
        SEARCHes — the exact shape of a pad slot, so the single compiled
        point execute serves range-bearing windows unchanged (and the
        breaker's replay, being masked the same way, stays bit-identical).
        Windows without ranges pass through untouched (zero-copy).
        """
        ops = np.asarray(window.ops)
        is_r = ops == RANGE
        if not is_r.any():
            return window.ops, window.keys
        keys = np.asarray(window.keys)
        sent = sentinel_for(keys.dtype)
        return (np.where(is_r, SEARCH, ops).astype(ops.dtype),
                np.where(is_r, sent, keys).astype(keys.dtype))

    def _execute_ranges(self, window: Window):
        """One fused range launch against the PRE-window index state."""
        ops = jnp.asarray(window.ops)
        keys = jnp.asarray(window.keys)
        keys2 = jnp.asarray(window.keys2)
        if isinstance(self._index, dist.ShardedPIIndex):
            return execute_ranges_sharded(self._index, self._mesh, ops, keys,
                                          keys2, self.max_span)
        return execute_ranges(self._index, ops, keys, keys2, self.max_span)

    def _breaker_armed(self) -> bool:
        return (self.overload is not None and self.overload.breaker
                and not isinstance(self._index, dist.ShardedPIIndex))

    def submit(self, window: Window) -> List[WindowResult]:
        """Dispatch a sealed window; retire whatever exceeds the depth.

        Returns the windows retired by this call (possibly empty) so
        callers can stream results without a separate polling loop.
        """
        self._check_poisoned()
        if self._read_only_active() and self._window_has_writes(window):
            if self.metrics is not None:
                self.metrics.read_only_rejections += window.n_arrivals
            raise ReadOnlyModeError(
                f"dispatcher is read-only after {self.breaker_trips} "
                f"breaker trips: window with writes rejected (searches "
                f"still serve).  Retry after the breaker closes, or "
                f"reset_breaker() to override.")
        pre = self._index if self._breaker_armed() else None
        # ranges first, against the pre-execute state: every RANGE in the
        # window observes the index as of the window boundary (DESIGN.md
        # §9), which is what makes exact-pair coalescing across window
        # writes sound.  Read-only, so failure-free w.r.t. the breaker.
        rcnt = rsum = None
        if self._window_has_ranges(window):
            with span("dispatch.ranges"):
                rcnt, rsum = self._execute_ranges(window)
        with span("dispatch.launch"):
            ops, keys = self._point_view(window)
            found, val, ovf, rebuilt, incr, dropped, pn = self._step(
                jnp.asarray(ops), jnp.asarray(keys),
                jnp.asarray(window.vals))
        self._inflight.append(
            _InFlight(window, found, val, ovf, rebuilt, incr, dropped,
                      pn=pn, rcnt=rcnt, rsum=rsum, pre_index=pre))
        if self.durability is not None:
            # the new index state reflects every window up to and
            # including this one, so window.seq is its WAL position
            with span("dispatch.snapshot"):
                self.durability.maybe_snapshot(self._index, window.seq)
        retired = []
        while len(self._inflight) > self.depth:
            retired.append(self._retire_front())
        return retired

    def flush(self) -> List[WindowResult]:
        """Retire every in-flight window (blocks until the device drains)."""
        self._check_poisoned()
        retired = []
        while self._inflight:
            retired.append(self._retire_front())
        return retired

    def run(self, stream, wcfg: Optional[WindowConfig] = None, *,
            collector: Optional[Collector] = None,
            chunk: Optional[int] = None,
            clock=None) -> List[WindowResult]:
        """Replay a whole arrival stream: bulk admission fused with
        double-buffered submit.

        ``stream`` is anything with 1-D ``t/ops/keys/vals`` arrays (an
        ``ArrivalStream``; an optional ``keys2`` array carries RANGE
        upper bounds); arrival i's qid is its position i.  Admission
        goes through ``Collector.offer_many`` one ``chunk`` at a time
        (default: one window's worth) so window formation for chunk k+1
        overlaps the device executing chunk k — feeding the whole stream
        to one ``offer_many`` call would serialize the two phases the
        depth exists to overlap.  With ``clock`` given, admission times
        are stamped from it per chunk (wall-clock saturation replay, the
        benchmark/example mode); otherwise the stream's own virtual times
        drive deadline splitting (deterministic, the oracle-test mode).
        The tail window is flush-sealed and every window is retired
        before returning, in retirement order.
        """
        col = collector if collector is not None else Collector(
            wcfg if wcfg is not None else WindowConfig(),
            on_seal=(self.durability.on_seal
                     if self.durability is not None else None))
        step = chunk or col.cfg.batch
        n = len(stream.t)
        qids = np.arange(n)
        keys2 = getattr(stream, "keys2", None)
        retired: List[WindowResult] = []
        for s in range(0, n, step):
            e = min(n, s + step)
            t = np.full(e - s, clock()) if clock is not None \
                else stream.t[s:e]
            _, sealed = col.offer_many(t, stream.ops[s:e], stream.keys[s:e],
                                       stream.vals[s:e], qids[s:e],
                                       keys2[s:e] if keys2 is not None
                                       else None)
            for w in sealed:
                retired.extend(self.submit(w))
        tail = col.take(clock()) if clock is not None else col.take()
        if tail is not None:
            retired.extend(self.submit(tail))
        retired.extend(self.flush())
        return retired

    def _check_poisoned(self):
        if self._poisoned is not None:
            # fresh instance per raise: re-raising the latched object would
            # grow its traceback on every call (each raise appends frames
            # to the same __traceback__), so long-lived callers polling a
            # poisoned dispatcher would accumulate unbounded tracebacks.
            # The original — with the traceback of the actual failure —
            # rides along as __cause__.
            e = self._poisoned
            fresh = type(e)(*e.args)
            fresh.windows = getattr(e, "windows", [])
            raise fresh from e

    def _retire_front(self) -> WindowResult:
        """Retire the oldest in-flight window; latch any data-loss error.

        A failed retirement means the index state already reflects an
        execute that lost queries — every later window was dispatched
        against that corrupted state, so silently continuing would
        propagate the loss.  With a breaker armed (``overload.breaker``,
        local index) a pending overflow is instead *recovered*: see
        ``_breaker_recover``.  Otherwise — or when recovery itself fails —
        the failure poisons the dispatcher (further ``submit``/``flush``
        re-raise it), the failing window stays in-flight, and the
        exception's ``windows`` lists it plus every window queued behind
        it, so the caller can replay them elsewhere.
        """
        try:
            res = self._retire(self._inflight[0])
        except PendingOverflowError as e:
            if self._breaker_armed() and self._inflight[0].pre_index \
                    is not None:
                return self._breaker_recover(e)
            e.windows = [f.window for f in self._inflight]
            self._poisoned = e
            raise
        except DispatchOverflowError as e:
            e.windows = [f.window for f in self._inflight]
            self._poisoned = e
            raise
        self._inflight.pop(0)
        return res

    def _breaker_recover(self, cause: PendingOverflowError) -> WindowResult:
        """Recover from a pending overflow: rollback → repack → replay.

        The overflowing execute dropped net inserts, so the post-execute
        state is corrupt — but the state *before* the failing window is
        still on device (``pre_index``; nothing is donated), and every
        window that executed after it is still in flight with its inputs
        intact.  Roll back, force a full repack (empties the pending
        buffer — the resource that overflowed), and replay every
        quarantined window through the always-rebuild recovery step.  A
        replay that overflows *from an empty pending buffer* is a
        geometry error (one window nets more inserts than the whole
        buffer) and latches poisoned for real.

        Escalation: recoveries inside one rolling ``recovery_interval``
        beyond ``max_recoveries`` degrade the breaker to read-only; a trip
        while *already* read-only means the degraded mode failed to
        protect the index and latches poisoned (the state machine's final
        arrow) — after the recovery completes, so the state stays
        consistent for a post-mortem.
        """
        ocfg = self.overload
        now = self._clock()
        self.breaker_trips += 1
        self._trip_times.append(now)
        if self.metrics is not None:
            self.metrics.breaker_trips += 1
        was_read_only = self._breaker == BREAKER_READ_ONLY
        self._breaker = BREAKER_RECOVERING
        quarantined = self._inflight
        self._inflight = []
        self._index = _repack(quarantined[0].pre_index)
        for i, f in enumerate(quarantined):
            w = f.window
            # same point-view masking as the live submit; the original
            # range results ride along untouched (ranges read the
            # pre-window state, which the rollback restored — recomputing
            # them against the repacked layout could only change
            # max_span truncation, never correctness, so keeping the
            # as-served values is the bit-identical choice)
            ops, keys = self._point_view(w)
            self._index, found, val, ovf, pn = _step_recover(
                self._index, jnp.asarray(ops), jnp.asarray(keys),
                jnp.asarray(w.vals))
            if bool(ovf):  # syncs, but recovery is off the fast path anyway
                err = PendingOverflowError(
                    f"unrecoverable overflow: window nets more inserts than "
                    f"the entire pending buffer even after a repack "
                    f"(occupancy {w.occupancy} vs pending_capacity "
                    f"{self._pending_capacity}) — geometry error, grow "
                    f"PIConfig.pending_capacity above the window batch")
                err.windows = [g.window for g in quarantined[i:]]
                self._poisoned = err
                raise err from cause
            self._inflight.append(
                _InFlight(w, found, val, ovf, jnp.array(True), None, None,
                          pn=pn, rcnt=f.rcnt, rsum=f.rsum, pre_index=None))
        self.breaker_recoveries += 1
        if self.metrics is not None:
            self.metrics.breaker_recoveries += 1
        if self.durability is not None and quarantined[-1].window.seq \
                is not None:
            # the quarantined windows were WAL'd before dispatch, so no
            # acked op can be lost — but a snapshot taken between the
            # corrupt execute and this recovery would capture pre-rollback
            # state.  A fresh blocking snapshot at the replayed frontier
            # supersedes it.
            self.durability.snapshot(self._index,
                                     seq=quarantined[-1].window.seq)
        if was_read_only:
            err = PendingOverflowError(
                "overflow while the breaker was already read-only: the "
                "degraded mode failed to protect the index.  State was "
                "recovered (no data lost) but serving halts — the workload "
                "is beyond what this geometry can absorb.")
            err.windows = []
            self._poisoned = err
            raise err from cause
        self._trip_times = [t for t in self._trip_times
                            if now - t <= ocfg.recovery_interval]
        if len(self._trip_times) > ocfg.max_recoveries:
            self._breaker = BREAKER_READ_ONLY
            self._read_only_since = now
        else:
            self._breaker = BREAKER_CLOSED
        res = self._retire(self._inflight[0])
        self._inflight.pop(0)
        return res

    def _retire(self, infl: _InFlight) -> WindowResult:
        with span("dispatch.wait"):
            found = np.asarray(infl.found)   # blocks on the device here
        with span("dispatch.result"):
            val = np.asarray(infl.val)
            if self.check_overflow and bool(infl.overflow):
                raise PendingOverflowError(
                    "pending buffer overflowed while executing a window: net "
                    "inserts were dropped.  Grow PIConfig.pending_capacity "
                    "above the window batch, or rebuild more aggressively.")
            if self.check_overflow and infl.dropped is not None \
                    and int(infl.dropped) > 0:
                raise DispatchOverflowError(
                    f"fence routing dropped {int(infl.dropped)} queries: a "
                    f"shard's send bucket overflowed.  Raise capacity_factor "
                    f"({self.capacity_factor}) or rebalance the fences.")
            res = WindowResult(window=infl.window, found=found, val=val,
                               t_retired=self._clock(),
                               rebuilt=bool(infl.rebuilt),
                               rebuilt_incremental=(
                                   infl.incr is not None and bool(infl.incr)),
                               pending_fill=(
                                   int(infl.pn) / self._pending_capacity
                                   if infl.pn is not None else float("nan")),
                               rcnt=(np.asarray(infl.rcnt)
                                     if infl.rcnt is not None else None),
                               rsum=(np.asarray(infl.rsum)
                                     if infl.rsum is not None else None))
            if self.metrics is not None:
                self.metrics.on_retire(res)
            return res
