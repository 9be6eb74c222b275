#!/usr/bin/env python3
"""Chip smoke: drive PI's serving path once on a TPU and check every answer.

    python chip_smoke.py             # one chip: collect -> WAL -> dispatch
                                     # at 16M keys, then recover() from the WAL
    python chip_smoke.py --chips 4   # four chips: the sharded index only

The one-chip phase builds a 16M-key index (capacity 2^25, the paper's
2M-256M range), serves at least 64 windows of 8192 slots through the
normal entry points (``Collector`` with a ``Durability`` WAL on its seal
hook, ``Dispatcher.run``) under ~50% writes, a few percent of them DELETEs,
and ~5% RANGE scans of 1-100 records, then recovers the index from its
WAL.  The four-chip phase range-partitions the same data over a
``("data",)`` mesh and serves the same stream through the sharded
dispatcher.  Every point answer is compared with ``core.ref.RefIndex`` and
every RANGE ``(count, sum)`` with a sorted-array reference; any mismatch
raises.  Printed times are those of one smoke run, not metrics.

The script needs a TPU: on any other platform it exits 2 and prints no
result.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
JAX's compilation cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when set,
else to ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import tempfile
import time
from collections import defaultdict

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

RATE = 1e5  # virtual arrivals per second of the open-loop stream


class SmokeError(RuntimeError):
    """An answer, a placement or a recovery did not match its reference."""


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    n_keys: int = 1 << 24            # 16M int32 keys (paper: 2M-256M)
    capacity: int = 1 << 25          # per-shard storage slots
    pending_capacity: int = 1 << 15  # per-shard pending buffer
    fanout: int = 8
    batch: int = 8192                # window slots (the paper's batch)
    windows: int = 66                # arrivals = windows * batch
    write_ratio: float = 0.5
    delete_frac: float = 0.06        # of the writes, turned into DELETEs
    range_frac: float = 0.05
    seed: int = 0


ONE_CHIP = SmokeConfig()
# 2^24 keys over four shards of capacity 2^23; each shard absorbs a
# quarter of the fresh inserts, so a smaller pending buffer still rebuilds
FOUR_CHIPS = dataclasses.replace(ONE_CHIP, capacity=1 << 23,
                                 pending_capacity=1 << 14)


# ---------------------------------------------------------------------------
# data, traffic and the reference
# ---------------------------------------------------------------------------

def make_data(cfg: SmokeConfig):
    """Dataset, open-loop stream and the reference, all from ``cfg.seed``."""
    from repro import data as data_mod
    from repro.core import DELETE, INSERT, RefIndex
    from repro.pipeline import ArrivalConfig, make_arrivals

    ycfg = data_mod.YCSBConfig(n_keys=cfg.n_keys, batch=cfg.batch,
                               write_ratio=cfg.write_ratio, seed=cfg.seed)
    keys, vals = data_mod.ycsb_dataset(ycfg)
    # spans of 1..100 mean key gaps cover ~1-100 records (uniform keys)
    gap = ycfg.key_space // cfg.n_keys
    stream = make_arrivals(
        ArrivalConfig(rate=RATE, n_arrivals=cfg.windows * cfg.batch,
                      range_frac=cfg.range_frac, span_min=gap,
                      span_max=100 * gap, seed=cfg.seed),
        ycfg, keys)
    # make_arrivals emits no DELETE: turn some writes into deletes of
    # existing keys
    rng = np.random.default_rng((cfg.seed, 0xDE1))
    dele = (stream.ops == INSERT) & (rng.random(len(stream))
                                     < cfg.delete_frac)
    victims = keys[rng.integers(0, cfg.n_keys, len(stream))]
    stream = dataclasses.replace(
        stream, ops=np.where(dele, np.int32(DELETE), stream.ops),
        keys=np.where(dele, victims, stream.keys),
        vals=np.where(dele, 0, stream.vals).astype(np.int32))
    ref = RefIndex(dict(zip(keys.tolist(), vals.tolist())))
    return keys, vals, stream, ref


class SortedRef:
    """Sorted-array image of the reference map, for RANGE aggregates.

    ``RefIndex.range`` re-sorts the whole map per call; this keeps one
    sorted copy and patches only the keys a window wrote.
    """

    def __init__(self, data: dict):
        self.k = np.fromiter(data.keys(), np.int64, len(data))
        self.v = np.fromiter(data.values(), np.int64, len(data))
        order = np.argsort(self.k)
        self.k, self.v = self.k[order], self.v[order]

    def ranges(self, lo, hi):
        """(count, int32-wrapped sum) of live records in each [lo, hi]."""
        i0 = np.searchsorted(self.k, lo, side="left")
        i1 = np.searchsorted(self.k, hi, side="right")
        csum = np.concatenate([[0], np.cumsum(self.v)])
        s = (csum[i1] - csum[i0]).astype(np.int64)
        return i1 - i0, ((s + (1 << 31)) % (1 << 32)) - (1 << 31)

    def patch(self, keys, data: dict):
        """Re-read ``keys`` (the window's written keys) from ``data``."""
        tk = np.unique(np.asarray(keys, np.int64))
        pos = np.searchsorted(self.k, tk)
        hit = pos < len(self.k)
        hit[hit] = self.k[pos[hit]] == tk[hit]
        self.k = np.delete(self.k, pos[hit])
        self.v = np.delete(self.v, pos[hit])
        live = np.array([k in data for k in tk.tolist()], bool)
        nk = tk[live]
        nv = np.array([data[k] for k in nk.tolist()], np.int64)
        at = np.searchsorted(self.k, nk)
        self.k = np.insert(self.k, at, nk)
        self.v = np.insert(self.v, at, nv)


def check_answers(retired, stream, ref) -> dict:
    """Replay the served windows against the reference, in window order.

    Every RANGE of a window reads the pre-window state (DESIGN.md §9);
    point ops then apply in arrival order.  Raises ``SmokeError`` at the
    first mismatch; returns counts of what was checked.
    """
    from repro.core import DELETE, RANGE, SEARCH

    sref = SortedRef(ref.data)
    n_point = n_range = 0
    for res in retired:
        qids = np.asarray(res.window.qids)
        ops = stream.ops[qids]
        rq = qids[ops == RANGE]
        if rq.size:
            got = res.per_arrival_ranges()
            cnt, sm = sref.ranges(stream.keys[rq], stream.keys2[rq])
            for q, c, s in zip(rq.tolist(), cnt.tolist(), sm.tolist()):
                if got[q] != (c, s):
                    raise SmokeError(f"RANGE arrival {q}: got {got[q]}, "
                                     f"reference ({c}, {s})")
            n_range += rq.size
        pq = qids[ops != RANGE]
        want = ref.execute(stream.ops[pq], stream.keys[pq], stream.vals[pq])
        got = res.per_arrival()
        for q, w in zip(pq.tolist(), want):
            op = stream.ops[q]
            found, val = got[q]
            if op == SEARCH and (val if found else None) != w:
                raise SmokeError(f"SEARCH arrival {q}: got "
                                 f"{(found, val)}, reference {w}")
            if op == DELETE and found != (w is not None):
                raise SmokeError(f"DELETE arrival {q}: got {found}, "
                                 f"reference {w is not None}")
        n_point += pq.size
        wk = stream.keys[pq][stream.ops[pq] != SEARCH]
        if wk.size:
            sref.patch(wk, ref.data)
    return dict(points=n_point, ranges=n_range, final=sref)


def check_pairs(what: str, k, v, want_k, want_v):
    if not (np.array_equal(k, want_k) and np.array_equal(v, want_v)):
        raise SmokeError(f"{what}: live (key, val) pairs differ "
                         f"({len(k)} vs {len(want_k)} keys)")


# ---------------------------------------------------------------------------
# instrumentation
# ---------------------------------------------------------------------------

class CompileLog:
    """Backend compilations seen by JAX, by program name."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.secs = defaultdict(float)
        self.count = 0

    def __call__(self, event, duration, **kw):
        if event == self.EVENT:
            self.secs[kw.get("fun_name", "?")] += duration
            self.count += 1

    @contextlib.contextmanager
    def listening(self):
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self)
        try:
            yield self
        finally:
            mon.unregister_event_duration_listener(self)


def print_compiles(log: CompileLog, out):
    for name, s in sorted(log.secs.items(), key=lambda kv: -kv[1]):
        if s >= 0.5:
            print(f"  compile {name}: {s:.1f} s", file=out)
    print(f"  compile total: {sum(log.secs.values()):.1f} s in "
          f"{log.count} programs", file=out)


def peak_bytes(devices) -> list:
    """``peak_bytes_in_use`` per device (None where not reported)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def warm_window(batch: int, key: int):
    """One padded window with a SEARCH and a RANGE: the shapes every
    served window has, so submitting it compiles both programs."""
    from repro.core import RANGE, SEARCH
    from repro.pipeline import Collector, WindowConfig

    col = Collector(WindowConfig(batch=batch))
    col.offer_many(np.zeros(2), np.array([SEARCH, RANGE], np.int32),
                   np.array([key, key], np.int32), np.zeros(2, np.int32),
                   np.arange(2), keys2=np.array([0, key], np.int32))
    return col.take()


def serve(disp, stream, batch: int, log: CompileLog, out, *,
          collector=None, mesh=None):
    """Warm up, then serve the whole stream; returns retired windows."""
    from repro.pipeline import Dispatcher, WindowConfig

    # the steps donate nothing, so a throwaway dispatcher can compile them
    # on the live index without changing it
    warm = Dispatcher(disp.index, mesh=mesh, depth=0)
    with log.listening():
        t0 = time.perf_counter()
        warm.submit(warm_window(batch, int(stream.keys[0])))
        print(f"warm-up (compile included): "
              f"{time.perf_counter() - t0:.1f} s", file=out)
    print_compiles(log, out)
    served = CompileLog()
    deadline = 2 * batch / RATE
    with served.listening():
        t0 = time.perf_counter()
        retired = disp.run(stream, WindowConfig(batch=batch,
                                                deadline=deadline),
                           collector=collector)
        wall = time.perf_counter() - t0
    rebuilds = [(i, "incremental" if r.rebuilt_incremental else "repack")
                for i, r in enumerate(retired) if r.rebuilt]
    print(f"smoke (not a metric): {len(retired)} windows, {len(stream)} "
          f"ops in {wall:.2f} s wall", file=out)
    print(f"compilations inside the served windows: {served.count}",
          file=out)
    for i, tier in rebuilds:
        print(f"  rebuild after window {i}: {tier}", file=out)
    if len(retired) < 64:
        raise SmokeError(f"only {len(retired)} windows served")
    if not rebuilds:
        raise SmokeError("no rebuild ran")
    return retired, dict(windows=len(retired), wall=wall,
                         compiles_served=served.count,
                         rebuilds=rebuilds)


# ---------------------------------------------------------------------------
# the two phases
# ---------------------------------------------------------------------------

def run_one_chip(cfg: SmokeConfig = ONE_CHIP, out=sys.stdout) -> dict:
    """Build → serve through collect → WAL → dispatch → check → recover."""
    import jax
    import jax.numpy as jnp
    from repro.core import PIConfig, build, live_items
    from repro.pipeline import (Collector, Dispatcher, Durability,
                                WindowConfig, recover)

    t0 = time.perf_counter()
    keys, vals, stream, ref = make_data(cfg)
    print(f"data: {cfg.n_keys} keys, {len(stream)} arrivals in "
          f"{time.perf_counter() - t0:.1f} s", file=out)
    pcfg = PIConfig(capacity=cfg.capacity, fanout=cfg.fanout,
                    pending_capacity=cfg.pending_capacity, backend="xla")
    log = CompileLog()
    with log.listening():
        t0 = time.perf_counter()
        index = jax.block_until_ready(
            build(pcfg, jnp.asarray(keys), jnp.asarray(vals)))
        print(f"build (compile included): {time.perf_counter() - t0:.1f} s",
              file=out)
    with tempfile.TemporaryDirectory(prefix="pi_smoke_") as tmp:
        wal_dir = os.path.join(tmp, "durable")
        dur = Durability(wal_dir, index, fsync="per_window")
        deadline = 2 * cfg.batch / RATE
        col = Collector(WindowConfig(batch=cfg.batch, deadline=deadline),
                        on_seal=dur.on_seal)
        disp = Dispatcher(index, depth=1, durability=dur)
        retired, summary = serve(disp, stream, cfg.batch, log, out,
                                 collector=col)
        dur.close()
        checked = check_answers(retired, stream, ref)
        final = checked.pop("final")
        live_k, live_v = live_items(disp.index)
        check_pairs("served index vs reference", live_k, live_v,
                    final.k, final.v)
        print(f"checked {checked['points']} point and {checked['ranges']} "
              f"RANGE answers against the reference: all match", file=out)
        t0 = time.perf_counter()
        recovered, replayed = recover(wal_dir)
        rk, rv = live_items(recovered)
        check_pairs("recovered index vs served index", rk, rv,
                    live_k, live_v)
        print(f"recover(): replayed {len(replayed)} WAL windows in "
              f"{time.perf_counter() - t0:.1f} s; live items identical "
              f"({len(rk)} keys)", file=out)
    peak = peak_bytes(jax.devices()[:1])
    print(f"peak_bytes_in_use: {peak[0]}", file=out)
    return dict(summary, **checked, peak_bytes=peak,
                compile_secs=dict(log.secs))


def run_sharded(devices, cfg: SmokeConfig = FOUR_CHIPS,
                out=sys.stdout) -> dict:
    """The sharded index over a ``("data",)`` mesh of ``devices``."""
    import jax
    from jax.sharding import Mesh
    from repro.core import PIConfig, build_sharded, collect_pairs
    from repro.pipeline import Dispatcher

    mesh = Mesh(np.array(devices), ("data",))
    S = len(devices)
    t0 = time.perf_counter()
    keys, vals, stream, ref = make_data(cfg)
    print(f"data: {cfg.n_keys} keys, {len(stream)} arrivals in "
          f"{time.perf_counter() - t0:.1f} s", file=out)
    pcfg = PIConfig(capacity=cfg.capacity, fanout=cfg.fanout,
                    pending_capacity=cfg.pending_capacity, backend="xla")
    log = CompileLog()
    with log.listening():
        t0 = time.perf_counter()
        state = build_sharded(pcfg, S, keys, vals, mesh=mesh)
        jax.block_until_ready(state.shards)
        print(f"build_sharded (compile included): "
              f"{time.perf_counter() - t0:.1f} s", file=out)
    print(f"peak_bytes_in_use per device after build_sharded: "
          f"{peak_bytes(devices)}", file=out)
    placement = check_placement(state, mesh, out)
    disp = Dispatcher(state, mesh=mesh, depth=1)
    # routing drops raise DispatchOverflowError at retirement, so serving
    # the whole stream is the zero-drop check
    retired, summary = serve(disp, stream, cfg.batch, log, out, mesh=mesh)
    print("routing drops: 0", file=out)
    checked = check_answers(retired, stream, ref)
    final = checked.pop("final")
    k, v = collect_pairs(disp.index)
    check_pairs("sharded index vs reference", k, v, final.k, final.v)
    print(f"checked {checked['points']} point and {checked['ranges']} "
          f"RANGE answers against the reference: all match", file=out)
    peak = peak_bytes(devices)
    print(f"peak_bytes_in_use per device: {peak}", file=out)
    return dict(summary, **checked, placement=placement, peak_bytes=peak,
                compile_secs=dict(log.secs))


def check_placement(state, mesh, out) -> list:
    """Shard s's leaves must live on the s-th device of the mesh."""
    import jax

    devs = list(mesh.devices.flat)
    nbytes = defaultdict(int)
    for leaf in jax.tree.leaves(state.shards):
        for sh in leaf.addressable_shards:
            s = sh.index[0].start
            if sh.device != devs[s]:
                raise SmokeError(f"shard {s} is on {sh.device}, "
                                 f"expected {devs[s]}")
            nbytes[sh.device] += sh.data.nbytes
    for sh in state.shards.keys.addressable_shards:
        print(f"  shard {sh.index[0].start}: keys on {sh.device}", file=out)
    per_dev = [nbytes[d] for d in devs]
    print(f"  index bytes per device: {per_dev}", file=out)
    return per_dev


def result_line(devices) -> str:
    """The last line of stdout: the devices the phase ran on."""
    return json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded index over four chips")
    args = ap.parse_args(argv)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    from repro.compile_cache import use_compile_cache

    print(f"compilation cache: {use_compile_cache()}")
    used = devices[:args.chips]
    print(f"device: {devices[0].device_kind}, {len(used)} of "
          f"{len(devices)} in use")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_sharded(used)
    else:
        run_one_chip()
    print(f"total: {time.perf_counter() - t0:.1f} s")
    print(result_line(used))
    return 0


if __name__ == "__main__":
    sys.exit(main())
