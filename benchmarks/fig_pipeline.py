"""Pipeline figure: double-buffered dispatch vs naive form-then-execute.

Saturation replay of open-loop arrival streams (the process shapes the
key/op sequence; the host offers as fast as the window admits) through two
dispatch policies over the SAME static batch shape and index:

  naive      depth-0 dispatch, no coalescing: form a window, execute it,
             block for results, repeat — host and device strictly
             alternate (the pre-pipeline driver loop).
  pipelined  depth-1 double buffering + SEARCH coalescing: the host forms
             window k+1 while the device executes window k, and skewed
             streams pack more arrivals per executed slot.

Reported per {process} × {theta}: arrivals/s plus enqueue→result latency
percentiles, and the pipelined/naive qps speedup.  A separate
``admission`` block isolates the host-side window-formation cost: the
same uniform stream admitted through the scalar ``offer`` loop vs
vectorized ``offer_many`` (no dispatch), whose ratio is the lifted
admission ceiling.  A ``durability`` block measures the WAL tax: the
pipelined replay with the admission-point WAL off vs on under each
fsync policy (``config.durability_tax`` records the qps ratios).  An
``overload`` block measures the degradation tier (DESIGN.md §8):
breaker recovery at 2x pending capacity, shed rate and goodput under a
write flood, and the adaptive deadline controller against a static
baseline on a diurnal stream.  ``BENCH_pipeline.json`` carries the
same rows for the perf trajectory.
"""
from __future__ import annotations

import dataclasses
import tempfile
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import default_backend, emit, make_index, replay_stream
from repro import data as data_mod
from repro.core import INSERT, PIConfig, build
from repro.pipeline import (ArrivalConfig, Collector, Dispatcher, Durability,
                            OverloadConfig, OverloadController,
                            PipelineMetrics, RetryPolicy, WindowConfig,
                            make_arrivals)
from repro.compile_cache import use_compile_cache


def replay(index, stream, wcfg: WindowConfig, depth: int, bulk: bool):
    """Drive one stream through collector+dispatcher; summary dict."""
    mets = PipelineMetrics()
    col = Collector(wcfg)
    disp = Dispatcher(index, depth=depth, metrics=mets)
    now = time.perf_counter
    mets.start(now())
    replay_stream(disp, col, stream, bulk=bulk, clock=now)
    mets.stop(now())
    return mets.summary()


def admission_bench(batch: int, n_arrivals: int, n_keys: int,
                    coalesce: bool = True):
    """Admission-only throughput: scalar ``offer`` loop vs ``offer_many``.

    Uniform (theta=0) read stream — the worst case for coalescing wins,
    so the measured ratio is pure vectorization, not slot sharing.
    Windows are formed and discarded (no dispatch); times come from the
    stream's own virtual axis so the clock isn't part of the cost.
    """
    ycfg = data_mod.YCSBConfig(n_keys=n_keys, theta=0.0, write_ratio=0.0)
    keys, _ = data_mod.ycsb_dataset(ycfg)
    stream = make_arrivals(ArrivalConfig(n_arrivals=n_arrivals), ycfg, keys)
    wcfg = WindowConfig(batch=batch, coalesce=coalesce)

    def scalar_pass():
        col = Collector(wcfg)
        t, ops, keys_l, vals = (stream.t.tolist(), stream.ops.tolist(),
                                stream.keys.tolist(), stream.vals.tolist())
        offer, take = col.offer, col.take
        n_w = 0
        t0 = time.perf_counter()
        for i in range(n_arrivals):
            while not offer(t[i], ops[i], keys_l[i], vals[i], i):
                take(t[i])
                n_w += 1
        return time.perf_counter() - t0, n_w

    def bulk_pass():
        col = Collector(wcfg)
        qids = np.arange(n_arrivals)
        # admission-only: no dispatch to overlap with, so several windows
        # per offer_many call amortize the per-call fixed cost (pipeline
        # replays chunk one window at a time to keep the overlap)
        chunk = max(batch, 4096)
        n_w = 0
        t0 = time.perf_counter()
        for s in range(0, n_arrivals, chunk):
            e = min(n_arrivals, s + chunk)
            _, sealed = col.offer_many(stream.t[s:e], stream.ops[s:e],
                                       stream.keys[s:e], stream.vals[s:e],
                                       qids[s:e])
            n_w += len(sealed)
        return time.perf_counter() - t0, n_w

    # best-of-3 per mode: wall-clock on a shared host is noisy and the
    # runs are short; the best run measures the code, not the neighbours
    dt_off, w_off = min(scalar_pass() for _ in range(3))
    dt_many, w_many = min(bulk_pass() for _ in range(3))
    assert w_off == w_many, "bulk and scalar admission disagree on windows"
    rows = [("admission", "poisson", 0.0, "offer",
             round(n_arrivals / dt_off), 0.0, 0.0, w_off, batch, 0),
            ("admission", "poisson", 0.0, "offer_many",
             round(n_arrivals / dt_many), 0.0, 0.0, w_many, batch, 0)]
    speedup = dt_off / dt_many
    print(f"[pipeline] admission: offer_many {n_arrivals / dt_many:,.0f} "
          f"arrivals/s vs offer {n_arrivals / dt_off:,.0f} "
          f"({speedup:.1f}x, batch {batch})")
    return rows, round(speedup, 3)


def durability_bench(n_keys: int, batch: int, n_arrivals: int,
                     backend=None):
    """Durability tax: the pipelined replay with the WAL off vs on, per
    fsync policy.

    ``Durability`` is constructed outside the timed region (the initial
    blocking snapshot is a one-time cost, not a per-window tax) and
    ``snapshot_every=0``, so the measured delta is exactly the
    admission-point WAL: one encode+append per sealed window plus
    whatever the fsync policy adds.  The acceptance bar lives in
    ``config.durability_tax``: ``off`` must stay within ~10% of the
    WAL-off qps.
    """
    idx, keys, ycfg = make_index(n_keys, backend=backend)
    stream = make_arrivals(ArrivalConfig(n_arrivals=n_arrivals), ycfg, keys)
    fresh = lambda: jax.tree.map(jnp.copy, idx)
    wcfg = WindowConfig(batch=batch)
    now = time.perf_counter
    # warm the compiled executable once; every policy reuses it
    warm = make_arrivals(ArrivalConfig(n_arrivals=2 * batch, seed=7),
                         ycfg, keys)
    Dispatcher(fresh(), depth=1).run(warm, wcfg, clock=now)

    def one_run(policy: str):
        mets = PipelineMetrics()
        state = fresh()
        if policy == "wal_off":
            dur, col = None, Collector(wcfg)
            tmp = None
        else:
            tmp = tempfile.TemporaryDirectory()
            dur = Durability(tmp.name, state, fsync=policy,
                             snapshot_every=0, metrics=mets)
            col = Collector(wcfg, on_seal=dur.on_seal)
        disp = Dispatcher(state, depth=1, metrics=mets, durability=dur)
        mets.start(now())
        replay_stream(disp, col, stream, clock=now)
        mets.stop(now())
        if dur is not None:
            dur.close()
        if tmp is not None:
            tmp.cleanup()
        return mets.summary()

    rows, qps = [], {}
    for policy in ("wal_off", "off", "interval", "per_window"):
        s = max((one_run(policy) for _ in range(3)),
                key=lambda s: s["qps"])
        qps[policy] = s["qps"]
        rows.append(("durability", "poisson", 0.0, policy,
                     round(s["qps"]), round(s["p50_ms"], 3),
                     round(s["p99_ms"], 3), s["windows"],
                     round(s["mean_occupancy"]), s["coalesced"]))
    tax = {p: round(qps[p] / qps["wal_off"], 3)
           for p in ("off", "interval", "per_window")}
    print(f"[pipeline] durability tax (qps vs WAL-off): "
          + ", ".join(f"{p}={r:.3f}" for p, r in tax.items()))
    return rows, tax


def overload_bench(backend=None):
    """Overload tier under saturation: three ``overload`` row blocks.

    ``breaker``   a distinct-insert burst at well over 2x the pending
                  capacity, shedding off — the geometry that used to
                  poison the dispatcher.  The circuit breaker must absorb
                  every overflow (recoveries == trips, goodput 1.0).
    ``shed``      a write-heavy hotkey flood through the full
                  ``OverloadController``: per-class shedding with bounded
                  retries.  Goodput is the acked fraction; the shed rate
                  and class split land in ``config.overload.shed``.
    ``deadline``  a diurnal stream replayed on its own (virtual) time
                  axis with the adaptive deadline controller on vs off.
                  The retune trajectory is recorded and adaptive goodput
                  must not trail the static baseline.

    Geometry note (same as tests/test_overload.py): for the pending
    buffer to overflow, windows must accumulate fill across retirements —
    so ``batch <= 3/4 * pending_capacity`` (the rebuild trigger fires at
    3/4 fill) and the seeded index is large enough that the 15%-churn
    rebuild trigger stays quiet.
    """
    now = time.perf_counter
    pc, batch = 256, 160
    rng = np.random.default_rng(11)
    keys0 = np.unique(rng.integers(1, 1 << 20, 6144).astype(np.int32))
    seed_idx = build(
        PIConfig(capacity=1 << 15, pending_capacity=pc, fanout=8,
                 backend=backend or default_backend()),
        jnp.asarray(keys0),
        jnp.asarray(rng.integers(0, 1 << 20, keys0.size).astype(np.int32)))
    fresh = lambda: jax.tree.map(jnp.copy, seed_idx)
    rows, summary = [], {}

    # -- breaker: 2x+ pending capacity, shedding off ----------------------
    n_burst = 4 * pc
    burst = types.SimpleNamespace(
        t=np.arange(n_burst, dtype=np.float64),
        ops=np.full(n_burst, INSERT, np.int32),
        keys=(2_000_000 + np.arange(n_burst)).astype(np.int32),
        vals=np.arange(n_burst, dtype=np.int32))
    m = PipelineMetrics()
    disp = Dispatcher(fresh(), depth=1, metrics=m,
                      overload=OverloadConfig(shed=False,
                                              max_recoveries=10_000))
    m.start(now())
    retired = disp.run(burst, collector=Collector(WindowConfig(batch=batch)),
                       chunk=batch, clock=now)
    m.stop(now())
    acked = {}
    for r in retired:
        acked.update(r.per_arrival())
    s = m.summary()
    assert s["breaker_trips"] >= 1, "burst geometry never overflowed"
    assert s["breaker_recoveries"] == s["breaker_trips"]
    assert len(acked) == n_burst, "breaker recovery lost an admitted op"
    rows.append(("overload", "burst", 0.0, "breaker", round(s["qps"]),
                 round(s["p50_ms"], 3), round(s["p99_ms"], 3), s["windows"],
                 round(s["mean_occupancy"]), s["coalesced"]))
    summary["breaker"] = {
        "trips": s["breaker_trips"], "recoveries": s["breaker_recoveries"],
        "goodput": round(len(acked) / n_burst, 3),
        "pending_fill_peak": round(s["pending_fill_peak"], 3)}
    print(f"[pipeline] overload breaker: {s['breaker_trips']} overflows "
          f"recovered, goodput {len(acked) / n_burst:.3f} at 4x pending "
          f"capacity")

    # -- shed: hotkey write flood through the controller ------------------
    n_flood = 6144
    flood = make_arrivals(
        ArrivalConfig(process="hotkey", rate=1e4, n_arrivals=n_flood,
                      hot_keys=4, hot_frac=0.8, seed=3),
        data_mod.YCSBConfig(write_ratio=0.6, theta=0.9), keys0)
    m = PipelineMetrics()
    ctl = OverloadController(
        OverloadConfig(shed_dup_at=0.15, shed_search_at=0.3,
                       shed_write_at=0.95, adapt_deadline=False,
                       max_recoveries=10_000),
        metrics=m, retry=RetryPolicy(max_retries=3))
    disp = Dispatcher(fresh(), depth=1, metrics=m, overload=ctl.cfg)
    m.start(now())
    rep = ctl.run(disp, Collector(WindowConfig(batch=batch)), flood,
                  chunk=batch, clock=now)
    m.stop(now())
    s = m.summary()
    rows.append(("overload", "hotkey", 0.9, "shed", round(s["qps"]),
                 round(s["p50_ms"], 3), round(s["p99_ms"], 3), s["windows"],
                 round(s["mean_occupancy"]), s["coalesced"]))
    summary["shed"] = {
        "goodput": round(rep.goodput / n_flood, 3),
        "shed_rate": round(s["shed_total"] / n_flood, 3),
        "shed_by_class": s["shed_by_class"], "retries": rep.retries,
        "dropped": len(rep.dropped),
        "pending_fill_peak": round(s["pending_fill_peak"], 3)}
    print(f"[pipeline] overload shed: goodput "
          f"{rep.goodput / n_flood:.3f}, shed rate "
          f"{s['shed_total'] / n_flood:.3f} ({s['shed_by_class']})")

    # -- deadline: diurnal stream, adaptive vs static ---------------------
    idx_d = build(
        PIConfig(capacity=1 << 15, pending_capacity=1024, fanout=8,
                 backend=backend or default_backend()),
        jnp.asarray(keys0),
        jnp.asarray(rng.integers(0, 1 << 20, keys0.size).astype(np.int32)))
    diurnal = make_arrivals(
        ArrivalConfig(process="diurnal", rate=2e3, n_arrivals=8000,
                      period=0.5, swing=0.95, seed=5),
        data_mod.YCSBConfig(write_ratio=0.2), keys0)

    def deadline_run(adapt: bool):
        mets = PipelineMetrics()
        ocfg = OverloadConfig(shed=False, breaker=False,
                              adapt_deadline=adapt, adjust_every=4,
                              hysteresis=2, deadline_min=1e-3,
                              deadline_max=0.5, deadline_step=2.0,
                              fill_low=0.5)
        # virtual time axis: the stream's own stamps drive deadline seals,
        # so the controller sees the diurnal shape, not host jitter
        d = Dispatcher(jax.tree.map(jnp.copy, idx_d), depth=1, metrics=mets,
                       clock=lambda: 0.0)
        col = Collector(WindowConfig(batch=64, deadline=0.002))
        c = OverloadController(ocfg, metrics=mets)
        t0 = now()
        r = c.run(d, col, diurnal, chunk=64)
        dt = now() - t0
        return mets.summary(), r, c, col, dt

    # best-of-2 per mode amortizes the one-time compile into the discard
    runs = {adapt: min((deadline_run(adapt) for _ in range(2)),
                       key=lambda r: r[-1])
            for adapt in (False, True)}
    for adapt, mode in ((False, "deadline_static"), (True, "deadline_adapt")):
        s, rep, _, _, dt = runs[adapt]
        # virtual-time latencies are not comparable to the wall rows;
        # report wall goodput/s and leave the latency columns zero
        rows.append(("overload", "diurnal", 0.0, mode,
                     round(rep.goodput / dt), 0.0, 0.0, s["windows"],
                     round(s["mean_occupancy"]), s["coalesced"]))
    s_st, rep_st, _, _, _ = runs[False]
    s_ad, rep_ad, ctl_ad, col_ad, _ = runs[True]
    assert s_ad["deadline_updates"] >= 1, "controller never retuned"
    assert rep_ad.goodput >= rep_st.goodput, \
        "adaptive deadline lost goodput vs the static baseline"
    summary["deadline"] = {
        "updates": s_ad["deadline_updates"],
        "final": col_ad.deadline,
        "trajectory": [list(p) for p in ctl_ad.deadline_controller.trajectory],
        "goodput_adapt": rep_ad.goodput, "goodput_static": rep_st.goodput,
        "occupancy_gain": round(s_ad["mean_occupancy"]
                                / max(s_st["mean_occupancy"], 1e-9), 3),
        "windows_adapt": s_ad["windows"], "windows_static": s_st["windows"]}
    print(f"[pipeline] overload deadline: {s_ad['deadline_updates']} "
          f"retunes to {col_ad.deadline:.4g}s, "
          f"{summary['deadline']['occupancy_gain']:.2f}x occupancy vs "
          f"static ({s_ad['windows']} vs {s_st['windows']} windows)")
    return rows, summary


def one_scenario(process: str, theta: float, n_keys: int, batch: int,
                 n_arrivals: int, backend=None):
    idx, keys, ycfg = make_index(n_keys, backend=backend)
    ycfg = dataclasses.replace(ycfg, theta=theta, write_ratio=0.0)
    acfg = ArrivalConfig(process=process, n_arrivals=n_arrivals)
    stream = make_arrivals(acfg, ycfg, keys)
    # every replay gets its own copy of the same starting state so modes
    # stay comparable even if the workload is ever given a write mix
    fresh = lambda: jax.tree.map(jnp.copy, idx)
    # warm the one compiled executable (both modes share it: same shape,
    # same config) before any timed replay
    warm = dataclasses.replace(acfg, n_arrivals=2 * batch, seed=acfg.seed + 1)
    replay(fresh(), make_arrivals(warm, ycfg, keys),
           WindowConfig(batch=batch), depth=1, bulk=True)
    # best-of-2 per mode: wall-clock replay on a shared host is noisy and
    # the best run is the one that measures the policy, not the neighbours
    best = lambda runs: max(runs, key=lambda s: s["qps"])
    # naive keeps the scalar offer loop: it IS the pre-pipeline baseline
    naive = best([replay(fresh(), stream,
                         WindowConfig(batch=batch, coalesce=False), depth=0,
                         bulk=False)
                  for _ in range(2)])
    piped = best([replay(fresh(), stream,
                         WindowConfig(batch=batch, coalesce=True), depth=1,
                         bulk=True)
                  for _ in range(2)])
    return naive, piped


def main(n_keys=1 << 18, batch=8192, n_arrivals=1 << 16,
         processes=("poisson", "bursty", "hotkey"), thetas=(0.0, 0.9)):
    rows = []
    speedups = {}
    for process in processes:
        for theta in thetas:
            naive, piped = one_scenario(process, theta, n_keys, batch,
                                        n_arrivals)
            for mode, s in (("naive", naive), ("pipelined", piped)):
                rows.append(("pipeline", process, theta, mode,
                             round(s["qps"]), round(s["p50_ms"], 3),
                             round(s["p99_ms"], 3), s["windows"],
                             round(s["mean_occupancy"]), s["coalesced"]))
            speedup = piped["qps"] / naive["qps"]
            speedups[f"{process}_theta{theta}"] = round(speedup, 3)
            print(f"[pipeline] {process} theta={theta}: "
                  f"{speedup:.2f}x qps over naive")
    vals = list(speedups.values())
    geomean = round(float(np.prod(vals)) ** (1.0 / len(vals)), 3)
    print(f"[pipeline] geomean speedup over naive: {geomean:.2f}x "
          f"(batch {batch})")
    admission_rows, admission_speedup = admission_bench(
        batch, n_arrivals, n_keys)
    rows += admission_rows
    durability_rows, durability_tax = durability_bench(
        n_keys, batch, n_arrivals)
    rows += durability_rows
    overload_rows, overload_summary = overload_bench()
    rows += overload_rows
    return emit(rows, ("fig", "process", "theta", "mode", "qps", "p50_ms",
                       "p99_ms", "windows", "occupancy", "coalesced"),
                fig="pipeline",
                config={"n_keys": n_keys, "batch": batch,
                        "n_arrivals": n_arrivals, "depth": 1,
                        "write_ratio": 0.0, "speedup": speedups,
                        "speedup_geomean": geomean,
                        "admission_speedup": admission_speedup,
                        "durability_tax": durability_tax,
                        "overload": overload_summary})


if __name__ == "__main__":
    use_compile_cache()
    main()
