"""Range-pipeline figure: windowed fused RANGE serving vs per-op replay.

Saturation replay of YCSB-E-style scan streams (``ArrivalConfig.range_frac``
turns point arrivals into RANGE(lo, hi) scans whose starts follow the same
zipf/hot-set skew) through two serving policies over the SAME index and
``max_span`` budget:

  naive      per-op replay: every RANGE arrival is its own ``range_agg``
             launch (batch 1, device sync per query) — the pre-tier
             driver loop a caller without the pipeline would write.
  windowed   the range serving tier (DESIGN.md §9): arrivals collect into
             windows (exact-pair coalescing), dispatch as ONE fused
             launch per window, depth-1 overlapped — and the whole
             replay runs from a single compiled range execute
             (``range_trace_count`` delta is asserted, not assumed).

Scenarios: a uniform scan mix (coalescing is rare — the win is batching)
and a hot-spot scan mix with a fixed span (hot starts → exact duplicate
ranges → coalescing packs many arrivals per executed slot, the YCSB-E
analogue of the hotkey SEARCH win).  A ``mixed`` block replays a
0.3-range/0.2-write stream through the same dispatcher to record the
integrated path (ranges + point execute + rebuilds in one run); it has
no naive twin — the naive loop cannot interleave per-op ranges with
batched writes without inventing a third policy.

``BENCH_range.json`` carries the rows plus per-scenario speedups and the
windowed run's coalesce/span metrics for the perf trajectory.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import default_backend, emit, make_index
from repro import data as data_mod
from repro.analysis.runtime import trace_guard
from repro.core import RANGE, range_agg
from repro.pipeline import (ArrivalConfig, Collector, Dispatcher,
                            PipelineMetrics, WindowConfig, make_arrivals,
                            range_trace_count)
from repro.compile_cache import use_compile_cache


MAX_SPAN = 2048


def scan_stream(acfg: ArrivalConfig, ycfg, keys):
    stream = make_arrivals(acfg, ycfg, keys)
    assert stream.keys2 is not None
    return stream


def naive_replay(idx, stream):
    """One ``range_agg`` launch per RANGE arrival, device-synced."""
    lo1 = jnp.zeros(1, stream.keys.dtype)
    n = 0
    t0 = time.perf_counter()
    for i in range(len(stream)):
        if stream.ops[i] != RANGE:
            continue
        cnt, sm = range_agg(idx, lo1 + int(stream.keys[i]),
                            lo1 + int(stream.keys2[i]), MAX_SPAN)
        n += 1
    jax.block_until_ready(cnt)
    dt = time.perf_counter() - t0
    return {"qps": n / dt, "p50_ms": 0.0, "p99_ms": 0.0, "windows": n,
            "mean_occupancy": 1, "coalesced": 0}


def windowed_replay(idx, stream, batch: int):
    mets = PipelineMetrics()
    col = Collector(WindowConfig(batch=batch))
    disp = Dispatcher(jax.tree.map(jnp.copy, idx), depth=1, metrics=mets,
                      max_span=MAX_SPAN)
    now = time.perf_counter
    mets.start(now())
    disp.run(stream, collector=col, chunk=batch, clock=now)
    mets.stop(now())
    return mets.summary()


def main(n_keys=1 << 15, batch=256, n_arrivals=4096):
    idx, keys, ycfg = make_index(n_keys)
    scenarios = {
        # uniform starts, variable spans: no sharing, the win is batching
        "uniform": ArrivalConfig(n_arrivals=n_arrivals, range_frac=1.0,
                                 span_min=1, span_max=256, seed=2),
        # hot starts + fixed span: exact duplicate ranges coalesce
        "hotscan": ArrivalConfig(process="hotkey", rate=1e4,
                                 n_arrivals=n_arrivals, hot_keys=8,
                                 hot_frac=0.7, range_frac=1.0,
                                 span_min=64, span_max=64, seed=2),
    }
    rows, speedups, windowed_stats = [], {}, {}
    for name, acfg in scenarios.items():
        stream = scan_stream(acfg, ycfg, keys)
        # warm both compiled paths outside the timed region
        naive_replay(idx, scan_stream(
            ArrivalConfig(n_arrivals=8, range_frac=1.0, seed=9), ycfg, keys))
        windowed_replay(idx, scan_stream(
            ArrivalConfig(n_arrivals=2 * batch, range_frac=1.0, seed=9),
            ycfg, keys), batch)
        base = range_trace_count()
        best = lambda runs: max(runs, key=lambda s: s["qps"])
        naive = best([naive_replay(idx, stream) for _ in range(2)])
        piped = best([windowed_replay(idx, stream, batch) for _ in range(2)])
        trace_guard("pipeline.ranges").expect(
            base, 0, "timed replays after warmup")
        for mode, s in (("naive", naive), ("windowed", piped)):
            rows.append(("range", name, mode, round(s["qps"]),
                         round(s["p50_ms"], 3), round(s["p99_ms"], 3),
                         s["windows"], round(s["mean_occupancy"]),
                         s.get("range_slots", 0),
                         s.get("range_coalesce_hits", 0)))
        speedups[name] = round(piped["qps"] / naive["qps"], 3)
        windowed_stats[name] = {
            "range_admitted": piped["range_admitted"],
            "range_slots": piped["range_slots"],
            "range_coalesce_hits": piped["range_coalesce_hits"]}
        print(f"[range] {name}: windowed {piped['qps']:,.0f} ranges/s vs "
              f"naive {naive['qps']:,.0f} ({speedups[name]:.1f}x, "
              f"{piped['range_coalesce_hits']} coalesce hits)")
    # integrated path: scans + point reads + writes through one dispatcher
    mixed = scan_stream(
        ArrivalConfig(n_arrivals=n_arrivals, range_frac=0.3, span_min=1,
                      span_max=128, seed=4),
        data_mod.YCSBConfig(n_keys=n_keys, write_ratio=0.2, theta=0.6),
        keys)
    s = windowed_replay(idx, mixed, batch)
    rows.append(("range", "mixed", "windowed", round(s["qps"]),
                 round(s["p50_ms"], 3), round(s["p99_ms"], 3), s["windows"],
                 round(s["mean_occupancy"]), s["range_slots"],
                 s["range_coalesce_hits"]))
    print(f"[range] mixed: {s['qps']:,.0f} arrivals/s, "
          f"{s['range_admitted']} ranges over {s['range_slots']} slots")
    vals = list(speedups.values())
    geomean = round(float(np.prod(vals)) ** (1.0 / len(vals)), 3)
    print(f"[range] geomean windowed/naive speedup: {geomean:.2f}x "
          f"(batch {batch}, max_span {MAX_SPAN})")
    return emit(rows, ("fig", "scenario", "mode", "qps", "p50_ms", "p99_ms",
                       "windows", "occupancy", "range_slots",
                       "coalesce_hits"),
                fig="range",
                config={"n_keys": n_keys, "batch": batch,
                        "n_arrivals": n_arrivals, "max_span": MAX_SPAN,
                        "depth": 1, "backend": default_backend(),
                        "speedup": speedups, "speedup_geomean": geomean,
                        "windowed": windowed_stats})


if __name__ == "__main__":
    use_compile_cache()
    main()
