"""Fig. 15: per-optimization breakdown.

Paper optimizations → PI-JAX analogues:
  SIMD entries (M-key vector compare)  → fanout/entry width (F=2 ≈ scalar
                                         binary descent, F=8 ≈ VPU entry)
  NUMA-aware partitioning              → shard_map index, one shard per
                                         device (8 virtual CPU devices)
  group query processing + prefetch    → batch size (64 → 8192): sorted
                                         batches amortize descent locality
The cumulative ladder mirrors the paper's bars.
"""
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from benchmarks.common import bench_backends, emit, make_index, \
    run_query_stream
from repro import data as data_mod
from repro.compile_cache import use_compile_cache
from repro.core import PIConfig, build_sharded, make_sharded_executor

CPU_SHARDS = 8  # virtual CPU devices the sharded phase runs on off-chip


def numa_qps(n_keys: int, devices) -> float:
    """qps of 8 YCSB batches through the sharded executor over a
    ``("data",)`` mesh of ``devices`` (after 2 warm-up batches)."""
    S, N = len(devices), n_keys
    cfg = PIConfig(capacity=2 * N // S, pending_capacity=max(1024, N // S // 4),
                   fanout=8)
    ycfg = data_mod.YCSBConfig(n_keys=N, batch=8192)
    keys, vals = data_mod.ycsb_dataset(ycfg)
    mesh = Mesh(np.array(devices), ("data",))
    state = build_sharded(cfg, S, keys, vals, mesh=mesh)
    run, _ = make_sharded_executor(mesh, cfg, 8192 // S)
    batches = [tuple(jnp.asarray(a) for a in data_mod.ycsb_batch(ycfg, keys, s))
               for s in range(10)]
    shards, fences = state.shards, state.fences
    for b in batches[:2]:
        shards, f, _, _, _ = run(shards, fences, *b)
    jax.block_until_ready(f)
    t0 = time.perf_counter()
    for b in batches[2:]:
        shards, f, _, _, _ = run(shards, fences, *b)
    jax.block_until_ready(f)
    return 8192 * 8 / (time.perf_counter() - t0)


def _numa_row(n_keys: int):
    """On a TPU host the sharded phase runs here, over every chip (a
    child could not reach a chip this process holds).  Off-chip it needs
    virtual CPU devices, which only a fresh process can have."""
    if jax.default_backend() == "tpu":
        devices = jax.devices()
        return f"+numa_{len(devices)}shards", numa_qps(n_keys, devices)
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={CPU_SHARDS}",
               PYTHONPATH="src")
    code = ("import json, jax; from benchmarks.fig15_breakdown import "
            f"numa_qps; print(json.dumps(numa_qps({n_keys}, jax.devices())))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"fig15 sharded phase failed:\n{out.stderr[-2000:]}")
    return (f"+numa_{CPU_SHARDS}shards",
            json.loads(out.stdout.strip().splitlines()[-1]))


def main(n_keys=1 << 16, n_batches=8):
    rows = []
    # 1) baseline: narrow entries (scalar-compare analogue), small batches
    idx, keys, ycfg = make_index(n_keys, fanout=2)
    small = dataclasses.replace(ycfg, batch=64)
    qps, _ = run_query_stream(idx, small, keys, n_batches * 4)
    rows.append(("fig15", "base_F2_b64", round(qps)))
    # 2) + batching/group processing (paper §4.3.4), still narrow entries
    qps, _ = run_query_stream(idx, ycfg, keys, n_batches)
    rows.append(("fig15", "+batch_8192_F2", round(qps)))
    # 3) + SIMD-width entries (one 8-key vector compare per level)
    idx, keys, ycfg = make_index(n_keys, fanout=8)
    qps, _ = run_query_stream(idx, ycfg, keys, n_batches)
    rows.append(("fig15", "+simd_F8", round(qps)))
    # 4) + NUMA sharding (one shard per device)
    label, qps = _numa_row(n_keys)
    rows.append(("fig15", label, round(qps)))
    # 5) engine backends side by side: the same F=8 workload routed through
    #    each SearchEngine backend bench_backends offers on this host
    for backend in bench_backends():
        idx, keys, ycfg = make_index(n_keys, fanout=8, backend=backend)
        qps, _ = run_query_stream(idx, ycfg, keys, n_batches)
        rows.append(("fig15", f"engine_{backend}", round(qps)))
    return emit(rows, ("fig", "config", "qps"))


if __name__ == "__main__":
    use_compile_cache()
    main()
