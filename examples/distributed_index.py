"""Multi-shard PI index example: NUMA-style range partitioning, one shard
per device, skewed workload, fence rebalancing (self-adjusted threading).

  PYTHONPATH=src python examples/distributed_index.py

On a TPU host the shards are the chips JAX finds.  Elsewhere the script
asks the CPU platform for 8 virtual devices (``XLA_FLAGS``, set before
JAX loads; the flag shapes only the CPU platform, never the TPU).
"""
import os

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import data as data_mod
from repro.core import (PIConfig, build_sharded, collect_pairs,
                        load_imbalance, make_sharded_executor,
                        rebalance_from_load)


def main():
    devices = jax.devices()
    S, N = len(devices), 1 << 15
    print(f"{S} shards on {devices[0].platform} ({devices[0].device_kind})")
    cfg = PIConfig(capacity=2 * N, pending_capacity=N // 8, fanout=8)
    ycfg = data_mod.YCSBConfig(n_keys=N, batch=4096, theta=0.9)  # skewed!
    keys, vals = data_mod.ycsb_dataset(ycfg)
    mesh = Mesh(np.array(devices), ("data",))
    state = build_sharded(cfg, S, keys, vals, mesh=mesh)
    run, cap = make_sharded_executor(mesh, cfg, ycfg.batch // S,
                                     capacity_factor=8.0)

    shards, fences = state.shards, state.fences
    loads = np.zeros(S)
    for step in range(4):
        ops, k, v = (jnp.asarray(a) for a in
                     data_mod.ycsb_batch(ycfg, keys, step))
        shards, f, vv, load, _ = run(shards, fences, ops, k, v)
        loads += np.asarray(load)
    print(f"zipf(0.9) load per shard: {loads.astype(int).tolist()}")
    print(f"imbalance before rebalance: {load_imbalance(loads):.2f}x")

    fences2 = rebalance_from_load(np.asarray(fences), loads, smoothing=1.0,
                                  key_lo=int(keys.min()),
                                  key_hi=int(keys.max()))
    kk, vv2 = collect_pairs(dataclasses.replace(state, shards=shards))
    state2 = build_sharded(cfg, S, kk, vv2, fences=fences2, mesh=mesh)
    shards2, fences2 = state2.shards, state2.fences
    loads2 = np.zeros(S)
    for step in range(4, 8):
        ops, k, v = (jnp.asarray(a) for a in
                     data_mod.ycsb_batch(ycfg, keys, step))
        shards2, f, vv, load, _ = run(shards2, fences2, ops, k, v)
        loads2 += np.asarray(load)
    print(f"load after rebalance:       {loads2.astype(int).tolist()}")
    print(f"imbalance after rebalance:  {load_imbalance(loads2):.2f}x")


if __name__ == "__main__":
    main()
