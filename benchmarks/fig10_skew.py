"""Fig. 10/11: throughput vs zipf skew, with and without the
self-adjusted rebalancing (fence rebalancing = the paper's self-adjusted
threading analogue).

Paper claim: with self-adjustment, skew barely hurts (Fig. 10); without
it, the hot shard bottlenecks (Fig. 11).  We additionally report the
load imbalance, the mechanism behind the claim.
"""
import json
import os
import subprocess
import sys

from benchmarks.common import emit
from repro.compile_cache import use_compile_cache

SCRIPT = r"""
import json, time, numpy as np, jax, jax.numpy as jnp
import dataclasses
from repro.core import (PIConfig, build_sharded, make_sharded_executor,
                        collect_pairs, rebalance_from_load, load_imbalance)
from repro import data as data_mod

S, N = 8, {N}
theta, rebalance = {THETA}, {REB}
cfg = PIConfig(capacity=2*N, pending_capacity=max(1024, N//8), fanout=8)
ycfg = data_mod.YCSBConfig(n_keys=N, batch=8192, theta=theta)
keys, vals = data_mod.ycsb_dataset(ycfg)
mesh = jax.make_mesh((S,), ("data",))
state = build_sharded(cfg, S, keys, vals, mesh=mesh)
run, cap = make_sharded_executor(mesh, cfg, 8192 // S, capacity_factor=8.0)
mk = lambda s: tuple(jnp.asarray(a) for a in data_mod.ycsb_batch(ycfg, keys, s))
shards, fences = state.shards, state.fences
loads = np.zeros(S)
# observe + optionally rebalance
for s in range(3):
    shards, f, vv, load, _ = run(shards, fences, *mk(s))
    loads += np.asarray(load)
if rebalance:
    f2 = rebalance_from_load(np.asarray(fences), loads, smoothing=1.0,
                             key_lo=int(keys.min()), key_hi=int(keys.max()))
    kk, vvv = collect_pairs(dataclasses.replace(state, shards=shards))
    state = build_sharded(cfg, S, kk, vvv, fences=f2, mesh=mesh)
    shards, fences = state.shards, state.fences
for ops, k, v in [mk(10)]:
    shards, f, vv, load, _ = run(shards, fences, ops, k, v)
jax.block_until_ready(f)
t0 = time.perf_counter(); loads = np.zeros(S)
for s in range(11, 19):
    shards, f, vv, load, _ = run(shards, fences, *mk(s))
    loads += np.asarray(load)
jax.block_until_ready(f)
dt = time.perf_counter() - t0
print(json.dumps({"qps": 8192*8/dt, "imbalance": load_imbalance(loads)}))
"""


def main(n_keys=1 << 16, thetas=(0.0, 0.5, 0.9)):
    rows = []
    for reb in (True, False):
        for th in thetas:
            env = dict(os.environ,
                       XLA_FLAGS="--xla_force_host_platform_device_count=8",
                       PYTHONPATH="src")
            out = subprocess.run(
                [sys.executable, "-c",
                 SCRIPT.replace("{N}", str(n_keys)).replace("{THETA}", str(th)).replace("{REB}", str(reb))],
                capture_output=True, text=True, env=env, timeout=900)
            if out.returncode != 0:
                raise RuntimeError(
                    f"fig10 (rebalance={reb}, theta={th}) failed:\n"
                    f"{out.stderr[-2000:]}")
            r = json.loads(out.stdout.strip().splitlines()[-1])
            rows.append(("fig10", reb, th, round(r["qps"]),
                         round(r["imbalance"], 2)))
    return emit(rows, ("fig", "self_adjusted", "theta", "qps", "imbalance"))


if __name__ == "__main__":
    use_compile_cache()
    main()
