"""Fig. 8: throughput vs number of shards ('threads' = devices here).

Paper claim: near-linear scaling with threads (super-linear 1→4 from
cache effects).  NOTE: this container exposes ONE physical core, so
forced host devices cannot give real wall-clock speedup; we report both
wall-clock qps and per-shard load balance (the mechanism the paper's
scaling rests on).  Run on a real multi-core/TPU host for wall-clock
scaling.
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
from repro.core import PIConfig, build_sharded, make_sharded_executor
from repro import data as data_mod

S = {S}
N = {N}
cfg = PIConfig(capacity=max(1024, 2*N//S), pending_capacity=max(1024, N//S//4), fanout=8)
ycfg = data_mod.YCSBConfig(n_keys=N, batch=8192)
keys, vals = data_mod.ycsb_dataset(ycfg)
mesh = jax.make_mesh((S,), ("data",))
state = build_sharded(cfg, S, keys, vals, mesh=mesh)
run, cap = make_sharded_executor(mesh, cfg, 8192 // S)
batches = [tuple(jnp.asarray(a) for a in data_mod.ycsb_batch(ycfg, keys, s)) for s in range(10)]
shards, fences = state.shards, state.fences
for ops, k, v in batches[:2]:
    shards, f, vv, load, _ = run(shards, fences, ops, k, v)
jax.block_until_ready(f)
t0 = time.perf_counter()
loads = np.zeros(S)
for ops, k, v in batches[2:]:
    shards, f, vv, load, _ = run(shards, fences, ops, k, v)
    loads += np.asarray(load)
jax.block_until_ready(f)
dt = time.perf_counter() - t0
print(json.dumps({"qps": 8192*8/dt, "imbalance": float(loads.max()/max(loads.mean(),1e-9))}))
"""


def main(n_keys=1 << 16, shard_counts=(1, 2, 4, 8)):
    rows = []
    for s in shard_counts:
        env = dict(os.environ,
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={s}",
                   PYTHONPATH="src")
        out = subprocess.run(
            [sys.executable, "-c",
             SCRIPT.replace("{S}", str(s)).replace("{N}", str(n_keys))],
            capture_output=True, text=True, env=env, timeout=600)
        if out.returncode != 0:
            raise RuntimeError(f"fig8 at {s} shards failed:\n"
                               f"{out.stderr[-2000:]}")
        r = json.loads(out.stdout.strip().splitlines()[-1])
        rows.append(("fig8", s, round(r["qps"]), round(r["imbalance"], 3)))
    return emit(rows, ("fig", "shards", "qps", "load_imbalance"))


if __name__ == "__main__":
    use_compile_cache()
    main()
