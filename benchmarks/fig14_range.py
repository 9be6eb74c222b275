"""Fig. 14: range-query throughput vs granularity (results per query).

Paper claim: throughput decreases roughly linearly in granularity;
smaller datasets degrade more slowly (cache reuse).
"""
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, make_index
from repro import data as data_mod
from repro.core import range_agg
from repro.compile_cache import use_compile_cache


def main(sizes=(1 << 14, 1 << 16), grans=(1, 10, 100, 1000),
         batch=2048, n_batches=4):
    rows = []
    for n in sizes:
        idx, keys, ycfg = make_index(n)
        ycfg = data_mod.YCSBConfig(n_keys=n, batch=batch)
        for g in grans:
            span = max(1024, 2 * g)
            lo, hi = data_mod.range_batch(ycfg, keys, 0, g)
            lo, hi = jnp.asarray(lo), jnp.asarray(hi)
            cnt, sm = range_agg(idx, lo, hi, span)   # warmup/compile
            jax.block_until_ready(cnt)
            t0 = time.perf_counter()
            for step in range(n_batches):
                lo, hi = data_mod.range_batch(ycfg, keys, step + 1, g)
                cnt, sm = range_agg(idx, jnp.asarray(lo), jnp.asarray(hi),
                                    span)
            jax.block_until_ready(cnt)
            dt = time.perf_counter() - t0
            rows.append(("fig14", n, g, round(batch * n_batches / dt)))
    return emit(rows, ("fig", "n_keys", "granularity", "qps"))


if __name__ == "__main__":
    use_compile_cache()
    main()
