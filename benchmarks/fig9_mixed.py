"""Fig. 9: throughput vs write ratio (mixed workloads).

Paper claim: throughput decreases slightly as the insert share grows
(longer storage-layer walks + rebuilds), no cliff.
"""
import dataclasses

from benchmarks.common import emit, make_index, run_query_stream
from repro.compile_cache import use_compile_cache


def main(n_keys=1 << 16, ratios=(0.0, 0.2, 0.4, 0.6, 0.8, 1.0),
         n_batches=8):
    rows = []
    for r in ratios:
        idx, keys, ycfg = make_index(n_keys, seed=2)
        ycfg = dataclasses.replace(ycfg, write_ratio=r)
        qps, _ = run_query_stream(idx, ycfg, keys, n_batches)
        rows.append(("fig9", r, round(qps)))
    return emit(rows, ("fig", "write_ratio", "qps"))


if __name__ == "__main__":
    use_compile_cache()
    main()
