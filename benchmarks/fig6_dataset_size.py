"""Fig. 6: query throughput vs dataset size (search-only and insert-only).

Paper claim: throughput decreases moderately with dataset size (cache
residency), insert < search, then flattens for large datasets.
"""
import dataclasses

from benchmarks.common import emit, make_index, run_query_stream
from repro.compile_cache import use_compile_cache


def main(sizes=(1 << 14, 1 << 15, 1 << 16, 1 << 17, 1 << 18),
         n_batches=8):
    rows = []
    for n in sizes:
        idx, keys, ycfg = make_index(n)
        qps_s, idx = run_query_stream(idx, ycfg, keys, n_batches)
        idx2, keys2, ycfg2 = make_index(n, seed=1)
        ycfg2 = dataclasses.replace(ycfg2, write_ratio=1.0)
        qps_i, _ = run_query_stream(idx2, ycfg2, keys2, n_batches)
        rows.append(("fig6", n, round(qps_s), round(qps_i)))
    return emit(rows, ("fig", "n_keys", "search_qps", "insert_qps"))


if __name__ == "__main__":
    use_compile_cache()
    main()
