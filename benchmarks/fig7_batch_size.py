"""Fig. 7: query throughput vs batch size for three dataset sizes.

Paper claim: throughput rises with batch size (sorted batches → better
locality + fewer per-batch fixed costs), more so for small datasets.
"""
import dataclasses

from benchmarks.common import emit, make_index, run_query_stream
from repro.compile_cache import use_compile_cache


def main(sizes=(1 << 14, 1 << 16, 1 << 18),
         batches=(2048, 4096, 8192, 16384, 32768), total=1 << 18):
    rows = []
    for n in sizes:
        for b in batches:
            idx, keys, ycfg = make_index(n)
            ycfg = dataclasses.replace(ycfg, batch=b)
            qps, _ = run_query_stream(idx, ycfg, keys,
                                      max(2, total // b))
            rows.append(("fig7", n, b, round(qps)))
    return emit(rows, ("fig", "n_keys", "batch", "qps"))


if __name__ == "__main__":
    use_compile_cache()
    main()
