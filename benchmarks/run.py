"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # all, small sizes
  PYTHONPATH=src python -m benchmarks.run fig6 fig8  # subset
"""
import sys
import time

from benchmarks import (fig6_dataset_size, fig7_batch_size, fig8_scalability,
                        fig9_mixed, fig10_skew, fig14_range, fig15_breakdown,
                        fig_pipeline, fig_range_pipeline, fig_rebuild,
                        model_check)
from repro.compile_cache import use_compile_cache

# every figure's emit() also writes a machine-readable BENCH_<fig>.json
# (rows + backend + scenario config) into BENCH_DIR (default: cwd) — that
# file is the per-PR perf trajectory record
ALL = {
    "fig6": fig6_dataset_size.main,
    "fig7": fig7_batch_size.main,
    "fig8": fig8_scalability.main,
    "fig9": fig9_mixed.main,
    "fig10": fig10_skew.main,
    "fig14": fig14_range.main,
    "fig15": fig15_breakdown.main,
    "pipeline": fig_pipeline.main,
    "range": fig_range_pipeline.main,
    "rebuild": fig_rebuild.main,
    "model": model_check.main,
}


def main():
    which = sys.argv[1:] or list(ALL)
    for name in which:
        print(f"### {name}")
        t0 = time.time()
        ALL[name]()
        print(f"### {name} done in {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    use_compile_cache()
    main()
