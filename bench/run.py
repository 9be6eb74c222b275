"""Entry point: run one cell of ``BENCHMARK.json`` and print its result.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs the accelerator the cell asks for: with no TPU, or fewer chips than
the cell's ``chips``, it exits 2 and prints no result.  The last line of
stdout is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each compared number beside its limit).  JAX's compilation
cache is ``$JAX_COMPILATION_CACHE_DIR`` if set, else
``<checkout>/.jax_cache`` (``repro.compile_cache``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the program is not in this checkout ({src})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from bench import harness, ycsb

    bench = harness.load_benchmark()
    cell, entry = harness.find_cell(bench, args.workload)
    cfg = harness.load_config(entry)
    mix = ycsb.load_traffic(cell["traffic"])

    try:
        devices = harness.chip_devices(cell["chips"])
    except harness.BenchError as e:
        print(f"bench: {args.workload}: {e}", file=sys.stderr)
        return 2
    result = harness.run_cell(
        cfg, mix, args.seed, args.seconds, bool(args.trace),
        devices, t_start=T_START,
        metric_names=harness.metrics_for(bench, cell["name"],
                                         bool(args.trace)))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
