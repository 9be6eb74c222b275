"""Run a cell's control on the chip: the program with its WAL unsynced.

    python3 -m bench.control --workload <cell> --seeds 1,2,3 --seconds <s>

The control breaks the guarantee the configurations state (an
acknowledged operation is durable) by serving with ``fsync="off"``; the
check must call every such run not correct.  Each seed runs in this one
process, one after another.  Prints one JSON line per seed: the seed,
``correct`` and the compared numbers with their limits.  The benchmark's
own runs never run this.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness, ycsb

    bench = harness.load_benchmark()
    cell, entry = harness.find_cell(bench, args.workload)
    cfg = harness.load_config(entry)
    cfg["durability"]["fsync"] = "off"
    mix = ycsb.load_traffic(cell["traffic"])
    try:
        devices = harness.chip_devices(cell["chips"])
    except harness.BenchError as e:
        print(f"bench.control: {args.workload}: {e}", file=sys.stderr)
        return 2
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run_cell(cfg, mix, seed, args.seconds, False, devices,
                             t_start=t_start)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "checks": r["checks"]}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
