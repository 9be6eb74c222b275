"""Tiny versions of the benchmark's cells, for runs on the CPU."""
import copy
import io
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))


def tiny(cell_name: str, shards: int = 1, **durability):
    """(cell, config, mix) of a cell cut to a size a test run holds:
    4096 records a shard, capacity 2^14, windows of 256, 512 clients.
    ``shards`` > 1 range-partitions it over that many devices."""
    from bench import harness, ycsb

    bench = harness.load_benchmark()
    cell, entry = harness.find_cell(bench, cell_name)
    cfg = copy.deepcopy(harness.load_config(entry))
    if shards > 1:
        cfg["shards"] = shards
    cfg["recordcount"] = 4096 * int(cfg.get("shards", 1))
    cfg["index"].update(capacity=1 << 14, pending_capacity=1 << 11)
    cfg["window"]["batch"] = 256
    cfg["durability"].update(durability)
    mix = dict(ycsb.load_traffic(cell["traffic"]), threadcount=512)
    return cell, cfg, mix


def run_tiny(cell_name: str, seed: int = 2**31 + 7, seconds: float = 1.0,
             shards: int = 1, **durability):
    """One tiny run on the CPU devices the process has; the result."""
    import jax
    from bench import harness

    cell, cfg, mix = tiny(cell_name, shards, **durability)
    devices = jax.devices()[:int(cfg.get("shards", 1))]
    bench = harness.load_benchmark()
    return harness.run_cell(
        cfg, mix, seed, seconds, False, devices,
        t_start=time.perf_counter(),
        metric_names=harness.metrics_for(bench, cell_name, False),
        out=io.StringIO())


@pytest.fixture
def tiny_run():
    return run_tiny
