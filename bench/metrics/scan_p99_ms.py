"""scan_p99_ms: 99th percentile of issue -> answer latency over every
scan issued and answered inside the window (YCSB's [SCAN]
99thPercentileLatency); exact, not bucketed."""

import numpy as np

from bench.ycsb import RANGE


def read(run):
    lat = run.latencies[run.ops == RANGE]
    if not len(lat):
        return None
    return float(np.percentile(lat, 99)) * 1e3
