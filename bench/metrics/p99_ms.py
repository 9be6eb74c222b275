"""p99_ms: 99th percentile of issue -> answer latency over every
operation issued and answered inside the window; exact, not bucketed."""

import numpy as np


def read(run):
    if not len(run.latencies):
        return None
    return float(np.percentile(run.latencies, 99)) * 1e3
