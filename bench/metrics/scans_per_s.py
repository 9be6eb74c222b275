"""scans_per_s: scans (YCSB's SCAN, the program's RANGE) whose answers
came back inside the window, over the window's seconds (host clock)."""

from bench.ycsb import RANGE


def read(run):
    n = int((run.ops == RANGE).sum())
    return n / run.window_s if n else None
