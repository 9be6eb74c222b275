"""ranges_roofline: the least bytes a window's scans move
(``roofline.scan_bytes``) over HBM bandwidth, as a share of
``ranges_ms``."""

from bench import roofline


def read(run):
    r = run.reduction
    if r is None or "scan_bytes" not in run.work:
        return None
    t, n = r.program("execute_ranges", r.devices[0])
    if not n:
        return None
    t /= n
    bw = roofline.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * run.work["scan_bytes"] / bw / t
