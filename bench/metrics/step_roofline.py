"""step_roofline: the least bytes a window's point work moves
(``roofline.point_bytes``) over HBM bandwidth, as a share of
``step_ms``."""

from bench import roofline


def read(run):
    r = run.reduction
    if r is None or "point_bytes" not in run.work:
        return None
    t, n = r.program("_step_single", r.devices[0])
    if not n:
        return None
    t /= n
    bw = roofline.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * run.work["point_bytes"] / bw / t
