"""ranges_ms: device time of one run of the range aggregate
(``execute_ranges``), mean over the runs in the traced window."""

PROGRAM = "execute_ranges"


def read(run):
    r = run.reduction
    if r is None:
        return None
    t, n = r.program(PROGRAM, r.devices[0])
    return t / n * 1e3 if n else None
