"""step_ms: device time of one run of the point step (``_step_single``:
execute and rebuild-if-due), mean over the runs in the traced window."""

PROGRAM = "_step_single"


def read(run):
    r = run.reduction
    if r is None:
        return None
    t, n = r.program(PROGRAM, r.devices[0])
    return t / n * 1e3 if n else None
