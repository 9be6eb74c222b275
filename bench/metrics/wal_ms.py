"""wal_ms: host time per window in ``Durability.on_seal`` (append and
fsync)."""


def read(run):
    if not run.windows or "wal" not in run.spans:
        return None
    return run.spans["wal"] / run.windows * 1e3
