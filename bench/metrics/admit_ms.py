"""admit_ms: host time per window in the collector (``offer_many`` and
``take``), the WAL append it calls on seal left out."""


def read(run):
    if not run.windows:
        return None
    own = run.spans.get("admit", 0.0) - run.spans.get("wal", 0.0)
    return own / run.windows * 1e3
