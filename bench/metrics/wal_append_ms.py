"""wal_append_ms: host time per WAL append (``pi.wal.append``: encode
and write) less the fsync inside it (``pi.wal.fsync``), read from the
program's span table over the ``pi.wal.append`` count."""

from bench.scopes import program_span_table, span_count, span_total


def read(run):
    t = program_span_table()
    n = span_count(t or {}, "pi.wal.append")
    if not n:
        return None
    return (span_total(t, "pi.wal.append")
            - span_total(t, "pi.wal.fsync")) / n * 1e3
