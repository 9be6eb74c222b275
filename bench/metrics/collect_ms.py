"""collect_ms: host time per sealed window inside the collector, read
from the program's span table: ``pi.collect.offer`` + ``pi.collect.take``
(``pi.collect.seal`` runs inside them), the WAL append the seal calls
(``pi.wal.append``) left out, over the ``pi.collect.seal`` count.  The
inside twin of ``admit_ms``; the table covers the process (set-up's
warm-up window and the drain's are two of some thousands)."""

from bench.scopes import program_span_table, span_count, span_total


def read(run):
    t = program_span_table()
    n = span_count(t or {}, "pi.collect.seal")
    if not n:
        return None
    own = (span_total(t, "pi.collect.offer") + span_total(t, "pi.collect.take")
           - span_total(t, "pi.wal.append"))
    return own / n * 1e3
