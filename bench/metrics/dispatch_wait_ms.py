"""dispatch_wait_ms: host time per retired window blocked on the
device's answers (``pi.dispatch.wait``, the read of ``found`` in
``Dispatcher._retire``), read from the program's span table."""

from bench.scopes import program_span_table, span_count, span_total


def read(run):
    t = program_span_table()
    n = span_count(t or {}, "pi.dispatch.wait")
    if not n:
        return None
    return span_total(t, "pi.dispatch.wait") / n * 1e3
