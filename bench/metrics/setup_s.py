"""setup_s: process start to the first timed operation (host clock).

Covers the imports and reaching the chip, the data, the index build, the
WAL's initial snapshot, the warm-up, flushing dirty pages to disk and,
on a cold cache, compilation."""


def read(run):
    return run.setup_s
