"""99th percentile over the window's DR flushes of the host time of the
program's stage `flush.dr` (coalesce, serve the rows, resolve), ms."""

from bench.metrics import readers


def read(ctx):
    return readers.stage_percentile(ctx, "flush.dr", 99)
