"""Rows per device batch the engine ran in the window (served rows over
batches, from `DRService` counters)."""

from bench.metrics import readers


def read(ctx):
    return readers.rows_per_batch(ctx)
