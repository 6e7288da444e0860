"""Share of the bucketed batches' rows that were padding, %."""

from bench.metrics import readers


def read(ctx):
    return readers.pad_share(ctx)
