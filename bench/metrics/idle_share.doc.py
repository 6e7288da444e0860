"""Share of the traced window in which no program ran on the device, %."""

from bench.metrics import readers


def read(ctx):
    return readers.idle_share(ctx)
