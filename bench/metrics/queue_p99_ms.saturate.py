"""99th percentile over the window's requests of the queue delay the
program's SLO cells record (submit to the start of its flush), ms."""

from bench.metrics import readers


def read(ctx):
    return readers.queue_percentile(ctx, 99)
