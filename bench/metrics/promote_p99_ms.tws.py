"""99th percentile over the window's promotes of the host time of
`DRService.promote()`, ms."""

from bench.metrics import readers


def read(ctx):
    return readers.p99(ctx["record"].get("promote_ms"))
