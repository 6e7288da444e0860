"""How late the load generator sent requests: the 99th percentile over
every request of the window of (prefill submitted - due), ms."""

from bench.metrics import readers


def read(ctx):
    return readers.p99(ctx["record"].get("late_ms"))
