"""Mean device time of one decode step in the traced window, ms."""

from bench.metrics import readers


def read(ctx):
    decode, _ = readers.lm_runs(ctx)
    if not decode:
        return None
    return sum(e.dur_ns for e in decode) / len(decode) * 1e-6
