"""Mean device time of one prefill run in the traced window, ms."""

from bench.metrics import readers


def read(ctx):
    _, prefill = readers.lm_runs(ctx)
    if not prefill:
        return None
    return sum(e.dur_ns for e in prefill) / len(prefill) * 1e-6
