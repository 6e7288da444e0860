"""Model FLOPs of the traced window's served rows over its seconds at
the chip's bf16 peak, %."""

from bench.metrics import readers


def read(ctx):
    return readers.dr_mfu(ctx)
