"""Roofline share of the fused EASI update kernel, %."""

from bench.metrics import readers


def read(ctx):
    return readers.easi_apply_roofline(ctx)
