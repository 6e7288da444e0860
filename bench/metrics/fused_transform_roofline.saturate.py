"""Roofline share of the fused RP+EASI serve kernel, %."""

from bench.metrics import readers


def read(ctx):
    return readers.fused_transform_roofline(ctx)
