"""XLA compilations inside the measured window (JAX's compile events)."""


def read(ctx):
    return float(ctx["window_compiles"])
