"""The least bytes one decode step must read (every weight outside the
routed experts, the routed held experts it ran, from the program's
counter, and the latent cache at the window's mean context) over its mean
device time, at the chip's HBM peak, %: a lower bound of the step's
share of the bandwidth roofline."""

from bench import costs_mla_moe as costs
from bench.metrics import readers


def read(ctx):
    decode, _ = readers.lm_runs(ctx)
    contexts = ctx["record"].get("decode_context")
    steps = readers.delta(ctx, "decode_steps")
    if not decode or not contexts or not steps:
        return None
    step_s = sum(e.dur_ns for e in decode) / len(decode) * 1e-9
    nbytes = costs.decode_bytes(ctx["config"], sum(contexts) / len(contexts),
                                readers.delta(ctx, "pairs_decode") / steps)
    return readers.roofline_share(ctx, 0.0, nbytes, step_s)
