"""Roofline share of the grouped-matmul kernel of the held experts (the
megablox `gmm` custom call) in the traced window: the pairs of the traced
prefill and decode runs (each run's tokens at the window's pairs per
token, or the window's pairs per decode step) against the kernel's device
time, %."""

from bench import costs_mla_moe as costs
from bench.metrics import doc_runs, readers

KERNEL = "gmm"


def read(ctx):
    tr = ctx.get("trace")
    decode, _ = readers.lm_runs(ctx)
    runs = doc_runs.traced_prefills(ctx) or []
    ppt, pps = doc_runs.pairs_per_token(ctx), doc_runs.pairs_per_step(ctx)
    if tr is None or not decode or ppt is None or pps is None:
        return None
    seconds = tr.kernel_s(KERNEL)
    dec = pps * len(decode)
    pairs = ppt * sum(n for n, _ in runs) + dec
    if not seconds or not pairs:
        return None
    cfg = ctx["config"]
    return readers.roofline_share(ctx, costs.gmm_flops(cfg, pairs),
                                  costs.gmm_bytes(cfg, pairs, dec), seconds)
