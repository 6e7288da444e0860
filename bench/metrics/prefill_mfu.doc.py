"""Model FLOPs of the prefill runs in the traced window (matmuls, causal
attention at each run's prompt length, the held experts' pairs at the
window's pairs per token, the head) over their device time at the chip's
bf16 peak, %."""

from bench import costs_mla_moe as costs
from bench.metrics import doc_runs, readers


def read(ctx):
    runs = doc_runs.traced_prefills(ctx)
    ppt = doc_runs.pairs_per_token(ctx)
    if not runs or ppt is None:
        return None
    cfg = ctx["config"]
    flops = sum(costs.prefill_flops(cfg, 1, n, n * (n + 1) // 2, ppt * n)
                for n, _ in runs)
    seconds = sum(s for _, s in runs)
    return 100.0 * flops / (seconds * readers.chip_peaks(ctx)["flops_bf16"])
