"""One decode step's model FLOPs (matmuls, the absorbed latent attention
over the mean context of the window's decode steps, the held experts'
pairs per step from the program's counter, the head) over its mean device
time at the chip's bf16 peak, %."""

from bench import costs_mla_moe as costs
from bench.metrics import readers


def read(ctx):
    decode, _ = readers.lm_runs(ctx)
    contexts = ctx["record"].get("decode_context")
    steps = readers.delta(ctx, "decode_steps")
    if not decode or not contexts or not steps:
        return None
    step_s = sum(e.dur_ns for e in decode) / len(decode) * 1e-9
    flops = costs.decode_flops(ctx["config"], sum(contexts) / len(contexts),
                               readers.delta(ctx, "pairs_decode") / steps)
    return 100.0 * flops / (step_s * readers.chip_peaks(ctx)["flops_bf16"])
