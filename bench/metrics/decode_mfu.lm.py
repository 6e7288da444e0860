"""One decode step's model FLOPs (matmuls, attention over the mean
context of the window's decode steps, the head) over its mean device time
at the chip's bf16 peak, %."""

from bench import costs
from bench.metrics import readers


def read(ctx):
    decode, _ = readers.lm_runs(ctx)
    contexts = ctx["record"].get("decode_context")
    if not decode or not contexts:
        return None
    step_s = sum(e.dur_ns for e in decode) / len(decode) * 1e-9
    flops = costs.lm_decode_flops(ctx["config"],
                                  sum(contexts) / len(contexts))
    return 100.0 * flops / (step_s * readers.chip_peaks(ctx)["flops_bf16"])
