"""The doc cell's traced LM step runs, with what each one computed.

Each prompt length has its own prefill program (`jit_fn(<id>)` in the
trace), so every run of one program has one length.  Which length is read
from the driver's set-up: `record["prefill_warm_ms"]` holds, per prompt
length, the time of one prefill run alone after its warm-up, and each
traced program takes the length whose time lies nearest (by ratio) its
runs' mean device time; the lengths of the set differ by 2x, so the match
is unambiguous.  The held experts' pairs of a traced run are its tokens
times the window's pairs per token (prefill), or the window's pairs per
decode step, from the program's counters: the counters reach the host per
request and do not line up with the trace's edges, the runs do."""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

from bench.metrics import readers


def traced_prefills(ctx: Dict[str, Any]) -> Optional[List[Tuple[int, float]]]:
    """[(prompt tokens, device seconds)] of each traced prefill run, or None."""
    tr = ctx.get("trace")
    warm = ctx["record"].get("prefill_warm_ms")
    if tr is None or not warm:
        return None
    groups = tr.module_runs("jit_fn(")
    if len(groups) < 2:
        return None
    decode = max(groups, key=lambda k: len(groups[k]))
    out = []
    for name, runs in groups.items():
        if name == decode:
            continue
        mean_ms = sum(e.dur_ns for e in runs) / len(runs) * 1e-6
        length = min(warm, key=lambda n: abs(math.log(warm[n] / mean_ms)))
        out.extend((int(length), e.dur_ns * 1e-9) for e in runs)
    return out or None


def pairs_per_token(ctx: Dict[str, Any]) -> Optional[float]:
    """Held-expert pairs per prompt token over the window."""
    tokens = readers.delta(ctx, "prefill_tokens")
    pairs = readers.delta(ctx, "pairs_prefill")
    return pairs / tokens if tokens and pairs is not None else None


def pairs_per_step(ctx: Dict[str, Any]) -> Optional[float]:
    """Held-expert pairs per decode step over the window."""
    steps = readers.delta(ctx, "decode_steps")
    pairs = readers.delta(ctx, "pairs_decode")
    return pairs / steps if steps and pairs is not None else None
