"""Arithmetic the per-layer readers share."""

from __future__ import annotations

from typing import Any, Dict, Optional

from bench import costs, peaks, stats


def delta(ctx: Dict[str, Any], key: str, traced: bool = False) -> Optional[float]:
    pair = ctx["trace_counters" if traced else "counters"]
    if pair is None or pair[0] is None or key not in pair[0]:
        return None
    return float(pair[1][key] - pair[0][key])


def p99(values) -> Optional[float]:
    return stats.percentile(values, 99) if values else None


def rows_per_batch(ctx: Dict[str, Any]) -> Optional[float]:
    rows, batches = delta(ctx, "served_rows"), delta(ctx, "batches_run")
    if not batches:
        return None
    return rows / batches


def pad_share(ctx: Dict[str, Any]) -> Optional[float]:
    pad, rows = delta(ctx, "padded_rows"), delta(ctx, "served_rows")
    if pad is None or not (pad + rows):
        return None
    return 100.0 * pad / (pad + rows)


def idle_share(ctx: Dict[str, Any]) -> Optional[float]:
    tr = ctx.get("trace")
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def chip_peaks(ctx):
    return peaks.peaks(ctx["device_kind"])


def roofline_share(ctx: Dict[str, Any], flops: float, nbytes: float,
                   seconds: float) -> Optional[float]:
    """max(flops / peak FLOP/s, bytes / peak bytes/s) over the measured
    time, in %: the least time the chip could take over the time taken."""
    if seconds <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    pk = chip_peaks(ctx)
    least = max(flops / pk["flops_bf16"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def fused_transform_roofline(ctx: Dict[str, Any]) -> Optional[float]:
    """The fused RP+EASI serve kernel: the served rows of the traced
    window over the kernel's device time there."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    events = tr.kernel_events("fused_transform")
    rows = delta(ctx, "served_rows", traced=True)
    if not events or not rows:
        return None
    c = ctx["config"]
    m, p, n = c["m"], c["p"], c["n"]
    return roofline_share(ctx, costs.dr_transform_flops(rows, m, p, n),
                          costs.dr_transform_bytes(rows, len(events), m, p, n),
                          tr.kernel_s("fused_transform"))


def easi_apply_roofline(ctx: Dict[str, Any]) -> Optional[float]:
    """The fused EASI update kernel: its calls' block rows over its device
    time in the traced window."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    events = tr.kernel_events("easi_apply")
    folded = delta(ctx, "folded_rows", traced=True)
    if not events or not folded:
        return None
    c = ctx["config"]
    rows = folded / len(events)
    e = c["easi"]
    flops = len(events) * costs.easi_update_flops(
        rows, c["n"], c["p"], e["second_order"], e["higher_order"])
    nbytes = len(events) * costs.easi_update_bytes(rows, c["n"], c["p"])
    return roofline_share(ctx, flops, nbytes, tr.kernel_s("easi_apply"))


def dr_mfu(ctx: Dict[str, Any]) -> Optional[float]:
    """Model FLOPs of the rows served (and folded into updates) in the
    traced window, over the traced seconds at the chip's bf16 peak."""
    tr = ctx.get("trace")
    rows = delta(ctx, "served_rows", traced=True)
    if tr is None or not tr.modules or not rows or tr.window_s <= 0:
        return None
    c = ctx["config"]
    m, p, n = c["m"], c["p"], c["n"]
    flops = costs.dr_transform_flops(rows, m, p, n)
    folded = delta(ctx, "folded_rows", traced=True)
    if folded:
        flops += folded * costs.dr_update_row_flops(m, p, n)
    return 100.0 * flops / (tr.window_s * chip_peaks(ctx)["flops_bf16"])


def lm_runs(ctx: Dict[str, Any]):
    """(decode runs, prefill runs) of the traced window.  The LM step
    programs run as `jit_fn(<id>)`; decode is the one run most often (once
    per output token), the others are the prefills (one per prompt
    length)."""
    tr = ctx.get("trace")
    if tr is None:
        return None, None
    groups = tr.module_runs("jit_fn(")
    if not groups:
        return None, None
    decode = max(groups, key=lambda k: len(groups[k]))
    prefill = [e for k, evs in groups.items() if k != decode for e in evs]
    return groups[decode], prefill
