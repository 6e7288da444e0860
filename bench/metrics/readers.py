"""Arithmetic the per-layer readers share."""

from __future__ import annotations

import collections
import math
from typing import Any, Dict, Iterable, Optional, Tuple

from bench import costs, peaks, stats

# The program's latency histograms (`repro.serve.slo`): bin 0 holds samples
# under 1 us, bin b >= 1 holds [2**((b-1)/16), 2**(b/16)) us.  The program
# owns that format; the reduction of two snapshots to a percentile is the
# benchmark's own and stays here, so that a change to the program cannot
# change how its stages are read.  `test_bench_readers.py` checks that it
# reads as `repro.serve.slo.snapshot_percentile` does, which catches a
# change of the format.
BINS_PER_OCTAVE = 16


def delta(ctx: Dict[str, Any], key: str, traced: bool = False) -> Optional[float]:
    pair = ctx["trace_counters" if traced else "counters"]
    if pair is None or pair[0] is None or key not in pair[0]:
        return None
    return float(pair[1][key] - pair[0][key])


def p99(values) -> Optional[float]:
    return stats.percentile(values, 99) if values else None


# ---- the program's stages and SLO cells: cumulative histograms ------------

def _bins(snap: Dict[str, Any]) -> Dict[int, int]:
    """A snapshot's bins keyed by int (a snapshot read back from JSON has
    them as strings)."""
    return {int(b): int(n) for b, n in snap["bins"].items()}


def hist_delta(before: Optional[Dict[str, Any]],
               after: Dict[str, Any]) -> Dict[int, int]:
    """The bins of what was recorded between two snapshots of one
    histogram (`before` None: it was empty)."""
    old = _bins(before) if before else {}
    return {b: n - old.get(b, 0) for b, n in _bins(after).items()
            if n - old.get(b, 0)}


def bin_value_ms(b: int) -> float:
    """What a sample in bin `b` reads as: the bin's geometric middle."""
    if b <= 0:
        return 0.0
    return 2.0 ** ((b - 0.5) / BINS_PER_OCTAVE) / 1e3


def hist_percentile(bins: Dict[int, int], q: float) -> Optional[float]:
    """Nearest-rank q-th percentile (0..100) of histogram bins, ms; None
    when they hold no sample."""
    n = sum(bins.values())
    if not n:
        return None
    rank = max(1, math.ceil(n * q / 100))
    seen = 0
    for b in sorted(bins):
        seen += bins[b]
        if seen >= rank:
            return bin_value_ms(b)
    raise AssertionError("unreachable: rank <= count")


def _snapshots(ctx: Dict[str, Any],
               key: str) -> Optional[Tuple[Dict[str, Any], Dict[str, Any]]]:
    pair = ctx["counters"]
    if pair is None or pair[0] is None or pair[1] is None \
            or key not in pair[0] or key not in pair[1]:
        return None
    return pair[0][key], pair[1][key]


def stage_percentile(ctx: Dict[str, Any], stage: str,
                     q: float) -> Optional[float]:
    """q-th percentile of the host time of the program's stage `stage`
    (a span of `SLOTracker`) over the window, ms, from the counters' two
    snapshots of `stages`; None without them or without a span of that
    stage in the window."""
    pair = _snapshots(ctx, "stages")
    if pair is None or stage not in pair[1]:
        return None
    return hist_percentile(hist_delta(pair[0].get(stage), pair[1][stage]), q)


def queue_percentile(ctx: Dict[str, Any], q: float,
                     buckets: Optional[Iterable[str]] = None
                     ) -> Optional[float]:
    """q-th percentile of the queue delay (submit to the start of its
    work) over every request of the window, ms, from the counters' two
    snapshots of `slo`; `buckets` keeps the SLO cells whose bucket reads
    as one of them (as "decode").  None without the snapshots or without
    a request in the window."""
    pair = _snapshots(ctx, "slo")
    if pair is None:
        return None
    keep = None if buckets is None else set(buckets)
    bins: Dict[int, int] = collections.Counter()
    for name, cells in pair[1].items():
        for bucket, cell in cells.items():
            if keep is not None and str(bucket) not in keep:
                continue
            before = pair[0].get(name, {}).get(bucket)
            bins.update(hist_delta(before and before["queue_delay"],
                                   cell["queue_delay"]))
    return hist_percentile(bins, q)


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
