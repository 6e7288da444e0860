"""Latency accounting and spans for the serving engine.

Two latency distributions per (model name, bucket):

  queue_delay — submit → the start of the ticket's work (its group's
                flush start; an LM step's own start): the time it sat in
                the admission queue, what the deadline scheduler bounds;
  e2e         — submit → result resolved (queue delay + batch compute).

plus deadline counters: a ticket submitted with `max_delay_ms` is *met*
when its flush STARTS at or before its deadline and *missed* otherwise —
the deadline bounds the batching window (queue delay), not batch
compute, so a deadline-triggered flush that fires on time is met.

Spans: `SLOTracker.span(stage)` times one stage of the serving path
(`flush.dr`, `serve_and_update`, ...) into a per-stage distribution, and
marks it `repro.<stage>` on the host plane of any profiler trace, on the
same clock as the device's program runs.  Every XLA compile that fires on
a thread is charged (seconds and a count) to each span open on that
thread, so a flush that compiled says so.

`LatencyStats` keeps exact percentiles over a bounded sliding window of
recent samples, and a cumulative histogram that never forgets: bins
16 per octave from 1 µs, so the geometric middle of a bin is within 2.2%
of any sample in it.  `snapshot()` returns that histogram as plain
numbers; the difference of two snapshots (`snapshot_delta`) is the
distribution of everything recorded between them, and
`snapshot_percentile` reads a percentile from it.  All values are
milliseconds, read from the engine's injectable `Clock` — under a
`VirtualClock` the recorded latencies are exact, which is what makes the
tests deterministic.
"""

from __future__ import annotations

import collections
import math
import threading
from typing import Any, Dict, Hashable, List, Optional, Tuple

import jax

from repro.serve.clock import Clock, MonotonicClock

BINS_PER_OCTAVE = 16
_TOP_BIN = BINS_PER_OCTAVE * 40     # 2**40 µs (12.7 days): slower lands here
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def bin_of(ms: float) -> int:
    """Histogram bin of a latency: 0 below 1 µs, else bin b holds
    [2**((b-1)/16), 2**(b/16)) µs."""
    us = ms * 1e3
    if us < 1.0:
        return 0
    return min(_TOP_BIN, 1 + int(BINS_PER_OCTAVE * math.log2(us)))


def bin_value_ms(b: int) -> float:
    """What a sample in bin `b` reads as: the bin's geometric middle (0 for
    the bin below 1 µs)."""
    if b <= 0:
        return 0.0
    return 2.0 ** ((b - 0.5) / BINS_PER_OCTAVE) / 1e3


def snapshot_delta(before: Dict[str, Any], after: Dict[str, Any]
                   ) -> Dict[str, Any]:
    """What was recorded between two snapshots of one `LatencyStats`
    (`max_ms` is not windowed, so it is left out)."""
    bins = {b: n - before["bins"].get(b, 0)
            for b, n in after["bins"].items()}
    out = {k: after[k] - before[k] for k in after
           if k not in ("bins", "max_ms")}
    out["bins"] = {b: n for b, n in bins.items() if n}
    return out


def snapshot_percentile(snap: Dict[str, Any], p: float) -> Optional[float]:
    """Nearest-rank p-th percentile of a snapshot (or of a difference of
    two), ms; None when it holds no sample."""
    if not 0.0 <= p <= 100.0:
        raise ValueError(f"percentile {p} outside [0, 100]")
    n = sum(snap["bins"].values())
    if not n:
        return None
    rank = max(1, math.ceil(n * p / 100))
    seen = 0
    for b in sorted(snap["bins"]):
        seen += snap["bins"][b]
        if seen >= rank:
            return bin_value_ms(b)
    raise AssertionError("unreachable: rank <= count")


class LatencyStats:
    """Latency distribution: exact percentiles over a bounded window,
    a cumulative histogram and counters over everything ever recorded."""

    def __init__(self, window: int = 4096):
        if window < 1:
            raise ValueError("window must be >= 1")
        self._samples: "collections.deque[float]" = collections.deque(maxlen=window)  # guarded-by: _lock
        # one lock per stats object: record() runs on the scheduler loop
        # thread while metrics() readers iterate the window from another
        self._lock = threading.Lock()
        self._bins: Dict[int, int] = {}  # guarded-by: _lock
        self.count = 0  # guarded-by: _lock
        self.total_ms = 0.0  # guarded-by: _lock
        self.max_ms = 0.0  # guarded-by: _lock

    def record(self, ms: float) -> None:
        ms = float(ms)
        if ms < 0:
            raise ValueError(f"negative latency {ms} ms")
        b = bin_of(ms)
        with self._lock:
            self._samples.append(ms)
            self._bins[b] = self._bins.get(b, 0) + 1
            self.count += 1
            self.total_ms += ms
            self.max_ms = max(self.max_ms, ms)

    def _window(self) -> list:
        with self._lock:
            return list(self._samples)

    def percentile(self, p: float) -> Optional[float]:
        """Exact p-th percentile (nearest-rank) over the retained window;
        None when nothing has been recorded."""
        if not 0.0 <= p <= 100.0:
            raise ValueError(f"percentile {p} outside [0, 100]")
        s = sorted(self._window())
        if not s:
            return None
        rank = max(1, -(-len(s) * p // 100))  # ceil(len * p / 100), >= 1
        return s[int(rank) - 1]

    @property
    def mean_ms(self) -> Optional[float]:
        with self._lock:
            return self.total_ms / self.count if self.count else None

    def snapshot(self) -> Dict[str, Any]:
        """The cumulative histogram and counters as plain numbers:
        `bins` maps a bin (`bin_of`) to its count."""
        with self._lock:
            return {"count": self.count, "total_ms": self.total_ms,
                    "max_ms": self.max_ms, "bins": dict(self._bins)}

    def summary(self) -> Dict[str, Any]:
        return {
            "count": self.count,
            "mean_ms": self.mean_ms,
            "max_ms": self.max_ms if self.count else None,
            "p50_ms": self.percentile(50),
            "p95_ms": self.percentile(95),
            "p99_ms": self.percentile(99),
        }


class SpanStats(LatencyStats):
    """One stage's spans: their latencies, plus the XLA compiles that fired
    inside them (count and milliseconds)."""

    def __init__(self, window: int = 4096):
        super().__init__(window)
        self.compiles = 0  # guarded-by: _lock
        self.compile_ms = 0.0  # guarded-by: _lock

    def record_span(self, ms: float, compiles: int, compile_ms: float) -> None:
        self.record(ms)
        with self._lock:
            self.compiles += compiles
            self.compile_ms += compile_ms

    def snapshot(self) -> Dict[str, Any]:
        out = super().snapshot()
        with self._lock:
            out["compiles"] = self.compiles
            out["compile_ms"] = self.compile_ms
        return out


# ---- spans --------------------------------------------------------------------
# The spans open on each thread, innermost last: a compile that fires on a
# thread is charged to all of them.  The compile listener is process-wide
# and registered once, by the first tracker.
_open = threading.local()
_listener_lock = threading.Lock()
_listening = False  # guarded-by: _listener_lock


def _open_spans() -> List["_Span"]:
    spans = getattr(_open, "spans", None)
    if spans is None:
        spans = _open.spans = []
    return spans


def _on_duration(event: str, secs: float, **kw: Any) -> None:
    if event != _COMPILE_EVENT:
        return
    for s in getattr(_open, "spans", ()):
        s.compiles += 1
        s.compile_ms += secs * 1e3


def _listen_for_compiles() -> None:
    global _listening
    with _listener_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True


class _Span:
    """One open span: a profiler annotation around a clock-timed interval."""

    __slots__ = ("_tracker", "_stage", "_annotation", "_t0", "compiles",
                 "compile_ms")

    def __init__(self, tracker: "SLOTracker", stage: str):
        self._tracker = tracker
        self._stage = stage
        self.compiles = 0
        self.compile_ms = 0.0

    def __enter__(self) -> "_Span":
        self._annotation = jax.profiler.TraceAnnotation(f"repro.{self._stage}")
        self._annotation.__enter__()
        _open_spans().append(self)
        self._t0 = self._tracker.clock.now()
        return self

    def __exit__(self, *exc: Any) -> None:
        ms = max(0.0, self._tracker.clock.now() - self._t0)
        _open_spans().pop()
        self._annotation.__exit__(*exc)
        self._tracker.stage(self._stage).record_span(ms, self.compiles,
                                                     self.compile_ms)


class BucketSLO:
    """One (name, bucket) cell: the two distributions + deadline counters."""

    def __init__(self, window: int = 4096):
        self.queue_delay = LatencyStats(window)
        self.e2e = LatencyStats(window)
        self.deadline_met = 0
        self.deadline_missed = 0

    @property
    def miss_rate(self) -> Optional[float]:
        n = self.deadline_met + self.deadline_missed
        return self.deadline_missed / n if n else None

    def summary(self) -> Dict[str, Any]:
        return {
            "queue_delay": self.queue_delay.summary(),
            "e2e": self.e2e.summary(),
            "deadline_met": self.deadline_met,
            "deadline_missed": self.deadline_missed,
            "deadline_miss_rate": self.miss_rate,
        }


class SLOTracker:
    """All SLO cells of one engine, keyed (model name, bucket size), and
    the spans of its stages, keyed by stage name.

    `bucket` is the compiled batch shape the request's rows pad to (an
    int), or a string tag for non-DR traffic routed through the queue
    (LM "prefill"/"decode" steps).  `clock` times the spans (the
    engine's own clock).
    """

    def __init__(self, window: int = 4096, clock: Optional[Clock] = None):
        self._window = window
        self.clock: Clock = clock if clock is not None else MonotonicClock()
        self._cells: Dict[Tuple[str, Hashable], BucketSLO] = {}  # guarded-by: _lock
        self._stages: Dict[str, SpanStats] = {}  # guarded-by: _lock
        self._lock = threading.Lock()
        _listen_for_compiles()

    def cell(self, name: str, bucket: Hashable) -> BucketSLO:
        with self._lock:
            key = (name, bucket)
            c = self._cells.get(key)
            if c is None:
                c = self._cells[key] = BucketSLO(self._window)
            return c

    def stage(self, stage: str) -> SpanStats:
        with self._lock:
            s = self._stages.get(stage)
            if s is None:
                s = self._stages[stage] = SpanStats(self._window)
            return s

    def span(self, stage: str) -> _Span:
        """`with tracker.span("flush.dr"): ...` — time the block into the
        stage's distribution, charge it the compiles that fire inside, and
        mark it `repro.<stage>` in any profiler trace."""
        return _Span(self, stage)

    def record(self, name: str, bucket: Hashable, *,
               queue_delay_ms: float, e2e_ms: float,
               deadline_ok: Optional[bool]) -> None:
        """Record one served ticket; `deadline_ok` is None for tickets
        submitted without a deadline (demand-flushed traffic)."""
        c = self.cell(name, bucket)
        c.queue_delay.record(queue_delay_ms)
        c.e2e.record(e2e_ms)
        if deadline_ok is not None:
            with self._lock:        # int += races lose counts across threads
                if deadline_ok:
                    c.deadline_met += 1
                else:
                    c.deadline_missed += 1

    def deadline_counts(self) -> Tuple[int, int]:
        """(met, missed) summed over every cell."""
        with self._lock:
            cells = list(self._cells.values())
        met = sum(c.deadline_met for c in cells)
        missed = sum(c.deadline_missed for c in cells)
        return met, missed

    def report(self) -> Dict[str, Dict[Hashable, Dict[str, Any]]]:
        """{name: {bucket: summary}} — what `DRService.metrics()['slo']`
        surfaces."""
        with self._lock:
            items = list(self._cells.items())
        out: Dict[str, Dict[Hashable, Dict[str, Any]]] = {}
        for (name, bucket), cell in items:
            out.setdefault(name, {})[bucket] = cell.summary()
        return out

    def snapshot(self) -> Dict[str, Dict[Hashable, Dict[str, Any]]]:
        """{name: {bucket: {"queue_delay": snapshot, "e2e": snapshot}}}:
        the cumulative histograms of every cell."""
        with self._lock:
            items = list(self._cells.items())
        out: Dict[str, Dict[Hashable, Dict[str, Any]]] = {}
        for (name, bucket), cell in items:
            out.setdefault(name, {})[bucket] = {
                "queue_delay": cell.queue_delay.snapshot(),
                "e2e": cell.e2e.snapshot()}
        return out

    def stages(self) -> Dict[str, Dict[str, Any]]:
        """{stage: snapshot} of every stage a span has timed — what
        `DRService.metrics()['stages']` surfaces."""
        with self._lock:
            items = list(self._stages.items())
        return {stage: s.snapshot() for stage, s in items}
