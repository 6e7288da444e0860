"""DR requests as `dr_serve` sends them (host float32 rows through
`DeadlineScheduler.submit`, every answer copied to the host), in a closed
loop: `outstanding` requests are always in flight, and the next request
goes out as soon as an answer reaches the host.  What it measures is the
rows per second the service completes when it is never short of work.

One thread drives the loop: it fills the `outstanding` slots, then takes
the answers in the order the requests were sent (a flush resolves its
group in that order) and sends one request for each answer it takes.
Nothing is sent once the window has closed; the requests still in flight
are waited for, checked, and left out of the rate.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from bench import stats, traffic
from bench.drivers import Context, Window, dr_serve, persist_compiles, \
    sleep_until
from bench.drivers._dr import NAME

WAIT_S = dr_serve.WAIT_S


class Driver(dr_serve.Driver):

    def __init__(self, ctx: Context):
        if ctx.spec["arrival"] != "closed":
            raise ValueError("dr_saturate drives closed-loop ('closed') "
                             "traffic only")
        self.ctx = ctx
        self.spec = ctx.spec
        self.sys = None
        self.sched = None
        self.record: Dict[str, Any] = {}
        self._cursor = 0

    def setup(self) -> None:
        super().setup()
        persist_compiles(True)
        self._warm_groups()
        persist_compiles(False)

    def _warm_groups(self) -> None:
        """A flush of more rows than the largest bucket serves them in
        full batches and joins the outputs, a program per (full batches,
        last bucket).  Warm each that a group can reach: up to the
        queue's limit, or the rows of the `outstanding` largest requests
        if fewer."""
        svc = self.sys.svc
        step = svc.buckets.max_bucket
        top = np.sort(self.schedule.sizes["rows"])[-self.schedule.outstanding:]
        most = min(svc.batcher.max_queue, int(top.sum()))
        for full in range(1, (most - 1) // step + 1):
            for b in svc.buckets.buckets():
                n = full * step + b
                if n > svc.batcher.max_queue:
                    break
                t = self.sched.submit(NAME, self.sys.pool[:n])
                t.wait(WAIT_S)
                np.asarray(t.result())

    def run(self, window: Window) -> Dict[str, float]:
        rec = self._drive(self.schedule, window, keep=True)
        self.record = rec
        return {"rows_per_s": stats.rate(rec["rows_in_window"],
                                         window.seconds)}

    def _drive(self, sched: traffic.Schedule, window: Window,
               keep: bool) -> Dict[str, Any]:
        self._cursor = 0
        return self._closed_loop(sched, window, keep)

    def _closed_loop(self, sched: traffic.Schedule, window: Window,
                     keep: bool) -> Dict[str, Any]:
        from repro.serve import QueueFull

        pool = self.sys.pool
        flight: "collections.deque" = collections.deque()
        offs: List[int] = []
        rows: List[int] = []
        done: List[float] = []
        answers: List[Optional[np.ndarray]] = []
        errors: List[str] = []
        refused = 0
        t0 = window.open()
        sleep_until(t0)
        try:
            while True:
                now = time.perf_counter()
                while now < window.end and len(flight) < sched.outstanding:
                    i = len(rows)
                    off, r = self._payload(i, sched)
                    offs.append(off)
                    rows.append(r)
                    done.append(np.nan)
                    answers.append(None)
                    try:
                        with jax.profiler.TraceAnnotation("bench.submit"):
                            t = self.sched.submit(NAME, pool[off:off + r])
                    except QueueFull:
                        refused += 1
                        break
                    flight.append((i, t))
                if not flight:
                    if now >= window.end:
                        break
                    time.sleep(1e-3)    # every slot refused: let it drain
                    continue
                i, t = flight.popleft()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    ok = t.wait(max(0.0, window.end + WAIT_S
                                    - time.perf_counter()))
                if not ok:
                    errors.append(f"request {i}: no answer")
                    continue
                try:
                    with jax.profiler.TraceAnnotation("bench.to_host"):
                        y = np.asarray(t.result())
                except Exception as e:  # noqa: BLE001 — a failed request
                    errors.append(f"request {i}: {e!r}")
                    continue
                done[i] = time.perf_counter()
                if keep:
                    answers[i] = y
        finally:
            window.join()
        n = len(rows)
        rows_a = np.asarray(rows, np.int64)
        done_a = np.asarray(done)
        answered = ~np.isnan(done_a)
        return {"attempted": n, "failed": int(n - answered.sum()),
                "refused": refused, "errors": errors[:5],
                "rows_in_window": int(rows_a[done_a <= window.end].sum()),
                "answers": answers, "offsets": np.asarray(offs, np.int64),
                "rows": rows_a}
