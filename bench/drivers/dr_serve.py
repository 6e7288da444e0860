"""DR requests through `DeadlineScheduler.submit` -> `DRService.flush` ->
the bucketed transform program, as clients send them: host float32 rows,
each answer copied back to the host.

Open loop ("poisson") only: one generator thread submits each request at
its due time; one client thread waits for the answers in order and copies
each to the host.  A request's latency runs from its due time to its
answer on the host.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from bench import stats, traffic
from bench.drivers import Comparison, Context, Window, persist_compiles, \
    sleep_until
from bench.drivers._dr import NAME, DRSystem, control_dtype, reference_pool, \
    served_out_check

WAIT_S = 60.0       # how long past the window an answer is waited for


class Driver:
    size_keys = ("rows",)

    def __init__(self, ctx: Context):
        if ctx.spec["arrival"] != "poisson":
            raise ValueError("dr_serve drives open-loop ('poisson') traffic "
                             "only")
        self.ctx = ctx
        self.spec = ctx.spec
        self.sys: Optional[DRSystem] = None
        self.sched = None
        self.record: Dict[str, Any] = {}
        self._cursor = 0

    # ---- set-up ------------------------------------------------------------
    def setup(self) -> None:
        from repro.serve.scheduler import DeadlineScheduler

        self.sys = DRSystem(self.ctx)
        self.sys.register()
        self.sys.svc.warmup(NAME)
        s = self.spec["scheduler"]
        self.sched = DeadlineScheduler(
            self.sys.svc, default_max_delay_ms=s["max_delay_ms"],
            wake_lead_ms=s["wake_lead_ms"])
        self.schedule = traffic.schedule(self.spec, self.ctx.seconds,
                                         self.size_keys)
        # the serving path compiles per shape it meets: every request size
        # the traffic sends, alone in a flush (padding to its bucket and
        # slicing the answer), once; these programs are kept for the next
        # run.  Groups of requests coalesced in one flush compile per
        # combination of sizes, which cannot all be met before the window:
        # `warmup_s` of the same traffic, with other payloads, meets the
        # commonest, and those are not kept.
        for n in sorted(set(int(r) for r in self.schedule.sizes["rows"])):
            t = self.sched.submit(NAME, self.sys.pool[:n])
            t.wait(WAIT_S)
            np.asarray(t.result())
        persist_compiles(False)
        warm_s = float(self.spec["warmup_s"])
        warm = traffic.schedule(self.spec, warm_s, self.size_keys)
        self._drive(warm, Window(warm_s), keep=False)

    def set_schedule(self, schedule: traffic.Schedule) -> None:
        self.schedule = schedule

    def counters(self) -> Dict[str, Any]:
        return self.sys.counters()

    # ---- the window --------------------------------------------------------
    def run(self, window: Window) -> Dict[str, float]:
        rec = self._drive(self.schedule, window, keep=True)
        self.record = rec
        out: Dict[str, float] = {}
        if rec["latency_ms"]:
            out["req_p95_ms"] = stats.percentile(rec["latency_ms"], 95)
        return out

    def _payload(self, i: int, sched: traffic.Schedule):
        """(offset, rows) of request i: the next `rows` rows of the pool,
        wrapping to its start."""
        n = int(sched.sizes["rows"][i % len(sched)])
        if self._cursor + n > self.sys.pool.shape[0]:
            self._cursor = 0
        off, self._cursor = self._cursor, self._cursor + n
        return off, n

    def _drive(self, sched: traffic.Schedule, window: Window,
               keep: bool) -> Dict[str, Any]:
        self._cursor = 0
        return self._open_loop(sched, window, keep)

    def _open_loop(self, sched, window: Window, keep: bool) -> Dict[str, Any]:
        from repro.serve import QueueFull

        pool = self.sys.pool
        n = len(sched)
        due = sched.due_s
        done = np.full(n, np.nan)
        late = np.zeros(n)
        answers: List[Optional[np.ndarray]] = [None] * n
        offs = np.zeros(n, np.int64)
        rows = np.zeros(n, np.int64)
        errors: List[str] = []
        handoff: "queue.Queue" = queue.Queue()

        def client() -> None:
            while True:
                item = handoff.get()
                if item is None:
                    return
                i, t = item
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

        th = threading.Thread(target=client, name="bench-client")
        th.start()
        t0 = window.open()
        refused = 0
        try:
            for i in range(n):
                off, r = self._payload(i, sched)
                offs[i], rows[i] = off, r
                target = t0 + due[i]
                with jax.profiler.TraceAnnotation("bench.sleep"):
                    sleep_until(target)
                late[i] = time.perf_counter() - target
                try:
                    with jax.profiler.TraceAnnotation("bench.submit"):
                        t = self.sched.submit(NAME, pool[off:off + r])
                except QueueFull:
                    refused += 1
                    continue
                handoff.put((i, t))
        finally:
            handoff.put(None)
            th.join()
            window.join()
        ok = ~np.isnan(done)
        lat = (done[ok] - (t0 + due[ok])) * 1e3
        return {"attempted": n, "failed": int(n - ok.sum()),
                "refused": refused, "errors": errors[:5],
                "latency_ms": lat.tolist(), "late_ms": (late * 1e3).tolist(),
                "answers": answers, "offsets": offs, "rows": rows}

    # ---- after the window --------------------------------------------------
    def close(self) -> None:
        if self.sched is not None:
            self.sched.shutdown()
            self.sched = None
        self.sys.free()

    def check(self, mode: str = "program") -> List[Comparison]:
        """`mode`: "program" compares what the window produced; "control"
        puts the reference, in the precision below the configuration's,
        in the program's place."""
        rec = self.record
        want = reference_pool(self.sys, self.sys.b0, None)
        limit = self.sys.limit("dr_serve", "err_out")
        if mode == "control":
            got = reference_pool(self.sys, self.sys.b0,
                                 control_dtype(self.sys.cfg))
            answers = [None if a is None else got[o:o + n]
                       for a, o, n in zip(rec["answers"], rec["offsets"],
                                          rec["rows"])]
        else:
            answers = rec["answers"]
        return [served_out_check("err_out", limit, answers, rec["offsets"],
                                 rec["rows"], want)]
