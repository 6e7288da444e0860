"""Train-while-serve: one closed-loop stream of fixed-size blocks through
`DRService.serve_and_update`, each answer copied to the host, and
`promote()` after every `promote_every` blocks.

Block j is the j-th run of `rows` rows of the payload pool, so the
reference can replay the whole stream from the pool.  The check replays
every block through the reference's update in the same order and compares
the answers of a sample of blocks (those on each side of every promote,
and one in `SAMPLE` more, by the seed) with the reference's transform
under the state live at that block, and the first promoted state's change
from the initial one with the reference's.  Later states are compared
through the answers only: over thousands of folds, rounding walks the
program's B away from the reference's along directions in which the
contrast is flat, by an amount that differs from seed to seed.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import stats
from bench.drivers import Comparison, Context, Window, sleep_until
from bench.drivers._dr import NAME, DRSystem, control_dtype, rel_gap
from bench.reference import dr as ref

WARM_NAME = "warm"      # same model and state: shares the fused program
SAMPLE = 50


class Driver:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.spec = ctx.spec
        self.rows = int(self.spec["rows"]["value"])
        self.every = int(self.spec["promote_every"])
        self.sys: Optional[DRSystem] = None
        self.record: Dict[str, Any] = {}
        self.folded_rows = 0
        self._pick = int(ctx.rng("sample").integers(SAMPLE))

    def setup(self) -> None:
        self.sys = DRSystem(self.ctx)
        self.sys.register(NAME)
        self.sys.register(WARM_NAME)
        svc = self.sys.svc
        # the fused transform+update program is keyed by the model's
        # configuration, so a scratch name warms it without folding
        # anything into the timed name's chain
        t_end = time.perf_counter() + float(self.spec["warmup_s"])
        n = 0
        while n < 2 * self.every or time.perf_counter() < t_end:
            np.asarray(svc.serve_and_update(WARM_NAME, self._block(n)))
            n += 1
            if n % self.every == 0:
                svc.promote(WARM_NAME)

    def _block(self, j: int) -> np.ndarray:
        off = self._offset(j)
        return self.sys.pool[off:off + self.rows]

    def _offset(self, j: int) -> int:
        span = self.sys.pool.shape[0] // self.rows
        return (j % span) * self.rows

    def counters(self) -> Dict[str, Any]:
        c = self.sys.counters()
        c["folded_rows"] = self.folded_rows
        return c

    def _keep(self, j: int) -> bool:
        k = j % self.every
        return k == 0 or k == self.every - 1 or j % SAMPLE == self._pick

    def run(self, window: Window) -> Dict[str, float]:
        svc = self.sys.svc
        answers: Dict[int, np.ndarray] = {}
        promote_ms: List[float] = []
        rows_in_window = 0
        j = 0
        t0 = window.open()
        sleep_until(t0)
        try:
            while time.perf_counter() < window.end:
                with jax.profiler.TraceAnnotation("bench.serve_and_update"):
                    y = svc.serve_and_update(NAME, self._block(j))
                with jax.profiler.TraceAnnotation("bench.to_host"):
                    y = np.asarray(y)
                self.folded_rows += self.rows
                if time.perf_counter() <= window.end:
                    rows_in_window += self.rows
                if self._keep(j):
                    answers[j] = y
                j += 1
                if j % self.every == 0:
                    a = time.perf_counter()
                    with jax.profiler.TraceAnnotation("bench.promote"):
                        svc.promote(NAME)
                    promote_ms.append((time.perf_counter() - a) * 1e3)
        finally:
            window.join()
        self.record = {"attempted": j, "failed": 0, "blocks": j,
                       "answers": answers, "promote_ms": promote_ms,
                       "promotes": len(promote_ms)}
        return {"tws_rows_per_s": stats.rate(rows_in_window, window.seconds)}

    def close(self) -> None:
        reg = self.sys.svc.registry
        self.first_b = (np.asarray(reg.state(NAME, 1).stages[1], np.float64)
                        if reg.n_versions(NAME) > 1 else None)
        self.sys.free()

    # ---- the check ---------------------------------------------------------
    def replay(self, operand_dtype=None, rows_used: Optional[int] = None
               ) -> np.ndarray:
        """The reference's B after every block (B_0 .. B_blocks), float64.
        `rows_used` folds only the first that many rows of each block (the
        half-batch fault)."""
        cfg = self.sys.cfg
        e = cfg["easi"]
        pool = jnp.asarray(self.sys.pool)
        span = self.sys.pool.shape[0] // self.rows
        scale, rows = self.sys.scale, self.rows

        def step(b, j):
            x = jax.lax.dynamic_slice(pool, ((j % span) * rows, 0),
                                      (rows_used or rows, cfg["m"]))
            b = ref.update(self.sys.r, b, x, scale, e["mu"],
                           e["second_order"], e["higher_order"],
                           operand_dtype)
            return b, b

        f = jax.jit(lambda b0, js: jax.lax.scan(step, b0, js)[1])
        out = [np.asarray(self.sys.b0, np.float64)[None]]
        n = self.record["blocks"]
        chunk = 8192
        b = self.sys.b0
        for a in range(0, n, chunk):
            bs = f(b, jnp.arange(a, min(n, a + chunk)))
            b = bs[-1]
            out.append(np.asarray(bs, np.float64))
        return np.concatenate(out)

    def check(self, mode: str = "program") -> List[Comparison]:
        """`mode`: "program" compares what the window produced; "control"
        puts the reference, in the precision below the configuration's,
        in the program's place; "half_batch" puts there the reference
        folding only half of each block; "stale" the reference whose
        promotes go through but which serves every block with the initial
        state."""
        rec = self.record
        bs = self.replay()
        live = lambda j: bs[(j // self.every) * self.every]   # noqa: E731
        control = mode != "program"
        od = control_dtype(self.sys.cfg) if mode == "control" else None
        got_bs = None
        if mode == "control":
            got_bs = self.replay(od)
        elif mode == "half_batch":
            got_bs = self.replay(rows_used=self.rows // 2)
        elif mode == "stale":
            got_bs = bs
        f = jax.jit(lambda b, x: ref.transform(self.sys.r, b, x,
                                               self.sys.scale))
        worst, scale = 0.0, 0.0
        wants = {}
        for j in sorted(rec["answers"]):
            x = jnp.asarray(self._block(j))
            wants[j] = np.asarray(f(jnp.asarray(live(j), jnp.float32), x),
                                  np.float64)
            scale = max(scale, float(np.max(np.abs(wants[j]))))
        for j, want in wants.items():
            if control:
                lj = bs[0] if mode == "stale" else \
                    got_bs[(j // self.every) * self.every]
                got = np.asarray(ref.transform(
                    self.sys.r, jnp.asarray(lj, jnp.float32),
                    jnp.asarray(self._block(j)), self.sys.scale, od))
            else:
                got = rec["answers"][j]
            worst = max(worst, rel_gap(got, want, scale))
        state = float("inf")                # no promote: nothing to compare
        got_b = got_bs[self.every] if control else self.first_b
        if rec["promotes"] and got_b is not None:
            d_want = bs[self.every] - bs[0]
            state = rel_gap(got_b - bs[0], d_want,
                            float(np.max(np.abs(d_want))) or 1.0)
        return [Comparison("err_answers", worst,
                           self.sys.limit("dr_tws", "err_answers")),
                Comparison("err_first_state", state,
                           self.sys.limit("dr_tws", "err_first_state"))]
