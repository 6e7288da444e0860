"""Drivers: one module per path through the program, named by a traffic
file's "driver".  Each defines `Driver(ctx)` with

  setup()              weights and state from the seed, the service, and
                       every shape the traffic will use, warmed
  run(window)          drive the traffic for the window; returns the
                       end-to-end values it measured
  close()              stop every thread, free the program's state
  check(mode)          the numbers compared with the reference, each with
                       its limit; mode "control" puts the reference, in
                       the precision below the configuration's, in the
                       program's place
  counters()           the program's counters now (read as deltas)
  set_schedule(s)      (open-loop drivers) the requests the window sends

and the window's host record, which the per-layer metric readers read.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import traffic as traffic_mod


@dataclasses.dataclass
class Context:
    """What a driver is given: the cell's configuration and traffic files,
    the seed and the devices it may use."""
    config: Dict[str, Any]
    spec: Dict[str, Any]
    seed: int
    seconds: float
    devices: List[Any]

    def rng(self, stream: str) -> np.random.Generator:
        return traffic_mod.rng_for(self.seed, stream)

    def jax_key(self, stream: str):
        import jax

        word = int(self.rng(stream).integers(0, 2 ** 31 - 1))
        return jax.random.PRNGKey(word)


@dataclasses.dataclass
class Comparison:
    """One number compared with the reference, and its limit (the check
    passes while value <= limit)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


class Window:
    """The measured window: its clock, and an optional profiler trace of a
    few seconds in its middle, with the program's counters read at the
    trace's start and stop."""

    def __init__(self, seconds: float, trace_dir: Optional[str] = None,
                 trace_s: float = 0.0,
                 counters: Optional[Callable[[], Dict[str, Any]]] = None):
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        self.trace_s = float(trace_s)
        self._counters = counters
        self.t0: Optional[float] = None
        self.trace_counters: Optional[tuple] = None  # (at start, at stop)
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def open(self, lead_s: float = 0.05) -> float:
        """Start the window `lead_s` from now; returns its start."""
        self.t0 = time.perf_counter() + lead_s
        if self.trace_dir is not None:
            self._thread = threading.Thread(target=self._trace, daemon=True,
                                            name="bench-trace")
            self._thread.start()
        return self.t0

    @property
    def end(self) -> float:
        return self.t0 + self.seconds

    def _trace(self) -> None:
        import jax

        try:
            start = self.t0 + max(0.0, (self.seconds - self.trace_s) / 2)
            sleep_until(start)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            c0 = self._counters() if self._counters else None
            sleep_until(time.perf_counter() + self.trace_s)
            c1 = self._counters() if self._counters else None
            jax.profiler.stop_trace()
            self.trace_counters = (c0, c1)
        except BaseException as e:  # noqa: BLE001 — raised again in join()
            self._error = e

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            raise self._error


def persist_compiles(on: bool) -> None:
    """Whether programs compiled from here on are written to the
    persistent compilation cache.  On for set-up and for the reference:
    what they compile is the same in every run and is then found by the
    next one.  Off for the traffic: the serving path compiles per new
    shape it meets, and those programs would pile up from run to run."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      0 if on else 1e9)


def sleep_until(t: float) -> None:
    """Sleep until `time.perf_counter()` reaches `t`."""
    while True:
        d = t - time.perf_counter()
        if d <= 0:
            return
        time.sleep(d)
