"""Reduce a profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read.

What the trace of a TPU holds (read by hand from a v5e trace):

  * plane `/device:TPU:<i>`, line "XLA Modules": one event per run of a
    compiled program, named `jit_<function>(<id>)`;
  * the same plane, line "XLA Ops": one event per operation inside those
    runs, named by its HLO text, `%<instruction> = <shape> <op>(...)`; a
    Pallas kernel is a `custom-call` whose instruction is named after the
    kernel's jitted wrapper (`%fused_transform.1 = ... custom-call(...),
    custom_call_target="tpu_custom_call"`);
  * plane `/host:CPU`: one line per host thread; the benchmark's own
    `jax.profiler.TraceAnnotation` spans (`bench.*`) and the runtime's
    events (compiles among them) lie there, on the same clock.

Device busy time is the union of the program runs' intervals; a kernel's
time is the sum of its custom-call events; an idle gap is a stretch
between busy intervals, named by the benchmark spans (and any compile)
that the host was in at its middle.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

_INSTR = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)? = ")
_COMPILE = ("Compile", "compile")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Summary:
    """The reduced trace of one traced window."""
    window_s: float
    busy_s: float                           # mean over the devices used
    modules: List[Event]                    # program runs, device 0
    ops: List[Event]                        # operations, device 0
    idle_gaps: List[Tuple[str, float]]      # (what the host did, seconds)

    def kernel_events(self, kernel: str) -> List[Event]:
        """The custom-call events of the Pallas kernel `kernel` (its jitted
        wrapper's name)."""
        return [e for e in self.ops
                if "custom-call(" in e.name and instruction(e.name) == kernel]

    def kernel_s(self, kernel: str) -> float:
        return sum(e.dur_ns for e in self.kernel_events(kernel)) * 1e-9

    def module_runs(self, prefix: str) -> Dict[str, List[Event]]:
        """Program runs whose name starts with `prefix`, by program."""
        out: Dict[str, List[Event]] = collections.defaultdict(list)
        for e in self.modules:
            if e.name.startswith(prefix):
                out[e.name].append(e)
        return dict(out)

    def breakdown(self, top: int = 10) -> Dict[str, List[List[Any]]]:
        ops: Dict[str, float] = collections.Counter()
        for e in self.ops:
            ops[instruction(e.name) or e.name[:64]] += e.dur_ns * 1e-9
        gaps: Dict[str, float] = collections.Counter()
        for label, s in self.idle_gaps:
            gaps[label] += s
        return {"device_ops": [[k, v] for k, v in
                               sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
                "idle_gaps": [[k, v] for k, v in
                              sorted(gaps.items(), key=lambda kv: -kv[1])[:top]]}


def instruction(op_name: str) -> Optional[str]:
    """`%fused_transform.1 = f32[...] custom-call(...)` -> "fused_transform"."""
    m = _INSTR.match(op_name)
    return m.group(1) if m else None


def union_ns(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _events(line) -> List[Event]:
    return [Event(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def read_planes(path: str) -> Tuple[Dict[int, Dict[str, List[Event]]],
                                     Dict[str, List[Event]]]:
    """({device id: {line: events}}, {host line: events}) of a trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, List[Event]]] = {}
    host: Dict[str, List[Event]] = collections.defaultdict(list)
    for plane in pd.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            devices[int(m.group(1))] = {
                ln.name: _events(ln) for ln in plane.lines
                if ln.name in ("XLA Modules", "XLA Ops")}
        elif plane.name == "/host:CPU":
            for i, ln in enumerate(plane.lines):
                host[f"{ln.name}#{i}"].extend(_events(ln))
    return devices, dict(host)


def label_gaps(gaps: Sequence[Tuple[float, float]],
               host: Dict[str, List[Event]]) -> List[Tuple[str, float]]:
    """Name each idle gap by what the host was doing at its middle: the
    benchmark's spans (`bench.*`) open there, plus "compile" where the
    runtime was compiling; "none" where neither."""
    spans = [e for evs in host.values() for e in evs
             if e.name.startswith("bench.")
             or any(c in e.name for c in _COMPILE)]
    spans.sort(key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]
    longest = max((e.dur_ns for e in spans), default=0.0)
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        lo = bisect.bisect_left(starts, mid - longest)
        hi = bisect.bisect_right(starts, mid)
        names = set()
        for e in spans[lo:hi]:
            if e.start_ns <= mid <= e.end_ns:
                names.add("compile" if not e.name.startswith("bench.")
                          else e.name)
        out.append(("+".join(sorted(names)) or "none", (b - a) * 1e-9))
    return out


def reduce(devices: Dict[int, Dict[str, List[Event]]],
           host: Dict[str, List[Event]], window_ns: Tuple[float, float],
           device_ids: Sequence[int]) -> Summary:
    """Busy time, idle gaps and the events of device `device_ids[0]` inside
    the traced window [window_ns)."""
    lo, hi = window_ns
    busy = []
    first: Dict[str, List[Event]] = {}
    for i, did in enumerate(device_ids):
        lines = devices.get(did, {})
        mods = [e for e in lines.get("XLA Modules", [])
                if e.end_ns > lo and e.start_ns < hi]
        merged = union_ns((max(e.start_ns, lo), min(e.end_ns, hi))
                          for e in mods)
        busy.append(sum(b - a for a, b in merged))
        if i == 0:
            first = {"merged": merged, "mods": mods,
                     "ops": [e for e in lines.get("XLA Ops", [])
                             if e.end_ns > lo and e.start_ns < hi]}
    merged = first.get("merged", [])
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=(sum(busy) / max(1, len(busy))) * 1e-9,
                   modules=first.get("mods", []), ops=first.get("ops", []),
                   idle_gaps=label_gaps(gaps, host))


def window_of(devices: Dict[int, Dict[str, List[Event]]],
              host: Dict[str, List[Event]]) -> Tuple[float, float]:
    """The traced window on the trace's clock: from the first to the last
    event the trace holds on any plane."""
    lo, hi = float("inf"), float("-inf")
    for lines in list(devices.values()) + [host]:
        for evs in lines.values():
            for e in evs:
                lo = min(lo, e.start_ns)
                hi = max(hi, e.end_ns)
    return lo, hi


def summarize(path: str, devices_used: Sequence[Any]) -> Summary:
    devices, host = read_planes(path)
    ids = [d.id for d in devices_used]
    return reduce(devices, host, window_of(devices, host), ids)


class CompileCounter:
    """Counts XLA compilations (JAX's `backend_compile` events) between
    `start()` and `stop()`."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event: str, duration: float, **kw: Any) -> None:
        if self._on and event == self.EVENT:
            self.count += 1

    def start(self) -> None:
        self._on = True

    def stop(self) -> None:
        self._on = False
