"""Every per-layer reader of every cell, run as a `--trace 1` run runs it:
on the reduction of a trace recorded on a TPU v5e (`data/tws_v5e.xplane.pb`,
with LM step runs added for the LM cell's readers), the program's counters
and the driver's host record.  Each reads a finite number, and a share of
a roofline or of the peak lies in (0, 100]."""

from __future__ import annotations

import json
import math
import pathlib

import pytest

from bench import run as bench_run
from bench import trace

DATA = pathlib.Path(__file__).parent / "data" / "tws_v5e.xplane.pb"
CELLS = [w["name"] for w in bench_run.load_benchmark()["workloads"]]


@pytest.fixture(scope="module")
def recorded():
    devices, host = trace.read_planes(str(DATA))
    s = trace.reduce(devices, host, trace.window_of(devices, host), [0])
    # two LM step programs inside the traced window: a decode run five
    # times, a prefill once
    t0 = s.modules[0].start_ns
    lm = ([trace.Event("jit_fn(11)", t0 + 1e5, 3.0e6)]
          + [trace.Event("jit_fn(12)", t0 + 4e6 + i * 2.5e6, 1.87e6)
             for i in range(5)])
    return trace.Summary(window_s=s.window_s, busy_s=s.busy_s,
                         modules=s.modules + lm, ops=s.ops,
                         idle_gaps=s.idle_gaps)


def _context(cell, summary):
    """What `bench.run.execute` hands the readers: 40 blocks of 256 rows,
    served and folded, in the traced window."""
    res = bench_run.resolve(bench_run.load_benchmark(), cell)
    c0 = {"served_rows": 1000, "padded_rows": 50, "batches_run": 10,
          "folded_rows": 1000}
    c1 = {"served_rows": 11240, "padded_rows": 250, "batches_run": 50,
          "folded_rows": 11240}
    record = {"late_ms": [0.2, 0.4, 1.5], "promote_ms": [0.03, 0.05],
              "decode_context": [300.0, 700.0]}
    return res, {"cell": res["cell"], "config": res["config"],
                 "spec": res["spec"], "record": record, "trace": summary,
                 "trace_counters": (c0, c1), "counters": (c0, c1),
                 "window_compiles": 3, "device_kind": "TPU v5 lite"}


@pytest.mark.parametrize("cell", CELLS)
def test_every_reader_of_the_cell_reads_a_number(cell, recorded):
    res, ctx = _context(cell, recorded)
    assert res["per_layer"]
    got = {}
    for m in res["per_layer"]:
        value = bench_run.load_reader(m["name"])(ctx)
        assert isinstance(value, float) and math.isfinite(value), m["name"]
        got[m["name"]] = value
        base = m["name"].split(".")[0]
        if m["unit"] == "%":
            assert 0.0 <= value <= 100.0, (m["name"], value)
        if base.endswith("_roofline") or "mfu" in base:
            assert value > 0.0, m["name"]
    json.dumps(got)


def test_a_reader_with_nothing_to_read_returns_none(recorded):
    """A DR kernel's roofline where the trace holds no run of it, and the
    LM readers on a trace of DR programs only."""
    _, ctx = _context("smollm_135m.chat", recorded)
    empty = trace.Summary(window_s=1.0, busy_s=0.0, modules=[], ops=[],
                          idle_gaps=[])
    ctx = dict(ctx, trace=empty)
    for name in ("decode_mfu.lm", "decode_step_ms.lm", "prefill_ms.lm"):
        assert bench_run.load_reader(name)(ctx) is None
    assert bench_run.load_reader("fused_transform_roofline.serve")(ctx) is None
