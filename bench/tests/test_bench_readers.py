"""Every per-layer reader of every cell, run as a `--trace 1` run runs it:
on the reduction of a trace recorded on a TPU v5e (`data/tws_v5e.xplane.pb`)
and the cell's own context, `data/contexts/<cell>.json`.  Each reads a
finite number, and a share of a roofline or of the peak lies in (0, 100].

A cell's context file holds what `bench.run.execute` hands its readers
beside the trace:

  counters, trace_counters   [before, after]: the driver's counters at the
                             window's (the trace's) start and end; values
                             are numbers or nested snapshots (`stages`,
                             `slo`)
  record                     the driver's host record of the window
  window_compiles            compilations inside the window
  programs                   program runs added to the trace's "XLA
                             Modules", one after another: [{"name", "ms",
                             "count"}]
  kernels                    (optional) Pallas kernel runs added to its
                             "XLA Ops", as custom calls whose instruction
                             is the kernel's name: [{"name", "ms", "count"}]

The recorded trace's busy and window seconds stay as recorded.  A new
cell brings its own file, so its readers are tested with no edit here.
"""

from __future__ import annotations

import json
import math
import pathlib
import shutil

import pytest

from bench import run as bench_run
from bench import trace
from bench.metrics import readers

DATA = pathlib.Path(__file__).parent / "data"
CONTEXTS = DATA / "contexts"
BENCH_DIR = pathlib.Path(bench_run.__file__).parent
CELLS = [w["name"] for w in bench_run.load_benchmark()["workloads"]]
GAP_NS = 0.5e6          # between two added runs


@pytest.fixture(scope="module")
def recorded():
    devices, host = trace.read_planes(str(DATA / "tws_v5e.xplane.pb"))
    return trace.reduce(devices, host, trace.window_of(devices, host), [0])


def _runs(entries, t_ns, event_name):
    """Events of `entries` laid out one after another from `t_ns`."""
    out = []
    for e in entries:
        for _ in range(int(e["count"])):
            out.append(trace.Event(event_name(e["name"]), t_ns, e["ms"] * 1e6))
            t_ns += e["ms"] * 1e6 + GAP_NS
    return out


def build_context(cell, bench, bench_dir, contexts, summary):
    """(resolved cell, the readers' context) of `cell`, from the recorded
    trace `summary` and the cell's context file under `contexts`."""
    path = contexts / f"{cell}.json"
    if not path.is_file():
        raise FileNotFoundError(
            f"cell {cell!r} has no reader-test context: add "
            f"bench/tests/data/contexts/{cell}.json (see this module's "
            f"docstring)")
    data = json.loads(path.read_text())
    res = bench_run.resolve(bench, cell, bench_dir)
    t0 = summary.modules[0].start_ns + 1e5
    modules = _runs(data["programs"], t0, lambda n: n)
    ops = _runs(data.get("kernels", []), t0,
                lambda n: f'%{n}.1 = f32[] custom-call(), '
                          f'custom_call_target="tpu_custom_call"')
    tr = trace.Summary(window_s=summary.window_s, busy_s=summary.busy_s,
                       modules=summary.modules + modules,
                       ops=summary.ops + ops, idle_gaps=summary.idle_gaps)
    pair = (lambda p: None if p is None else tuple(p))
    return res, {"cell": res["cell"], "config": res["config"],
                 "spec": res["spec"], "record": data["record"], "trace": tr,
                 "trace_counters": pair(data["trace_counters"]),
                 "counters": pair(data["counters"]),
                 "window_compiles": data["window_compiles"],
                 "device_kind": "TPU v5 lite"}


def _read_every_metric(res, ctx, bench_dir=BENCH_DIR):
    assert res["per_layer"]
    got = {}
    for m in res["per_layer"]:
        value = bench_run.load_reader(m["name"], bench_dir)(ctx)
        assert isinstance(value, float) and math.isfinite(value), m["name"]
        got[m["name"]] = value
        base = m["name"].split(".")[0]
        if m["unit"] == "%":
            assert 0.0 <= value <= 100.0, (m["name"], value)
        if base.endswith("_roofline") or "mfu" in base:
            assert value > 0.0, m["name"]
    json.dumps(got)
    return got


@pytest.mark.parametrize("cell", CELLS)
def test_every_reader_of_the_cell_reads_a_number(cell, recorded):
    res, ctx = build_context(cell, bench_run.load_benchmark(), BENCH_DIR,
                             CONTEXTS, recorded)
    _read_every_metric(res, ctx)


def test_a_reader_with_nothing_to_read_returns_none(recorded):
    """A DR kernel's roofline where the trace holds no run of it, and the
    LM readers on a trace of DR programs only."""
    _, ctx = build_context("smollm_135m.chat", bench_run.load_benchmark(),
                           BENCH_DIR, CONTEXTS, recorded)
    empty = trace.Summary(window_s=1.0, busy_s=0.0, modules=[], ops=[],
                          idle_gaps=[])
    ctx = dict(ctx, trace=empty)
    for name in ("decode_mfu.lm", "decode_step_ms.lm", "prefill_ms.lm"):
        assert bench_run.load_reader(name)(ctx) is None
    assert bench_run.load_reader("fused_transform_roofline.serve")(ctx) is None


def test_a_cell_without_a_context_file_names_the_file(recorded, tmp_path):
    with pytest.raises(FileNotFoundError,
                       match=r"contexts/dr_paper\.steady\.json"):
        build_context("dr_paper.steady", bench_run.load_benchmark(),
                      BENCH_DIR, tmp_path, recorded)


def test_a_dropped_in_cell_with_its_readers_and_context_needs_no_edit(
        recorded, tmp_path):
    """A new cell whose metrics read a counter and a kernel no other cell
    has: its traffic, reader and context files, and its BENCHMARK.json
    entries, are all it brings."""
    bench_dir = tmp_path / "bench"
    for sub in ("traffic", "metrics", "drivers"):
        shutil.copytree(BENCH_DIR / sub, bench_dir / sub)
    contexts = tmp_path / "contexts"
    shutil.copytree(CONTEXTS, contexts)
    (bench_dir / "traffic" / "longdoc.json").write_text(
        (BENCH_DIR / "traffic" / "steady.json").read_text())
    (bench_dir / "metrics" / "tokens_per_expert.longdoc.py").write_text(
        "from bench.metrics import readers\n\n\n"
        "def read(ctx):\n"
        "    routed = readers.delta(ctx, 'routed_tokens')\n"
        "    return None if routed is None else routed / 64\n")
    (bench_dir / "metrics" / "moe_roofline.longdoc.py").write_text(
        "from bench.metrics import readers\n\n\n"
        "def read(ctx):\n"
        "    s = ctx['trace'].kernel_s('moe_gmm')\n"
        "    return readers.roofline_share(ctx, 1e9, 0.0, s) if s else None\n")
    (contexts / "dr_paper.longdoc.json").write_text(json.dumps({
        "counters": [{"routed_tokens": 100}, {"routed_tokens": 6500}],
        "trace_counters": None, "record": {}, "window_compiles": 0,
        "programs": [{"name": "jit_step(1)", "ms": 2.0, "count": 3}],
        "kernels": [{"name": "moe_gmm", "ms": 0.5, "count": 4}]}))
    bench = bench_run.load_benchmark()
    bench["workloads"].append({"name": "dr_paper.longdoc",
                               "config": "dr_paper", "traffic": "longdoc",
                               "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("dr_paper.longdoc")
    for name, unit in (("tokens_per_expert.longdoc", "tokens"),
                       ("moe_roofline.longdoc", "%")):
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "higher",
            "source": "program_counter", "layer": "experts",
            "moves": bench["end_to_end"][0]["name"],
            "workloads": ["dr_paper.longdoc"]})
    res, ctx = build_context("dr_paper.longdoc", bench, bench_dir, contexts,
                             recorded)
    got = _read_every_metric(res, ctx, bench_dir)
    assert got["tokens_per_expert.longdoc"] == 100.0
    # 1e9 FLOP in 2 ms of kernel time at 197 TFLOP/s
    assert got["moe_roofline.longdoc"] == pytest.approx(
        100 * 1e9 / 197e12 / 2e-3)
    assert [e.name for e in ctx["trace"].module_runs("jit_step(")
            ["jit_step(1)"]] == ["jit_step(1)"] * 3


# ---- the program's stages and SLO cells, as the window's two snapshots -----

def _stats_pair(before, window, cls):
    """Two snapshots of one histogram: after `before`, after `window`."""
    s = cls()
    for v in before:
        s.record(v)
    a = s.snapshot()
    for v in window:
        s.record(v)
    return a, s.snapshot()


def _ctx(c0, c1):
    return {"counters": (c0, c1)}


def test_stage_and_queue_percentiles_read_only_the_window():
    """Samples from before the window (100 ms each) are left out; the
    window's 1..100 ms read as the program's own percentile of them."""
    from repro.serve import slo

    window = [float(v) for v in range(1, 101)]
    f0, f1 = _stats_pair([100.0] * 50, window, slo.SpanStats)
    q0, q1 = _stats_pair([100.0] * 50, window[:60], slo.LatencyStats)
    _, q16 = _stats_pair([], window[60:], slo.LatencyStats)
    c0 = {"stages": {"flush.dr": f0},
          "slo": {"dr": {8: {"queue_delay": q0, "e2e": q0}}}}
    c1 = {"stages": {"flush.dr": f1, "poll": f1},
          "slo": {"dr": {8: {"queue_delay": q1, "e2e": q1},
                         16: {"queue_delay": q16, "e2e": q16}}}}
    only = slo.LatencyStats()
    for v in window:
        only.record(v)
    for q in (50, 95, 99):
        want = slo.snapshot_percentile(only.snapshot(), q)
        assert readers.stage_percentile(_ctx(c0, c1), "flush.dr", q) == want
        assert readers.queue_percentile(_ctx(c0, c1), q) == want
        # read back from JSON, bins and buckets keyed by strings
        back = json.loads(json.dumps([c0, c1]))
        assert readers.stage_percentile(_ctx(*back), "flush.dr", q) == want
        assert readers.queue_percentile(_ctx(*back), q) == want
    # a stage first seen inside the window: all its samples count
    assert readers.stage_percentile(_ctx(c0, c1), "poll", 99) == \
        slo.snapshot_percentile(f1, 99)
    assert readers.queue_percentile(_ctx(c0, c1), 100, buckets=["8"]) == \
        slo.snapshot_percentile(only.snapshot(), 60)


def test_stage_and_queue_percentiles_without_snapshots_are_none():
    from repro.serve import slo

    a, b = _stats_pair([1.0], [], slo.SpanStats)
    c0, c1 = {"stages": {"flush.dr": a}}, {"stages": {"flush.dr": b}}
    assert readers.stage_percentile(_ctx(c0, c1), "flush.dr", 99) is None
    assert readers.stage_percentile(_ctx(c0, c1), "poll", 99) is None
    assert readers.queue_percentile(_ctx(c0, c1), 99) is None
    assert readers.stage_percentile(_ctx({"steps": 1}, {"steps": 2}),
                                    "flush.dr", 99) is None
    assert readers.stage_percentile({"counters": None}, "flush.dr", 99) is None
