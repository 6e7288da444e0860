"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell is looked up in BENCHMARK.json; its
configuration (`bench/configs/<config>.json`), traffic mix
(`bench/traffic/<traffic>.json`, whose "driver" names
`bench/drivers/<driver>.py`) and per-layer metric readers
(`bench/metrics/<metric>.py`) are found by name, so a cell, a
configuration, a traffic mix or a metric is added with new files and new
BENCHMARK.json entries only.

One process: weights and state from the seed on the device, every shape
the cell uses warmed (set-up), then the window of `--seconds`, then the
check of what the window produced against the plain reference.  With
`--trace 0` the result carries the cell's end-to-end metrics; with
`--trace 1` a profiler trace of a few seconds in the middle of the window
gives its per-layer metrics, `busy_s`/`window_s` and a breakdown.  The last
lines of standard error list every number compared with its limit, and
the last line of standard output is the result object.  Without an
accelerator, or with fewer chips than the cell asks for, it exits 2 and
prints no result.

How to add a cell (new files and new BENCHMARK.json entries only):

  bench/configs/<config>.json     the configuration as it is run, with
                                  its "limits" for the check (a new
                                  configuration only), and its plain
                                  reference in bench/reference/
  bench/traffic/<traffic>.json    the traffic mix, read by traffic.py
  bench/drivers/<driver>.py       the path it drives, where no driver
                                  drives it yet (a `Driver` class, see
                                  drivers/__init__.py); its counters()
                                  hand over what the readers read
  bench/metrics/<metric>.py       one reader per per-layer metric, a thin
                                  call to metrics/readers.py where it can
  bench/tests/data/contexts/<cell>.json
                                  the reader-test context: counters, host
                                  record and program runs the cell's
                                  readers read (test_bench_readers.py)

and in BENCHMARK.json: the configuration (a new one only), the cell under
"workloads", its per-layer metrics with "workloads": [<cell>], a new
end-to-end metric if the cell reports one, and the cell's name added to
the "workloads" list of each end-to-end metric it reports.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional, Sequence, Tuple  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TRACE_DIR = ROOT / ".bench_trace"     # the profiler's output, per run
TRACE_S = 4.0                         # traced seconds, in the window's middle


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------

def load_benchmark(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def resolve(bench: Dict[str, Any], workload: str,
            bench_dir: pathlib.Path = BENCH_DIR) -> Dict[str, Any]:
    """The cell's entry, configuration, traffic mix, and the end-to-end and
    per-layer metrics it reports, each found by name."""
    from bench import traffic

    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(ROOT / configs[cell["config"]]["file"]) as f:
        config = json.load(f)
    spec = traffic.load(cell["traffic"], bench_dir / "traffic")
    if not (bench_dir / "drivers" / f"{spec['driver']}.py").is_file():
        raise FileNotFoundError(f"traffic {cell['traffic']!r} names driver "
                                f"{spec['driver']!r}, which has no file")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    for m in per_layer:
        if not (bench_dir / "metrics" / f"{m['name']}.py").is_file():
            raise FileNotFoundError(f"per-layer metric {m['name']!r} has no "
                                    f"reader file")
    return {"cell": cell, "config": config, "spec": spec, "end_to_end": e2e,
            "per_layer": per_layer}


def load_reader(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The `read(ctx)` function of `bench/metrics/<name>.py`."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_driver(name: str):
    return importlib.import_module(f"bench.drivers.{name}").Driver


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _device_info(devices: Sequence[Any]) -> Dict[str, Any]:
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def execute(resolved: Dict[str, Any], seed: int, seconds: float, trace: bool,
            devices: Sequence[Any], t_start: float = T_START,
            trace_dir: pathlib.Path = TRACE_DIR,
            trace_s: Optional[float] = None) -> Tuple[Dict[str, Any], List[Any]]:
    """Set up, run the window, check; returns the result object and the
    comparisons it was judged by."""
    import jax

    from bench.drivers import Context, Window, persist_compiles
    from bench import trace as trace_mod
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    persist_compiles(True)

    cell = resolved["cell"]
    ctx = Context(config=resolved["config"],
                  spec=resolved["spec"], seed=int(seed),
                  seconds=float(seconds), devices=list(devices))
    drv = load_driver(resolved["spec"]["driver"])(ctx)
    drv.setup()
    persist_compiles(False)
    compiles = trace_mod.CompileCounter()
    setup_s = time.perf_counter() - t_start

    tdir = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tdir = str(trace_dir)
    if trace_s is None:
        trace_s = float(resolved["spec"].get("trace_s", TRACE_S))
    window = Window(seconds, trace_dir=tdir,
                    trace_s=min(trace_s, seconds / 2),
                    counters=drv.counters)
    c0 = drv.counters()
    compiles.start()
    e2e_values = drv.run(window)
    compiles.stop()
    c1 = drv.counters()
    device = _device_info(ctx.devices)
    drv.close()
    t_window = time.perf_counter()
    _note(f"set-up {setup_s:.1f} s; window and answers "
          f"{t_window - t_start - setup_s:.1f} s; {compiles.count} "
          f"compilations in the window")
    for err in drv.record.get("errors", []):
        _note(f"failed: {err}")

    result: Dict[str, Any] = {
        "correct": False,
        "attempted": int(drv.record["attempted"]),
        "failed": int(drv.record["failed"]),
        "metrics": {},
        "device": device,
    }
    if not trace:
        e2e_values["setup_s"] = setup_s
        for m in resolved["end_to_end"]:
            if m["name"] not in e2e_values:
                raise RuntimeError(f"{cell['name']}: the driver measured no "
                                   f"{m['name']!r}")
            result["metrics"][m["name"]] = {"value": e2e_values[m["name"]],
                                            "unit": m["unit"]}
    else:
        summary = trace_mod.summarize(trace_mod.find_xplane(tdir),
                                      ctx.devices)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
        rctx = {"cell": cell, "config": ctx.config, "spec": ctx.spec,
                "record": drv.record, "trace": summary,
                "trace_counters": window.trace_counters,
                "counters": (c0, c1), "window_compiles": compiles.count,
                "device_kind": device["kind"]}
        for m in resolved["per_layer"]:
            value = load_reader(m["name"])(rctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        _note(f"trace read in {time.perf_counter() - t_window:.1f} s")
    t_check = time.perf_counter()
    persist_compiles(True)
    comparisons = drv.check()
    _note(f"check {time.perf_counter() - t_check:.1f} s")
    result["correct"] = bool(comparisons) and all(c.ok for c in comparisons)
    result["checked"] = {c.name: {"value": c.value, "limit": c.limit}
                         for c in comparisons}
    return result, comparisons


def _note(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = parse_args(argv)
    resolved = resolve(load_benchmark(), args.workload)
    chips = int(resolved["cell"]["chips"])

    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < chips:
        print(f"bench: cell {args.workload!r} needs {chips} accelerator "
              f"chip(s); JAX sees {len(devices)} {devices[0].platform!r} "
              f"device(s). Nothing was run.", file=sys.stderr)
        return 2
    result, comparisons = execute(resolved, args.seed, args.seconds,
                                  bool(args.trace), devices[:chips])
    for c in comparisons:
        print(f"check {c.name} = {c.value!r} (limit {c.limit!r}): "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
