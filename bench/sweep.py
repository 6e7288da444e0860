"""Sweep the offered load of one open-loop cell to find its knee.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 100,200,400

One process: the cell is set up once, then each rate is offered for
`--seconds` in turn (same traffic file otherwise).  Each rate prints one
JSON line: offered rate, requests sent and failed, completions per second,
latency percentiles, how late the generator ran, and compilations in the
window.  The knee is the highest rate whose latency tail stays flat and
whose requests all complete; a cell is then set at about four fifths of it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run  # noqa: E402  (bench/run.py, which puts the repo on sys.path)

from bench import stats, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax

    from bench.drivers import Context, Window, persist_compiles
    from bench.trace import CompileCounter
    from repro.launch.compile_cache import use_compile_cache

    devices = jax.devices()
    if devices[0].platform == "cpu":
        print("sweep: no accelerator", file=sys.stderr)
        return 2
    use_compile_cache()
    persist_compiles(True)
    resolved = run.resolve(run.load_benchmark(), args.workload)
    spec = dict(resolved["spec"])
    ctx = Context(config=resolved["config"],
                  spec=spec, seed=args.seed, seconds=args.seconds,
                  devices=devices[:1])
    drv = run.load_driver(spec["driver"])(ctx)
    t = time.perf_counter()
    drv.setup()
    persist_compiles(False)
    print(json.dumps({"setup_s": time.perf_counter() - t}), flush=True)
    counter = CompileCounter()
    for r in [float(x) for x in args.rates.split(",")]:
        s = dict(spec, rate_per_s=r)
        drv.set_schedule(traffic.schedule(s, args.seconds, drv.size_keys))
        counter.count = 0
        counter.start()
        e2e = drv.run(Window(args.seconds))
        counter.stop()
        rec = drv.record
        line = {"rate_per_s": r, "attempted": rec["attempted"],
                "failed": rec["failed"], "window_compiles": counter.count,
                "gen_late_p99_ms": stats.percentile(rec["late_ms"], 99)}
        line.update(e2e)
        for key in ("latency_ms", "ttft_ms"):
            if rec.get(key):
                line[key + "_p50"] = stats.percentile(rec[key], 50)
                line[key + "_p90"] = stats.percentile(rec[key], 90)
                line[key + "_p99"] = stats.percentile(rec[key], 99)
        print(json.dumps(line), flush=True)
    drv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
