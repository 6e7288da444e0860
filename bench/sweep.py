"""Sweep the offered load of one cell to find its knee.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 100,200,400            # open loop: requests per second
    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --outstanding 16,48,96,192     # closed loop: requests in flight

One process: the cell is set up once (a closed loop for the most requests
in flight it is swept to), then each load is offered for `--seconds` in
turn (same traffic file otherwise).  Each load prints one JSON line:
offered load, requests sent and failed, the cell's end-to-end metrics,
latency percentiles, how late the generator ran, compilations in the
window, and the rows per batch, padding and flush and queue-delay
percentiles where the driver's counters hand them over.  An open-loop
knee is the highest rate whose latency tail stays flat and whose requests
all complete; a cell is then set at about four fifths of it.  A closed
loop's knee is the fewest requests in flight at which the rate completed
stops rising; a cell above capacity keeps well over it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run  # noqa: E402  (bench/run.py, which puts the repo on sys.path)

from bench import stats, traffic  # noqa: E402
from bench.metrics import readers  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    load = ap.add_mutually_exclusive_group(required=True)
    load.add_argument("--rates")
    load.add_argument("--outstanding")
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
    if args.rates:
        key, loads = "rate_per_s", [float(x) for x in args.rates.split(",")]
    else:
        key = "outstanding"
        loads = [int(x) for x in args.outstanding.split(",")]
        spec["outstanding"] = max(loads)
    ctx = Context(config=resolved["config"],
                  spec=spec, seed=args.seed, seconds=args.seconds,
                  devices=devices[:1])
    drv = run.load_driver(spec["driver"])(ctx)
    t = time.perf_counter()
    drv.setup()
    persist_compiles(False)
    print(json.dumps({"setup_s": time.perf_counter() - t}), flush=True)
    counter = CompileCounter()
    for v in loads:
        s = dict(spec, **{key: v})
        drv.set_schedule(traffic.schedule(s, args.seconds, drv.size_keys))
        counter.count = 0
        c0 = drv.counters()
        counter.start()
        e2e = drv.run(Window(args.seconds))
        counter.stop()
        rc = {"counters": [c0, drv.counters()]}
        rec = drv.record
        line = {key: v, "attempted": rec["attempted"],
                "failed": rec["failed"], "window_compiles": counter.count}
        line.update(e2e)
        if rec.get("late_ms"):
            line["gen_late_p99_ms"] = stats.percentile(rec["late_ms"], 99)
        for name in ("latency_ms", "ttft_ms"):
            if rec.get(name):
                line[name + "_p50"] = stats.percentile(rec[name], 50)
                line[name + "_p90"] = stats.percentile(rec[name], 90)
                line[name + "_p99"] = stats.percentile(rec[name], 99)
        found = {"rows_per_batch": readers.rows_per_batch(rc),
                 "pad_share": readers.pad_share(rc),
                 "flush_p50_ms": readers.stage_percentile(rc, "flush.dr", 50),
                 "flush_p99_ms": readers.stage_percentile(rc, "flush.dr", 99),
                 "queue_p50_ms": readers.queue_percentile(rc, 50),
                 "queue_p99_ms": readers.queue_percentile(rc, 99)}
        line.update({k: x for k, x in found.items() if x is not None})
        print(json.dumps(line), flush=True)
    drv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
