"""Read what the check compares, for the program and for its control, on
many seeds, to set each limit between the two.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 8 \
        [--modes program,control]

One process, on the accelerator: for each seed the cell is set up, driven
for `--seconds` at its own load, and checked in each mode: "program"
(what the timed path produced), "control" (the reference, in the precision
below the configuration's, put in the program's place) and, for
train-while-serve, "half_batch" (the reference folding half of each
block) and "stale" (the reference serving every block with the initial
state while its promotes go through).  Each seed prints one JSON line of
readings.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run  # noqa: E402  (bench/run.py, which puts the repo on sys.path)


def readings(resolved, seed: int, seconds: float, modes, devices):
    """{mode: {number: value}} for one seed of the cell."""
    from bench.drivers import Context, Window

    ctx = Context(config=resolved["config"],
                  spec=resolved["spec"], seed=seed, seconds=seconds,
                  devices=list(devices))
    drv = run.load_driver(resolved["spec"]["driver"])(ctx)
    drv.setup()
    drv.run(Window(seconds))
    drv.close()
    out = {"seed": seed, "attempted": drv.record["attempted"],
           "failed": drv.record["failed"]}
    for mode in modes:
        try:
            out[mode] = {c.name: c.value for c in drv.check(mode)}
        except Exception as e:  # noqa: BLE001 — a control that crashes fails
            out[mode] = {"error": repr(e)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--modes", default="program,control")
    args = ap.parse_args(argv)
    import jax

    from repro.launch.compile_cache import use_compile_cache

    devices = jax.devices()
    if devices[0].platform == "cpu":
        print("calibrate: no accelerator", file=sys.stderr)
        return 2
    use_compile_cache()
    resolved = run.resolve(run.load_benchmark(), args.workload)
    chips = int(resolved["cell"]["chips"])
    for s in args.seeds.split(","):
        t = time.perf_counter()
        line = readings(resolved, int(s), args.seconds,
                        args.modes.split(","), devices[:chips])
        line["s"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
