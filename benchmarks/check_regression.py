"""Benchmark regression gate: measured rows vs the committed baseline.

CI runs `serve_latency.py --smoke --json serve_latency.json`, uploads
the JSON as an artifact (the start of a perf trajectory across PRs), and
then gates the metrics named in `benchmarks/baseline.json`.  A baseline
entry is either a bare number (a lower-is-better CEILING: fail past
`factor` × baseline) or `{"value": v, "gate": "floor"|"ceiling"}` — a
`floor` metric is higher-is-better (throughput, utilization) and fails
BELOW baseline / `factor`.  The default 2x factor is generous on
purpose — shared CI runners are noisy; the gate exists to catch
order-of-magnitude regressions like an accidental re-compile per request,
not 10% drift.  Only
load-robust metrics belong in the baseline: the deadline row's p99 rides
on real-clock scheduler wakeups and swings 10x with CPU contention (its
behavior is asserted by `--smoke` instead), while pow2 p99, flip_ms and
failover_ms stay within ~2x under a fully loaded host.

Measured rows/metrics with NO baseline entry are printed as
"new row, no gate" / "new metric, no gate" — informational, never a
failure and never silently dropped, so a freshly added benchmark row is
visible on its first CI run and gating it later is just a baseline.json
entry.

Run: python benchmarks/check_regression.py measured.json \
         benchmarks/baseline.json [--factor 2.0]
Exit code 1 on any regression; prints a comparison table either way.
"""

from __future__ import annotations

import argparse
import json
import sys


def parse_gate(base) -> tuple:
    """Baseline entry -> (value, direction).  Bare numbers keep the
    historical lower-is-better ceiling; dict entries name their direction."""
    if isinstance(base, dict):
        direction = base.get("gate", "ceiling")
        if direction not in ("floor", "ceiling"):
            raise ValueError(f"unknown gate direction {direction!r}")
        return float(base["value"]), direction
    return float(base), "ceiling"


def gate_ok(got: float, base: float, direction: str, factor: float) -> tuple:
    """(passed, limit): ceiling fails past factor×base, floor below base/factor."""
    if direction == "floor":
        limit = base / factor
        return got >= limit, limit
    limit = factor * base
    return got <= limit, limit


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("measured", help="JSON written by serve_latency --json")
    ap.add_argument("baseline", help="committed benchmarks/baseline.json")
    ap.add_argument("--factor", type=float, default=2.0,
                    help="fail past factor x baseline (default 2.0)")
    ap.add_argument("--analysis", metavar="FILE",
                    help="`repro.analysis --format json` report; injected as "
                    "an 'analysis/findings' row so finding-count creep is "
                    "visible on the same trajectory as the latency rows")
    ap.add_argument("--kernel-resources", metavar="FILE",
                    help="`python -m repro.kernels.resource_model --json` "
                    "rows; merged into the measured set so each kernel's "
                    "static VMEM bytes are CEILING-gated per baseline.json "
                    "(the repo's analogue of the paper's resource table)")
    ap.add_argument("--only", action="append", metavar="ROW",
                    help="gate only these baseline rows (repeatable) — for "
                    "runs that legitimately measure a subset, e.g. the "
                    "kernels CI job gating serve_latency/kernels from a "
                    "--backend pallas run that skips the fleet rows")
    args = ap.parse_args()

    with open(args.measured) as f:
        measured = {row["name"]: row for row in json.load(f)}
    with open(args.baseline) as f:
        baseline = json.load(f)
    if args.only:
        unknown = sorted(set(args.only) - set(baseline))
        if unknown:
            print(f"--only names absent from baseline: {unknown}",
                  file=sys.stderr)
            return 2
        baseline = {k: v for k, v in baseline.items() if k in args.only}

    if args.analysis:
        with open(args.analysis) as f:
            ana = json.load(f)
        # `findings_new` is gated at 0 via baseline.json (any un-baselined
        # finding is a regression); `findings_total`/`findings_baselined`
        # ride along ungated — grandfathering an exception must not fail
        # the latency gate, but its count should stay visible.
        measured["analysis/findings"] = {
            "name": "analysis/findings",
            "findings_new": int(ana.get("new", 0)),
            "findings_total": int(ana.get("total", 0)),
            "findings_baselined": int(ana.get("baselined", 0)),
        }

    if args.kernel_resources:
        with open(args.kernel_resources) as f:
            for row in json.load(f):
                measured[row["name"]] = row

    failures = []
    print(f"{'row':<40} {'metric':<14} {'measured':>12} {'baseline':>12} "
          f"{'limit':>12}  verdict")
    for name, metrics in sorted(baseline.items()):
        row = measured.get(name)
        if row is None:
            failures.append(f"{name}: row missing from measured output")
            print(f"{name:<40} {'-':<14} {'MISSING':>12}")
            continue
        for metric, base_entry in sorted(metrics.items()):
            got = row.get(metric)
            if got is None or not isinstance(got, (int, float)):
                failures.append(f"{name}: metric {metric!r} missing")
                print(f"{name:<40} {metric:<14} {'MISSING':>12}")
                continue
            base, direction = parse_gate(base_entry)
            ok, limit = gate_ok(float(got), base, direction, args.factor)
            verdict = "ok" if ok else "REGRESSION"
            if direction == "floor":
                verdict += " (floor)" if ok else ""
            print(f"{name:<40} {metric:<14} {float(got):>12.4f} "
                  f"{base:>12.4f} {limit:>12.4f}  {verdict}")
            if not ok:
                cmp = "<" if direction == "floor" else ">"
                failures.append(
                    f"{name}.{metric} = {got:.4f} {cmp} {direction} limit "
                    f"{limit:.4f} ({args.factor:g}x of baseline {base:.4f})")
    # rows/metrics measured but absent from the baseline are REPORTED,
    # never gated and never silently dropped: a freshly added benchmark
    # row shows up here on its first CI run, and committing a baseline
    # entry for it later turns the gate on — no ordering dance between
    # "add the row" and "hand-edit baseline.json".
    for name, row in sorted(measured.items()):
        gated = baseline.get(name)
        new_metrics = sorted(
            k for k, v in row.items()
            if k != "name" and isinstance(v, (int, float))
            and not isinstance(v, bool)
            and (gated is None or k not in gated))
        label = "new row, no gate" if gated is None else "new metric, no gate"
        for metric in new_metrics:
            print(f"{name:<40} {metric:<14} {float(row[metric]):>12.2f} "
                  f"{'-':>12} {'-':>12}  {label}")
    if failures:
        print("\nregression gate FAILED:", file=sys.stderr)
        for f_ in failures:
            print(f"  - {f_}", file=sys.stderr)
        return 1
    print("\nregression gate ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
