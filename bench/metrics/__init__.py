"""Per-layer metric readers: `bench/metrics/<metric name>.py`, each with one
function `read(ctx) -> float | None`.  A reader that finds nothing to read
returns None and the metric is left out of the result line.

`ctx` holds: "record" (the driver's host record of the window),
"counters" (the program's counters at the window's start and end),
"trace_counters" (the same at the trace's start and stop), "trace" (a
`bench.trace.Summary`), "window_compiles", "config", "spec" and
"device_kind".  `readers.py` holds the arithmetic readers share.
"""
