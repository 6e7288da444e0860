"""The chip benchmark: one command, cells found by name in BENCHMARK.json.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Layout (every piece that belongs to one configuration, traffic mix or
per-layer metric is a file of its own, found by its name):

  configs/<config>.json    sizes and precision of one configuration
  traffic/<traffic>.json   parameters of one traffic mix, read by
                           `traffic.py`; its "driver" names the path it
                           drives (`drivers/<driver>.py`)
  metrics/<metric>.py      one per-layer metric's reader
  reference/<family>.py    the plain float32 reference of a model family
  run.py                   the harness: set-up, window, check, result line
  sweep.py, calibrate.py   tools run by hand on the chip: the knee of an
                           open-loop cell; the readings its limits are
                           set from
"""
