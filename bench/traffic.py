"""The one traffic generator: turns a traffic file's parameters into a
schedule.

Every seed gets the same schedule: sizes and gaps between arrivals are
the distribution's quantiles at (i + 1/2) / n, in one shuffled order fixed
by `ORDER_SEED`.  The seed changes what the requests carry (payload rows,
prompt ids) and the weights, not the work or its timing.  With the order
drawn from the seed as well, runs of six seeds spread by 27-32% in the
chat cell's time to first token and gap between tokens, against a few
percent between two runs of one seed: which long prompts land in which
burst is what sets a tail.

A traffic file (`traffic/<name>.json`) holds:

  driver         the path it drives: `drivers/<driver>.py`
  arrival        "poisson" (open loop; `rate_per_s`) or "closed"
                 (`outstanding` requests always in flight)
  <size keys>    each a size distribution, named by the driver:
                 {"dist": "lognormal", "median", "sigma", "min", "max"},
                 {"dist": "choice", "values": [...]} (equal shares), or
                 {"dist": "fixed", "value"}
  trace_s        seconds the `--trace 1` run traces (default 4)
  anything else  the driver's own settings (scheduler delays, ...)
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
from statistics import NormalDist
from typing import Any, Dict, Optional

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"

# a closed loop cycles through this many sizes (the same multiset for
# every seed); an open loop draws one size per arrival
CLOSED_LOOP_SIZES = 4096
ORDER_SEED = 0


def load(name: str, directory: pathlib.Path = TRAFFIC_DIR) -> Dict[str, Any]:
    path = directory / f"{name}.json"
    with open(path) as f:
        spec = json.load(f)
    for key in ("driver", "arrival"):
        if key not in spec:
            raise ValueError(f"{path}: no {key!r}")
    if spec["arrival"] not in ("poisson", "closed"):
        raise ValueError(f"{path}: arrival {spec['arrival']!r} is not "
                         f"'poisson' or 'closed'")
    return spec


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per named stream of one seed (any seed up
    to 2**63, far past 32 bits)."""
    tag = [int(b) for b in stream.encode()]
    return np.random.default_rng(np.random.SeedSequence([int(seed), *tag]))


def quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    """`n` sizes of `dist` at the quantiles (i + 1/2) / n, ascending."""
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    if kind == "choice":
        values = np.asarray(dist["values"], np.int64)
        return np.sort(values[np.arange(n) * len(values) // n])
    if kind == "lognormal":
        std = NormalDist()
        z = np.array([std.inv_cdf((i + 0.5) / n) for i in range(n)])
        x = np.round(float(dist["median"]) * np.exp(float(dist["sigma"]) * z))
        return np.clip(x, int(dist["min"]), int(dist["max"])).astype(np.int64)
    raise ValueError(f"unknown size distribution {kind!r}")


@dataclasses.dataclass(frozen=True)
class Schedule:
    """What one run offers.  `due_s` is each arrival's due time from the
    window's start (open loop; None for a closed loop, whose requests are
    sent as earlier ones finish).  `sizes` maps each size key of the
    traffic file to one size per request (closed loop: a cycle)."""
    arrival: str
    due_s: Optional[np.ndarray]
    sizes: Dict[str, np.ndarray]
    outstanding: int

    def __len__(self) -> int:
        return len(next(iter(self.sizes.values())))


def schedule(spec: Dict[str, Any], seconds: float,
             size_keys: tuple) -> Schedule:
    """The schedule of `spec` for a window of `seconds`."""
    rng = rng_for(ORDER_SEED, "schedule")
    if spec["arrival"] == "poisson":
        rate = float(spec["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        # exponential gaps at their quantiles, scaled so that they sum to
        # the window: exactly n arrivals due inside it, at the mean rate
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        gaps = gaps / gaps.sum() * seconds
        due = np.cumsum(rng.permutation(gaps)) - gaps.min() / 2
        outstanding = 0
    else:
        n = CLOSED_LOOP_SIZES
        due = None
        outstanding = int(spec["outstanding"])
        if outstanding < 1:
            raise ValueError("a closed loop needs outstanding >= 1")
    sizes = {k: rng.permutation(quantiles(spec[k], n)) for k in size_keys}
    return Schedule(arrival=spec["arrival"], due_s=due, sizes=sizes,
                    outstanding=outstanding)
