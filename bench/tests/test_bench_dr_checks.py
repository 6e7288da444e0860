"""The DR serving cells' check: a sound run passes, the lower-precision
control fails, and a run whose timed path alters one answer fails; and the
saturate cell's closed loop.

Each drives a whole run of the cell past the harness's look for a chip,
on the CPU (Pallas kernels interpreted) with buckets of 8..64 rows.
"""

from __future__ import annotations

import numpy as np
import pytest

from bench.tests.conftest import SMALL_DR

STEADY = {"rate_per_s": 40.0, "warmup_s": 0.2}
# four requests in flight, of sizes that make groups of up to four batches
SATURATE = {"outstanding": 4,
            "rows": {"dist": "choice", "values": [3, 8, 21, 50]}}


def _alter_one_answer(monkeypatch):
    """Break the engine where answers are produced: every third device
    batch it runs comes back with one value moved by 1."""
    from repro.serve import engine

    real = engine.DRService._serve_rows
    calls = []

    def broken(self, snap, x):
        y = real(self, snap, x)
        calls.append(1)
        if len(calls) % 3 == 0:
            y = y.at[0, 0].add(1.0)
        return y

    monkeypatch.setattr(engine.DRService, "_serve_rows", broken)


def test_steady_sound_run_is_correct_and_reports_its_metrics(cell_run):
    out = cell_run("dr_paper.steady", spec=STEADY, config=SMALL_DR)
    assert out["correct"], out["checked"]
    assert out["attempted"] == 20 and out["failed"] == 0
    assert set(out["metrics"]) == {"req_p95_ms", "setup_s"}
    assert list(out)[-1] == "checked"


def test_steady_control_in_lower_precision_fails(cell_run, monkeypatch):
    from bench.drivers import dr_serve

    real = dr_serve.Driver.check
    monkeypatch.setattr(dr_serve.Driver, "check",
                        lambda self, mode="program": real(self, "control"))
    out = cell_run("dr_paper.steady", spec=STEADY, config=SMALL_DR)
    assert not out["correct"]
    assert out["checked"]["err_out"]["value"] > 3 * 6.5e-3


@pytest.mark.parametrize("workload,spec", [("dr_paper.steady", STEADY),
                                           ("dr_paper.saturate", SATURATE)])
def test_an_answer_altered_where_it_is_produced_fails(cell_run, monkeypatch,
                                                      workload, spec):
    _alter_one_answer(monkeypatch)
    out = cell_run(workload, spec=spec, config=SMALL_DR)
    assert not out["correct"]
    assert out["checked"]["err_out"]["value"] > 0.1


def test_closed_loop_traffic_is_refused():
    from bench.drivers import Context, dr_serve

    spec = {"driver": "dr_serve", "arrival": "closed", "outstanding": 8}
    with pytest.raises(ValueError, match="open-loop"):
        dr_serve.Driver(Context(config={}, spec=spec, seed=1, seconds=1.0,
                                devices=[]))


def test_saturate_sound_run_is_correct_and_reports_its_metrics(cell_run):
    out = cell_run("dr_paper.saturate", spec=SATURATE, config=SMALL_DR)
    assert out["correct"], out["checked"]
    assert out["attempted"] > 4 and out["failed"] == 0
    assert set(out["metrics"]) == {"rows_per_s", "setup_s"}
    assert out["metrics"]["rows_per_s"]["value"] > 0
    assert list(out)[-1] == "checked"


def test_saturate_control_in_lower_precision_fails(cell_run, monkeypatch):
    from bench.drivers import dr_serve

    real = dr_serve.Driver.check
    monkeypatch.setattr(dr_serve.Driver, "check",
                        lambda self, mode="program": real(self, "control"))
    out = cell_run("dr_paper.saturate", spec=SATURATE, config=SMALL_DR)
    assert not out["correct"]
    assert out["checked"]["err_out"]["value"] > 3 * 6.5e-3


class _CountingScheduler:
    """Answers at once; counts the requests sent whose answer the client
    has not taken yet."""

    def __init__(self):
        self.in_flight = 0
        self.at_submit = []

    def submit(self, name, x):
        self.at_submit.append(self.in_flight)
        self.in_flight += 1
        return _Answer(self, x.shape[0])


class _Answer:
    def __init__(self, sched, rows):
        self.sched, self.rows, self.taken = sched, rows, False

    def wait(self, timeout=None):
        return True

    def result(self):
        assert not self.taken
        self.taken = True
        self.sched.in_flight -= 1
        return np.zeros((self.rows, 8), np.float32)


def test_the_closed_loop_keeps_exactly_outstanding_requests_in_flight():
    from bench import traffic
    from bench.drivers import Context, Window, dr_saturate

    spec = dict(traffic.load("saturate"), outstanding=8)
    drv = dr_saturate.Driver(Context(config={}, spec=spec, seed=5,
                                     seconds=0.2, devices=[]))
    drv.sched = _CountingScheduler()
    drv.sys = type("S", (), {"pool": np.zeros((4096, 32), np.float32)})()
    sched = traffic.schedule(spec, 0.2, ("rows",))
    rec = drv._drive(sched, Window(0.2), keep=False)
    seen = drv.sched.at_submit
    assert len(seen) > 100 and rec["attempted"] == len(seen)
    # the first eight fill the slots; each later one replaces an answer
    assert seen[:8] == list(range(8)) and set(seen[8:]) == {7}
    assert drv.sched.in_flight == 0 and rec["failed"] == 0
    assert 0 < rec["rows_in_window"] <= rec["rows"].sum()


def test_open_loop_traffic_is_refused_by_the_closed_loop():
    from bench.drivers import Context, dr_saturate

    spec = {"driver": "dr_saturate", "arrival": "poisson", "rate_per_s": 4}
    with pytest.raises(ValueError, match="closed-loop"):
        dr_saturate.Driver(Context(config={}, spec=spec, seed=1, seconds=1.0,
                                   devices=[]))
