"""The DR serving cells' check: a sound run passes, the lower-precision
control fails, and a run whose timed path alters one answer fails.

Each drives a whole run of the cell past the harness's look for a chip,
on the CPU (Pallas kernels interpreted) with buckets of 8..64 rows.
"""

from __future__ import annotations

import pytest

from bench.tests.conftest import SMALL_DR

STEADY = {"rate_per_s": 40.0, "warmup_s": 0.2}


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


@pytest.mark.parametrize("workload,spec", [("dr_paper.steady", STEADY)])
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
