"""The harness's pure parts: schedules, statistics, lateness, finding a
cell's files by name, and refusing to run without an accelerator."""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from bench import stats, traffic, waveform
from bench import run as bench_run

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


# ---- schedules and payloads -------------------------------------------------

@pytest.mark.parametrize("name", ["steady", "chat", "train_while_serve",
                                  "saturate"])
def test_every_seed_gets_the_same_schedule(name):
    spec = traffic.load(name)
    keys = tuple(k for k, v in spec.items()
                 if isinstance(v, dict) and "dist" in v)
    a = traffic.schedule(spec, 20.0, keys)
    b = traffic.schedule(spec, 20.0, keys)
    for k in keys:
        assert np.array_equal(a.sizes[k], b.sizes[k])
        assert np.array_equal(np.sort(a.sizes[k]),
                              traffic.quantiles(spec[k], len(a)))
    if spec["arrival"] == "poisson":
        assert np.array_equal(a.due_s, b.due_s)
        assert len(a) == round(spec["rate_per_s"] * 20.0)
        assert 0 < a.due_s.min() and a.due_s.max() < 20.0
        assert np.all(np.diff(a.due_s) > 0)
        # the gaps are shuffled, not sorted
        assert not np.all(np.diff(np.diff(a.due_s)) >= 0)


def test_seeds_change_what_requests_carry_not_the_work():
    from bench.drivers import Context

    big = 2 ** 62 + 12345                 # far past 32 bits
    ctx = [Context(config={}, spec={}, seed=s, seconds=1.0,
                   devices=[]) for s in (big, big, big + 1)]
    draws = [c.rng("prompts").integers(0, 49152, 64) for c in ctx]
    assert np.array_equal(draws[0], draws[1])
    assert not np.array_equal(draws[0], draws[2])
    keys = [int(c.jax_key("weights")[1]) for c in ctx]
    assert keys[0] == keys[1] != keys[2]


def test_lognormal_sizes_keep_their_median_and_bounds():
    spec = traffic.load("steady")
    q = traffic.quantiles(spec["rows"], 10001)
    assert np.median(q) == 8 and q.min() >= 1 and q.max() <= 1024
    assert 12.0 < q.mean() < 14.5          # lognormal mean 8*e^(1/2) = 13.2


def test_payloads_are_seeded_waveform_rows():
    a = waveform.pool(traffic.rng_for(7, "payload"), 4096, 32)
    b = waveform.pool(traffic.rng_for(7, "payload"), 4096, 32)
    c = waveform.pool(traffic.rng_for(8, "payload"), 4096, 32)
    assert a.dtype == np.float32 and a.shape == (4096, 32)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    # centred, one global scale: mean per-feature variance 1
    assert np.abs(a.mean(axis=0)).max() < 1e-4
    assert np.mean(a.var(axis=0)) == pytest.approx(1.0, rel=1e-3)


# ---- statistics over the whole window ---------------------------------------

def test_percentiles_are_over_every_request_not_medians_of_chunks():
    lat = np.concatenate([np.full(990, 1.0), np.full(10, 100.0)])
    np.random.default_rng(0).shuffle(lat)
    chunks = [stats.percentile(c, 99) for c in np.split(lat, 10)]
    assert stats.percentile(lat, 99) == pytest.approx(
        float(np.percentile(lat, 99)))
    assert stats.percentile(lat, 99.5) > 1.0
    assert float(np.median(chunks)) != stats.percentile(lat, 99)
    assert stats.percentile([], 99) is None


def test_rates_take_all_the_work_over_all_the_window():
    assert stats.rate(4500, 45.0) == 100.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


# ---- lateness and latency run from the due time -----------------------------

class _SlowScheduler:
    """Answers at once, but each submit takes 20 ms of the generator."""

    def submit(self, name, x):
        time.sleep(0.02)
        from repro.serve.batching import Ticket
        t = Ticket(int(x.shape[0]))
        t._resolve(np.zeros((x.shape[0], 8), np.float32))
        return t


def test_open_loop_times_requests_from_their_due_time():
    from bench.drivers import Context, Window
    from bench.drivers import dr_serve

    spec = dict(traffic.load("steady"), rate_per_s=200.0)
    ctx = Context(config={}, spec=spec, seed=5, seconds=0.2,
                  devices=[])
    drv = dr_serve.Driver(ctx)
    drv.sched = _SlowScheduler()
    drv.sys = type("S", (), {"pool": np.zeros((4096, 32), np.float32)})()
    sched = traffic.schedule(spec, 0.2, ("rows",))
    rec = drv._open_loop(sched, Window(0.2), keep=False)
    late = np.asarray(rec["late_ms"])
    lat = np.asarray(rec["latency_ms"])
    assert rec["attempted"] == 40 and rec["failed"] == 0
    # 40 requests due over 0.2 s, each taking 20 ms to send: the generator
    # falls behind, and every request's latency counts that wait
    assert late[-1] > 500
    assert np.all(lat >= late + 20.0 - 1.0)
    assert late[-1] > late[0]


# ---- files found by name ----------------------------------------------------

def test_every_cell_resolves_its_files_by_name():
    bench = bench_run.load_benchmark()
    for w in bench["workloads"]:
        res = bench_run.resolve(bench, w["name"])
        assert res["config"]["family"] in ("dr", "lm")
        assert (BENCH / "drivers" / f"{res['spec']['driver']}.py").is_file()
        assert any(m["name"] == "setup_s" for m in res["end_to_end"])
        assert len(res["end_to_end"]) >= 2 and res["per_layer"]
        for m in res["per_layer"]:
            assert callable(bench_run.load_reader(m["name"]))


def test_benchmark_json_follows_its_own_rules():
    bench = bench_run.load_benchmark()
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_a_dropped_in_traffic_file_and_metric_need_no_edit(tmp_path):
    bench_dir = tmp_path / "bench"
    for sub in ("traffic", "metrics", "drivers"):
        shutil.copytree(BENCH / sub, bench_dir / sub)
    spec = dict(traffic.load("steady"), rate_per_s=50.0)
    (bench_dir / "traffic" / "burst.json").write_text(json.dumps(spec))
    (bench_dir / "metrics" / "late_max_ms.burst.py").write_text(
        "def read(ctx):\n    return max(ctx['record']['late_ms'])\n")
    bench = bench_run.load_benchmark()
    bench["workloads"].append({"name": "dr_paper.burst", "config": "dr_paper",
                               "traffic": "burst", "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("dr_paper.burst")
    bench["per_layer"].append({"name": "late_max_ms.burst", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "load generator",
                               "moves": "req_p95_ms",
                               "workloads": ["dr_paper.burst"]})
    res = bench_run.resolve(bench, "dr_paper.burst", bench_dir)
    assert res["spec"]["rate_per_s"] == 50.0
    assert [m["name"] for m in res["per_layer"]] == ["late_max_ms.burst"]
    read = bench_run.load_reader("late_max_ms.burst", bench_dir)
    assert read({"record": {"late_ms": [1.0, 3.0]}}) == 3.0


def test_an_unknown_driver_is_refused(tmp_path):
    bench_dir = tmp_path / "bench"
    for sub in ("traffic", "metrics", "drivers"):
        shutil.copytree(BENCH / sub, bench_dir / sub)
    spec = dict(traffic.load("steady"), driver="nothing")
    (bench_dir / "traffic" / "steady.json").write_text(json.dumps(spec))
    with pytest.raises(FileNotFoundError):
        bench_run.resolve(bench_run.load_benchmark(), "dr_paper.steady",
                          bench_dir)


# ---- no accelerator, no result ----------------------------------------------

def test_without_an_accelerator_run_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "dr_paper.steady", "--seed", str(2 ** 40), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "accelerator" in p.stderr
