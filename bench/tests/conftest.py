"""Shared fixtures for the benchmark's CPU tests.

`cell_run` drives a whole run of a cell through `bench.run.execute`, past
the harness's look for a chip, on the CPU at a size a test run holds (the
Pallas kernels in interpret mode).  The persistent compilation cache is
left alone: the run's settings of it are undone after each test.
"""

from __future__ import annotations

import copy
import time

import pytest

SMALL_DR = {"buckets": {"min_bucket": 8, "max_bucket": 64},
            "data": {"pool_rows": 8192}}
SMALL_LM = {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "num_hidden_layers": 2,
            "intermediate_size": 128, "vocab_size": 512}


@pytest.fixture
def cell_run(monkeypatch):
    import jax

    from bench import run

    monkeypatch.setattr("repro.launch.compile_cache.use_compile_cache",
                        lambda: None)
    before = jax.config.jax_persistent_cache_min_compile_time_secs

    def go(workload, seconds=0.5, seed=2 ** 40 + 7, spec=None, config=None,
           trace=False, tmp_path=None):
        res = run.resolve(run.load_benchmark(), workload)
        res = copy.deepcopy(res)
        res["spec"].update(spec or {})
        res["config"].update(config or {})
        kw = {}
        if trace:
            kw = {"trace_dir": tmp_path / "trace", "trace_s": 0.2}
        out, _ = run.execute(res, seed, seconds, trace, jax.devices()[:1],
                             t_start=time.perf_counter(), **kw)
        return out

    yield go
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)
