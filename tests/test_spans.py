"""Spans: `SLOTracker.span` and the stages the serving path times with it.

All timing via VirtualClock (advanced inside the spans), zero sleeps; the
compile charge is read from JAX's own compile events.
"""

import jax
import jax.numpy as jnp
import pytest

from harness import ServingHarness
from repro.serve import VirtualClock
from repro.serve.slo import (LatencyStats, SLOTracker, bin_of, bin_value_ms,
                             snapshot_delta)


def _x(rows, seed=0, m=32):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, m))


@pytest.mark.parametrize("ms", [1e-3, 0.0125, 0.7, 3.0, 41.5, 2500.0])
def test_a_bin_reads_within_2_2_percent_of_its_samples(ms):
    assert abs(bin_value_ms(bin_of(ms)) - ms) <= 0.022 * ms
    assert bin_of(0.0) == 0 and bin_value_ms(0) == 0.0


def test_a_snapshot_difference_sees_only_what_was_recorded_between():
    s = LatencyStats(window=4)
    for v in (0.5, 9.0, 120.0):
        s.record(v)
    a = s.snapshot()
    between = (0.5, 2.0, 2.0, 30.0, 30.0, 7000.0)
    for v in between:
        s.record(v)
    d = snapshot_delta(a, s.snapshot())
    only = LatencyStats()
    for v in between:
        only.record(v)
    want = only.snapshot()
    assert d["bins"] == want["bins"]
    assert d["count"] == 6 and d["total_ms"] == pytest.approx(want["total_ms"])
    assert "max_ms" not in d


def test_nested_spans_record_exact_durations_under_virtual_clock():
    clk = VirtualClock()
    tr = SLOTracker(clock=clk)
    for _ in range(2):
        with tr.span("outer"):
            clk.advance(2.0)
            with tr.span("inner"):
                clk.advance(3.0)
            clk.advance(1.0)
    outer, inner = tr.stage("outer"), tr.stage("inner")
    assert (outer.count, outer.total_ms, outer.percentile(100)) == (2, 12.0, 6.0)
    assert (inner.count, inner.total_ms, inner.percentile(100)) == (2, 6.0, 3.0)
    assert set(tr.stages()) == {"outer", "inner"}


def test_a_compile_is_charged_to_the_open_spans_only():
    tr = SLOTracker(clock=VirtualClock())
    x = jnp.arange(13.0)
    f = jax.jit(lambda v: v * 3.0 + 1.0)
    g = jax.jit(lambda v: v - 5.0)
    with tr.span("parent"):
        with tr.span("child"):
            jax.block_until_ready(f(x))         # a new program: compiles
        with tr.span("sibling"):
            jax.block_until_ready(f(x))         # the same: no compile
    jax.block_until_ready(g(x))                 # compiles, outside any span
    st = tr.stages()
    assert st["child"]["compiles"] == 1 and st["parent"]["compiles"] == 1
    assert st["sibling"]["compiles"] == 0
    assert 0.0 < st["child"]["compile_ms"] == st["parent"]["compile_ms"]


def test_a_flush_of_two_dr_tickets_records_its_stages():
    with ServingHarness() as h:
        t1 = h.submit(_x(3, seed=1), max_delay_ms=4.0)
        h.clock.advance(1.0)
        t2 = h.submit(_x(5, seed=2), max_delay_ms=4.0)
        h.advance(3.0)
        assert t1.done and t2.done
        st = h.service.metrics()["stages"]
        for stage in ("poll", "flush.dr", "flush.coalesce", "serve_rows",
                      "flush.resolve"):
            assert st[stage]["count"] == 1, stage
        # every compile of the flush lands in one of its three parts
        parts = sum(st[k]["compiles"] for k in
                    ("flush.coalesce", "serve_rows", "flush.resolve"))
        assert st["poll"]["compiles"] == st["flush.dr"]["compiles"] == parts
        slo = h.service.slo.snapshot()["m"]
        delays = [c["queue_delay"] for c in slo.values()]
        assert sum(d["count"] for d in delays) == 2
        assert sorted(d["total_ms"] for d in delays) == [3.0, 4.0]


def test_decode_steps_drained_together_wait_for_the_ones_before():
    """Four steps flushed at their deadline run one after another; each
    one's queue delay runs to its own start.  The deadline verdict stays
    on the flush start: all four are met."""
    with ServingHarness() as h:
        def step():
            h.clock.advance(1.0)                # one step of device time
            return "tok"
        ts = [h.submit_step("lm", "decode", step, max_delay_ms=5.0)
              for _ in range(4)]
        h.advance(5.0)
        assert [t.result() for t in ts] == ["tok"] * 4
        cell = h.service.slo.cell("lm", "decode")
        assert [cell.queue_delay.percentile(q) for q in (25, 50, 75, 100)] \
            == [5.0, 6.0, 7.0, 8.0]
        assert (cell.deadline_met, cell.deadline_missed) == (4, 0)
        st = h.service.metrics()["stages"]["step.decode"]
        assert (st["count"], st["total_ms"]) == (4, 4.0)


def test_train_while_serve_and_promote_are_timed():
    with ServingHarness() as h:
        for seed in range(3):
            h.service.serve_and_update("m", _x(8, seed=seed))
        h.service.promote("m")
        st = h.service.metrics()["stages"]
        assert st["serve_and_update"]["count"] == 3
        assert st["tws.lock_wait"]["count"] == 3
        assert st["promote"]["count"] == 1
        # the fused program was built once, through the service's cache
        assert st["cache.build"]["count"] == h.service.cache.misses
        assert st["serve_and_update"]["compiles"] >= 1


def test_the_spans_lie_on_the_profilers_host_plane(tmp_path):
    """A DR flush under the profiler, on the CPU: its `repro.*` spans are
    on the `/host:CPU` plane, where the benchmark's trace reader finds
    host events."""
    from bench import trace
    from repro.dr import DRModel, EASIStage, RPStage
    from repro.serve import DRService

    model = DRModel(stages=(RPStage(32, 16), EASIStage.rotation(16, 8)),
                    block_size=4)
    svc = DRService()
    svc.register("m", model, model.init(jax.random.PRNGKey(0)))
    x = _x(5, seed=1)
    with jax.profiler.trace(str(tmp_path)):
        tickets = [svc.submit("m", x), svc.submit("m", x[:3])]
        svc.flush()
        jax.block_until_ready([t.result() for t in tickets])
    _, host = trace.read_planes(trace.find_xplane(str(tmp_path)))
    names = [e.name for evs in host.values() for e in evs]
    for stage in ("flush.dr", "flush.coalesce", "serve_rows",
                  "flush.resolve"):
        assert names.count(f"repro.{stage}") == 1, stage
