"""Deadline scheduler tests — all timing via VirtualClock, zero sleeps.

Covers: the clock protocol, deadline-vs-fill flush triggers and ordering,
partial-bucket flushes reusing the bucketed compile universe, the threaded
event loop (wakeup on advance, shutdown drains), per-bucket SLO histogram
correctness under virtual time, LM prefill/decode through the shared
admission queue, and registry fault injection under concurrency.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness as harness_mod
from harness import ServingHarness, small_model
from repro.serve import (DRService, DeadlineScheduler, ModelRegistry,
                         MonotonicClock, QueueFull, SchedulerClosed,
                         VirtualClock)
from repro.serve.batching import MicroBatcher
from repro.serve.slo import (LatencyStats, SLOTracker, snapshot_delta,
                             snapshot_percentile)

jax.config.update("jax_enable_x64", False)


def _x(rows, seed=0, m=32):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, m))


class TestClock:
    def test_monotonic_now_advances(self):
        c = MonotonicClock()
        a, b = c.now(), c.now()
        assert b >= a

    def test_virtual_advance_and_now(self):
        c = VirtualClock(start_ms=100.0)
        assert c.now() == 100.0
        assert c.advance(2.5) == 102.5
        assert c.now() == 102.5

    def test_virtual_rejects_backwards(self):
        with pytest.raises(ValueError):
            VirtualClock().advance(-1.0)

    def test_virtual_advance_wakes_parked_waiter(self):
        c = VirtualClock()
        cond = threading.Condition()
        woke = threading.Event()

        def park():
            with cond:
                c.wait(cond, timeout_ms=10.0)   # timeout ignored: virtual
            woke.set()

        th = threading.Thread(target=park, daemon=True)
        th.start()
        while not cond._waiters:                # wait for the park, no sleep
            pass
        c.advance(1.0)
        assert woke.wait(5.0)
        th.join(5.0)

    def test_no_sleep_anywhere_in_these_tests(self):
        """The harness' contract: tests advance time, they never sleep."""
        for path in (__file__, harness_mod.__file__):
            src = open(path).read()
            assert ("sleep" + "(") not in src, path      # no sleep CALLS


class TestDeadlineFlush:
    """Loopless mode: advance() pumps poll() synchronously."""

    def test_single_subbucket_request_answered_at_deadline(self):
        """Acceptance: one lone request, max_delay_ms=D, no other traffic —
        answered exactly after advance(D)."""
        D = 25.0
        with ServingHarness(threaded=False) as h:
            x = _x(3, seed=1)
            t = h.submit(x, max_delay_ms=D)
            assert h.poll() == 0 and not t.done          # nothing due at t=0
            assert h.advance(D - 0.01) == 0 and not t.done
            assert h.advance(0.01) == 1 and t.done
            np.testing.assert_allclose(np.asarray(t.result()),
                                       np.asarray(h.expect(x)),
                                       rtol=1e-6, atol=1e-7)

    def test_default_deadline_applies(self):
        with ServingHarness(default_max_delay_ms=7.0) as h:
            t = h.submit(_x(2))
            h.advance(6.99)
            assert not t.done
            h.advance(0.01)
            assert t.done

    def test_explicit_deadline_overrides_default(self):
        with ServingHarness(default_max_delay_ms=1000.0) as h:
            t = h.submit(_x(2), max_delay_ms=2.0)
            h.advance(2.0)
            assert t.done

    def test_bucket_fill_flushes_before_deadline(self):
        """flush_rows reached → flush NOW, deadline untouched."""
        with ServingHarness(flush_rows=8, default_max_delay_ms=1000.0) as h:
            t1 = h.submit(_x(5, seed=1))
            assert h.poll() == 0 and not t1.done         # 5 < 8 rows
            t2 = h.submit(_x(3, seed=2))
            assert h.poll() >= 1                          # 8 rows: due at t=0
            assert t1.done and t2.done
            assert h.now() == 0.0                         # no time passed

    def test_oldest_deadline_governs_the_bucket(self):
        """A later ticket's longer deadline can't delay the oldest's."""
        with ServingHarness() as h:
            t1 = h.submit(_x(3, seed=1), max_delay_ms=10.0)
            t2 = h.submit(_x(2, seed=2), max_delay_ms=1000.0)
            b0 = h.service.batches_run
            h.advance(10.0)
            # both coalesce into the flush the OLDEST deadline triggered
            assert t1.done and t2.done
            assert h.service.batches_run - b0 == 1

    def test_deadline_flush_ordering_across_keys(self):
        """Groups flush in deadline order as time advances; undue groups
        stay queued (selective drain)."""
        with ServingHarness() as h:
            h.service.register("m2", h.model, h.state)
            ta = h.submit(_x(2, seed=1), max_delay_ms=5.0)
            tb = h.submit(_x(2, seed=2), name="m2", max_delay_ms=15.0)
            h.advance(5.0)
            assert ta.done and not tb.done               # only "m" was due
            h.advance(10.0)
            assert tb.done

    def test_partial_bucket_flush_pads_to_bucket(self):
        with ServingHarness() as h:                      # min_bucket=4
            t = h.submit(_x(3, seed=3), max_delay_ms=1.0)
            h.advance(1.0)
            assert t.done and t.result().shape == (3, 8)
            assert h.service.padded_rows == 1            # 3 rows → bucket 4
            assert h.service.cache.misses == 1

    def test_compile_counts_match_demand_flush(self):
        """Acceptance: deadline flushes reuse the same bucketed programs —
        compile counts per bucket policy are unchanged from PR 2 (one per
        touched bucket, 4 for these sizes)."""
        sizes = [3, 7, 1, 5, 12, 2, 9, 30, 4]            # buckets 4, 8, 16, 32
        with ServingHarness() as h:
            for i, s in enumerate(sizes):
                t = h.submit(_x(s, seed=i), max_delay_ms=1.0)
                h.advance(1.0)                           # each flushes alone
                np.testing.assert_allclose(np.asarray(t.result()),
                                           np.asarray(h.expect(_x(s, seed=i))),
                                           rtol=1e-6, atol=1e-7)
            assert h.service.cache.misses == 4

    def test_next_deadline_tracks_oldest(self):
        with ServingHarness() as h:
            h.service.register("m2", h.model, h.state)
            assert h.scheduler.next_deadline() is None
            h.submit(_x(2, seed=1), max_delay_ms=50.0)
            h.submit(_x(2, seed=2), name="m2", max_delay_ms=20.0)
            assert h.scheduler.next_deadline() == 20.0
            h.advance(20.0)                              # flushes only "m2"
            assert h.scheduler.next_deadline() == 50.0
            h.advance(30.0)
            assert h.scheduler.next_deadline() is None

    def test_wake_lead_flushes_early_and_counts_met(self):
        """wake_lead_ms makes a group due that many ms before its deadline
        — the real-clock anti-epsilon-miss knob, pinned virtually."""
        with ServingHarness(wake_lead_ms=2.0) as h:
            t = h.submit(_x(2), max_delay_ms=10.0)
            assert h.advance(7.9) == 0 and not t.done    # 10 - 7.9 > lead
            assert h.advance(0.1) == 1 and t.done        # due at D - lead
            m = h.service.metrics()
            assert (m["deadline_met"], m["deadline_missed"]) == (1, 0)

    def test_backpressure_passes_through(self):
        with ServingHarness(max_queue=8) as h:
            h.submit(_x(6, seed=1))
            with pytest.raises(QueueFull):
                h.submit(_x(3, seed=2))
            h.advance(10.0)                              # drains the queue
            h.submit(_x(3, seed=2))                      # admitted again

    def test_demand_flush_composes_with_scheduler(self):
        """A manual service.flush() resolves everything; the scheduler's
        next poll finds nothing due — no double-resolution."""
        with ServingHarness() as h:
            t = h.submit(_x(2), max_delay_ms=100.0)
            h.service.flush()
            assert t.done
            assert h.advance(100.0) == 0


@pytest.mark.slow
class TestThreadedLoop:
    """The real background event loop against the virtual clock."""

    def test_advance_wakes_loop_and_resolves(self):
        with ServingHarness(threaded=True, default_max_delay_ms=8.0) as h:
            x = _x(3, seed=1)
            t = h.submit(x)
            h.advance(8.0)
            assert t.wait(10.0)
            np.testing.assert_allclose(np.asarray(t.result()),
                                       np.asarray(h.expect(x)),
                                       rtol=1e-6, atol=1e-7)

    def test_fill_flushes_without_time_passing(self):
        with ServingHarness(threaded=True, flush_rows=8,
                            default_max_delay_ms=1e6) as h:
            t1 = h.submit(_x(5, seed=1))
            t2 = h.submit(_x(3, seed=2))                 # fills to 8 rows
            assert t1.wait(10.0) and t2.wait(10.0)
            assert h.now() == 0.0

    def test_shutdown_drains_queue(self):
        h = ServingHarness(threaded=True, default_max_delay_ms=1e6)
        tickets = [h.submit(_x(2, seed=i)) for i in range(5)]
        h.shutdown()                                     # drain=True default
        assert all(t.done for t in tickets)
        for i, t in enumerate(tickets):
            np.testing.assert_allclose(np.asarray(t.result()),
                                       np.asarray(h.expect(_x(2, seed=i))),
                                       rtol=1e-6, atol=1e-7)

    def test_shutdown_without_drain_leaves_pending(self):
        h = ServingHarness(threaded=True, default_max_delay_ms=1e6)
        t = h.submit(_x(2))
        h.shutdown(drain=False)
        assert not t.done
        with pytest.raises(RuntimeError, match="not served yet"):
            t.result()

    def test_submit_after_shutdown_raises(self):
        h = ServingHarness(threaded=True)
        h.shutdown()
        with pytest.raises(SchedulerClosed):
            h.submit(_x(2))
        with pytest.raises(SchedulerClosed):
            h.scheduler.start()

    def test_shutdown_idempotent_and_loopless_drain(self):
        h = ServingHarness(threaded=False, default_max_delay_ms=1e6)
        t = h.submit(_x(2))
        h.shutdown()
        assert t.done                                    # loopless drain path
        h.shutdown()                                     # second time: no-op


class TestSLO:
    def test_exact_latency_under_virtual_clock(self):
        with ServingHarness(default_max_delay_ms=10.0) as h:
            h.submit(_x(3, seed=1))                      # bucket 4
            h.advance(7.0)                               # not due yet (10 ms)
            h.service.flush()                            # demand flush at t=7
            cell = h.service.slo.cell("m", 4)
            assert cell.queue_delay.count == 1
            # no time passes inside a virtual-clock flush: e2e == queue delay
            for stats in (cell.queue_delay, cell.e2e):
                assert stats.percentile(50) == 7.0
                assert stats.percentile(99) == 7.0
                assert stats.max_ms == 7.0
            assert (cell.deadline_met, cell.deadline_missed) == (1, 0)

    def test_deadline_miss_counted(self):
        with ServingHarness() as h:
            h.submit(_x(2, seed=1), max_delay_ms=5.0)
            h.advance(9.0)                               # first poll at t=9 > 5
            m = h.service.metrics()
            assert (m["deadline_met"], m["deadline_missed"]) == (0, 1)
            cell = h.service.slo.cell("m", 4)
            assert cell.miss_rate == 1.0
            assert cell.e2e.percentile(50) == 9.0

    def test_resolution_at_deadline_is_met(self):
        with ServingHarness() as h:
            h.submit(_x(2), max_delay_ms=5.0)
            h.advance(5.0)
            m = h.service.metrics()
            assert (m["deadline_met"], m["deadline_missed"]) == (1, 0)

    def test_per_bucket_cells(self):
        with ServingHarness() as h:                      # buckets 4..32
            h.submit(_x(3, seed=1), max_delay_ms=1.0)    # → bucket 4
            h.submit(_x(9, seed=2), max_delay_ms=1.0)    # → bucket 16
            h.advance(1.0)
            slo = h.service.metrics()["slo"]
            assert sorted(slo["m"]) == [4, 16]
            assert slo["m"][4]["e2e"]["count"] == 1
            assert slo["m"][16]["deadline_met"] == 1

    def test_demand_traffic_has_no_deadline_counts(self):
        """Tickets without max_delay_ms record latency but never miss."""
        with ServingHarness() as h:
            h.service.submit("m", _x(2))                 # bypass scheduler
            h.advance(3.0)
            h.service.flush()
            cell = h.service.slo.cell("m", 4)
            assert cell.e2e.count == 1 and cell.e2e.percentile(50) == 3.0
            assert (cell.deadline_met, cell.deadline_missed) == (0, 0)
            assert cell.miss_rate is None

    def test_latency_stats_exact_percentiles(self):
        s = LatencyStats()
        for v in range(1, 101):
            s.record(float(v))
        assert s.percentile(50) == 50.0
        assert s.percentile(95) == 95.0
        assert s.percentile(99) == 99.0
        assert s.percentile(100) == 100.0 and s.percentile(0) == 1.0
        assert s.count == 100 and s.mean_ms == 50.5

    def test_latency_stats_window_bounds_samples(self):
        s = LatencyStats(window=4)
        for v in (1.0, 2.0, 3.0, 100.0, 100.0, 100.0, 100.0):
            s.record(v)
        assert s.count == 7                              # cumulative survives
        assert s.percentile(50) == 100.0                 # window forgot 1..3
        assert s.max_ms == 100.0

    @pytest.mark.parametrize("p", [0, 10, 50, 90, 95, 99, 100])
    def test_cumulative_bins_read_window_percentiles(self, p):
        """A percentile of the window between two snapshots, read from the
        cumulative bins, lies within 2.2% of the exact nearest-rank one
        over that window's samples — past the 4096-sample deque."""
        rng = np.random.default_rng(1234)
        before, window = rng.lognormal(0.0, 1.5, (2, 6000))
        s = LatencyStats()
        for v in before:
            s.record(v)
        a = s.snapshot()
        for v in window:
            s.record(v)
        got = snapshot_percentile(snapshot_delta(a, s.snapshot()), p)
        exact = np.sort(window)[max(1, int(np.ceil(len(window) * p / 100))) - 1]
        assert abs(got - exact) <= 0.022 * exact
        assert LatencyStats().percentile(50) is None
        assert snapshot_percentile(LatencyStats().snapshot(), 50) is None

    def test_tracker_report_shape(self):
        tr = SLOTracker()
        tr.record("a", 8, queue_delay_ms=1.0, e2e_ms=2.0, deadline_ok=True)
        tr.record("a", 8, queue_delay_ms=3.0, e2e_ms=4.0, deadline_ok=False)
        rep = tr.report()
        assert rep["a"][8]["deadline_miss_rate"] == 0.5
        assert rep["a"][8]["queue_delay"]["p50_ms"] == 1.0
        assert tr.deadline_counts() == (1, 1)


class TestStepTraffic:
    """LM/step work through the same admission queue as DR features."""

    def test_step_runs_at_flush_and_shares_queue(self):
        with ServingHarness() as h:
            ran = []
            t = h.submit_step("lm", "prefill",
                              lambda a, b: ran.append(1) or (a + b), 2, 3,
                              rows=4, max_delay_ms=5.0)
            assert h.service.batcher.queue_depth() == 4 and not ran
            h.advance(5.0)
            assert t.result() == 5 and ran == [1]
            slo = h.service.metrics()["slo"]
            assert slo["lm"]["prefill"]["deadline_met"] == 1

    def test_step_and_dr_interleave_one_flush(self):
        with ServingHarness() as h:
            x = _x(3, seed=1)
            td = h.submit(x, max_delay_ms=2.0)
            ts = h.submit_step("lm", "decode", lambda: "tok", max_delay_ms=2.0)
            h.advance(2.0)
            assert td.done and ts.result() == "tok"
            names = set(h.service.metrics()["slo"])
            assert names == {"m", "lm"}

    def test_step_failure_fails_only_its_ticket(self):
        with ServingHarness() as h:
            def boom():
                raise RuntimeError("step exploded")
            ts = h.submit_step("lm", "decode", boom, max_delay_ms=1.0)
            # same (tag, kind) group: must still run after the failure
            tok = h.submit_step("lm", "decode", lambda: "tok",
                                max_delay_ms=1.0)
            td = h.submit(_x(2), max_delay_ms=1.0)
            h.advance(1.0)
            assert td.done and td.result().shape == (2, 8)
            assert tok.result() == "tok"
            with pytest.raises(RuntimeError, match="step exploded"):
                ts.result()

    def test_lm_prefill_decode_through_queue(self):
        """Real prefill/decode admitted through the queue, compiled into the
        SERVICE's bounded cache (one LRU for DR + LM programs)."""
        from repro.configs import registry as cfg_reg
        from repro.launch.mesh import make_smoke_mesh
        from repro.models import api

        cfg = cfg_reg.get_smoke("smollm_135m")
        params = api.init_params(jax.random.PRNGKey(0), cfg)
        prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                     cfg.vocab_size)
        mesh = make_smoke_mesh()

        clk = VirtualClock()
        svc = DRService(clock=clk)
        sched = DeadlineScheduler(svc, default_max_delay_ms=5.0, start=False)
        tp = sched.lm_prefill(cfg, mesh, params, {"tokens": prompts}, 16)
        assert not tp.done
        clk.advance(5.0)
        sched.poll()
        logits, cache = tp.result()
        assert logits.shape == (2, cfg.vocab_size)

        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        td = sched.lm_decode(cfg, mesh, params, tok, cache, max_delay_ms=0.0)
        sched.poll()
        logits2, _ = td.result()
        assert logits2.shape == (2, cfg.vocab_size)
        assert svc.cache.misses == 2                     # prefill + decode jits
        slo = svc.metrics()["slo"]["lm"]
        assert set(slo) == {"prefill", "decode"}
        assert slo["prefill"]["e2e"]["p50_ms"] == 5.0    # flushed at deadline
        sched.shutdown()


class TestSelectiveDrain:
    def test_drain_keys_preserves_fifo_for_rest(self):
        mb = MicroBatcher(max_queue=100)
        mb.submit("a", "a0", 1)
        mb.submit("b", "b0", 2)
        mb.submit("a", "a1", 3)
        got = mb.drain(keys=["a"])
        assert [k for k, _ in got] == ["a"]
        assert [p for p, _ in got[0][1]] == ["a0", "a1"]
        rest = mb.drain()
        assert [k for k, _ in rest] == ["b"]

    def test_pending_by_key_rows_and_earliest_deadline(self):
        mb = MicroBatcher(max_queue=100)
        mb.submit("a", "p", 2, deadline=50.0)
        mb.submit("a", "q", 3, deadline=20.0)
        mb.submit("b", "r", 1)
        assert mb.pending_by_key() == {"a": (5, 20.0), "b": (1, None)}
        mb.drain()
        assert mb.pending_by_key() == {}


@pytest.mark.slow
class TestRegistryFaultInjection:
    def test_rollback_past_version_zero_raises_cleanly(self):
        reg = ModelRegistry()
        model = small_model()
        reg.register("m", model, model.init(jax.random.PRNGKey(0)))
        with pytest.raises(RuntimeError, match="no previous live version"):
            reg.rollback("m")
        assert reg.get("m").version == 0                 # still serviceable
        svc = DRService()
        svc.register("m", model, model.init(jax.random.PRNGKey(0)))
        with pytest.raises(RuntimeError):
            svc.rollback("m")

    def test_concurrent_transform_vs_promote_rollback(self):
        """N reader threads serve while a mutator loops push/promote/
        rollback: every reply equals the output of exactly one registered
        state version — never a torn (model, state) mix."""
        model = small_model()
        s0 = model.init(jax.random.PRNGKey(0))
        s1 = model.init(jax.random.PRNGKey(1))
        svc = DRService()
        svc.register("m", model, s0)
        x = _x(5, seed=7)
        y0 = np.asarray(svc.transform("m", x))           # also warms the jit
        svc.registry.push("m", s1)
        svc.promote("m", 1)
        y1 = np.asarray(svc.transform("m", x))
        svc.rollback("m")
        assert not np.array_equal(y0, y1)

        stop = threading.Event()
        errors = []

        def reader():
            try:
                while not stop.is_set():
                    y = np.asarray(svc.transform("m", x))
                    if not (np.array_equal(y, y0) or np.array_equal(y, y1)):
                        errors.append("torn read")
                        return
            except Exception as e:                        # noqa: BLE001
                errors.append(repr(e))

        def mutator():
            try:
                for i in range(60):
                    v = svc.registry.push("m", s1 if i % 2 == 0 else s0)
                    svc.promote("m", v)
                    if i % 3 == 0:
                        svc.rollback("m")
            except Exception as e:                        # noqa: BLE001
                errors.append(repr(e))
            finally:
                stop.set()

        readers = [threading.Thread(target=reader) for _ in range(4)]
        mut = threading.Thread(target=mutator)
        for th in readers:
            th.start()
        mut.start()
        mut.join(60.0)
        stop.set()
        for th in readers:
            th.join(60.0)
        assert not errors, errors
        assert svc.registry.n_versions("m") == 62        # 2 + 60 pushes
