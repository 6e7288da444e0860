"""Serving-engine tests: bucket policy, bounded compile cache (+ eviction),
model registry hot-swap, ragged micro-batched serving with asserted compile
counts, ensemble output layout, train-while-serve ≡ offline fit, the hoisted
epoch compile, stage-type-driven ModelState accessors, and the multi-device
ragged-batch degrade (subprocess, 8 host devices)."""

import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dr import DRModel, EASIStage, ModelState, RPStage
from repro.dr import model as model_mod
from repro.serve import (BoundedCompileCache, BucketPolicy, DRService,
                         ModelRegistry, QueueFull, dr_serve)
from repro.serve.batching import EXACT, MicroBatcher

jax.config.update("jax_enable_x64", False)


def _model(m=32, p=16, n=8, block=4):
    return DRModel(stages=(RPStage(m, p), EASIStage.rotation(p, n, mu=1e-3)),
                   block_size=block)


def _service(model, key=0, **kw):
    kw.setdefault("buckets", BucketPolicy(min_bucket=4, max_bucket=32))
    svc = DRService(**kw)
    state = model.init(jax.random.PRNGKey(key))
    svc.register("m", model, state)
    return svc, state


class TestBucketPolicy:
    def test_pow2_padding(self):
        p = BucketPolicy(min_bucket=4, max_bucket=64)
        assert [p.bucket_for(n) for n in (1, 4, 5, 8, 9, 33, 64, 200)] == \
            [4, 4, 8, 8, 16, 64, 64, 64]
        assert p.buckets() == (4, 8, 16, 32, 64)

    def test_exact_policy(self):
        assert EXACT.bucket_for(13) == 13
        assert EXACT.buckets() == ()

    def test_validation(self):
        with pytest.raises(ValueError):
            BucketPolicy(min_bucket=8, max_bucket=4)
        with pytest.raises(ValueError):
            BucketPolicy(min_bucket=0)
        with pytest.raises(ValueError):
            BucketPolicy().bucket_for(0)


class TestBoundedCompileCache:
    def test_lru_eviction_and_counters(self):
        c = BoundedCompileCache(maxsize=2)
        c.get_or_build("a", lambda: "A")
        c.get_or_build("b", lambda: "B")
        assert c.get_or_build("a", lambda: "A2") == "A"   # hit refreshes LRU
        c.get_or_build("c", lambda: "C")                   # evicts "b"
        assert "b" not in c and "a" in c and "c" in c
        assert len(c) == 2
        assert (c.hits, c.misses, c.evictions) == (1, 3, 1)
        assert c.compiles == 3

    def test_lost_build_race_counts_as_miss(self):
        """Satellite bugfix: a thread that built but lost the insert race
        did REAL compile work — it must book a miss (misses == programs
        actually built), tracked as a race, not a phantom hit."""
        c = BoundedCompileCache(maxsize=4)
        entered, release = threading.Event(), threading.Event()

        def slow_build():
            entered.set()
            release.wait(10.0)
            return "slow"

        out = []
        t = threading.Thread(
            target=lambda: out.append(c.get_or_build("k", slow_build)))
        t.start()
        assert entered.wait(10.0)
        # this thread's build wins the insert while the slow build hangs
        assert c.get_or_build("k", lambda: "fast") == "fast"
        release.set()
        t.join(10.0)
        assert out == ["fast"]              # loser returns the winner's fn
        assert (c.hits, c.misses, c.races) == (0, 2, 1)
        st = c.stats()
        assert st["races"] == 1 and st["size"] == 1

    def test_dr_transform_cache_is_bounded(self, monkeypatch):
        """Satellite: the old lru_cache never evicted live meshes — the
        bounded cache must."""
        from repro.launch.mesh import make_smoke_mesh

        small = BoundedCompileCache(maxsize=2)
        monkeypatch.setattr(dr_serve, "_CACHE", small)
        mesh = make_smoke_mesh(1)
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
        for n in (4, 5, 6):   # three distinct models through a 2-slot cache
            model = DRModel(stages=(EASIStage.rotation(16, n),))
            st = model.init(jax.random.PRNGKey(n))
            y = dr_serve.dr_transform(model, st, x, mesh=mesh)
            np.testing.assert_allclose(np.asarray(y),
                                       np.asarray(model.transform(st, x)),
                                       rtol=1e-6, atol=1e-7)
        assert len(small) == 2 and small.evictions == 1


class TestRegistry:
    def test_register_get_and_hash_guard(self):
        reg = ModelRegistry()
        m1, m2 = _model(), _model(n=4)
        s1 = m1.init(jax.random.PRNGKey(0))
        assert reg.register("a", m1, s1) == 0
        snap = reg.get("a")
        assert snap.version == 0 and snap.model is m1
        with pytest.raises(ValueError, match="replace=True"):
            reg.register("a", m2, m2.init(jax.random.PRNGKey(1)))
        reg.register("a", m2, m2.init(jax.random.PRNGKey(1)), replace=True)
        assert reg.get("a").model is m2
        with pytest.raises(KeyError, match="no model registered"):
            reg.get("nope")

    def test_versions_promote_rollback(self):
        reg = ModelRegistry()
        m = _model()
        s0 = m.init(jax.random.PRNGKey(0))
        s1 = m.init(jax.random.PRNGKey(1))
        reg.register("a", m, s0)
        v = reg.push("a", s1)
        assert v == 1 and reg.get("a").version == 0    # push is NOT live yet
        assert reg.promote("a") == 1
        assert reg.get("a").version == 1
        assert reg.rollback("a") == 0
        assert reg.get("a").version == 0
        assert reg.n_versions("a") == 2
        with pytest.raises(IndexError):
            reg.promote("a", 7)


class TestMicroBatchedServing:
    def test_ragged_stream_bucketed_compile_count(self):
        """Acceptance: ragged requests serve through bucketed micro-batches
        with an asserted compile count (one per touched bucket)."""
        model = _model()
        svc, st = _service(model)
        sizes = [3, 7, 1, 5, 12, 2, 9, 30, 4]   # buckets: 4, 8, 16, 32
        xs = [jax.random.normal(jax.random.PRNGKey(i), (s, 32))
              for i, s in enumerate(sizes)]
        for x in xs:                              # one-shot path
            np.testing.assert_allclose(np.asarray(svc.transform("m", x)),
                                       np.asarray(model.transform(st, x)),
                                       rtol=1e-6, atol=1e-7)
        assert svc.cache.misses == 4              # == touched buckets, not 9
        # queued path: same answers, still no new compiles for the big
        # coalesced batch as long as its chunks hit existing buckets
        tickets = [svc.submit("m", x) for x in xs]
        assert svc.batcher.queue_depth() == sum(sizes)
        svc.flush()
        for t, x in zip(tickets, xs):
            np.testing.assert_allclose(np.asarray(t.result()),
                                       np.asarray(model.transform(st, x)),
                                       rtol=1e-6, atol=1e-7)
        assert svc.cache.misses == 4
        met = svc.metrics()
        assert met["queue"]["queue_depth"] == 0
        assert met["compile_cache"]["misses"] == 4

    def test_oversize_request_chunks(self):
        model = _model()
        svc, st = _service(model)       # max_bucket=32
        x = jax.random.normal(jax.random.PRNGKey(0), (81, 32))
        y = svc.transform("m", x)
        assert y.shape == (81, 8)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(model.transform(st, x)),
                                   rtol=1e-6, atol=1e-7)

    def test_backpressure_queue_full(self):
        model = _model()
        svc, _ = _service(model, max_queue=16)
        svc.submit("m", jnp.ones((10, 32)))
        with pytest.raises(QueueFull):
            svc.submit("m", jnp.ones((7, 32)))
        assert svc.batcher.rejected == 1
        svc.flush()
        svc.submit("m", jnp.ones((7, 32)))        # drained queue admits again

    def test_never_admittable_request_is_value_error(self):
        """Satellite bugfix: rows > max_queue can NEVER admit — that is a
        caller bug (chunk your request), not transient backpressure, so it
        must not masquerade as a retryable QueueFull."""
        mb = MicroBatcher(max_queue=8)
        with pytest.raises(ValueError, match="can never be admitted"):
            mb.submit("a", "x", 9)
        assert mb.rejected == 0                   # not a backpressure event
        assert mb.submit("a", "x", 8).rows == 8   # exactly max_queue admits
        # the same contract through the service front door
        svc, _ = _service(_model(), max_queue=16)
        with pytest.raises(ValueError, match="can never be admitted"):
            svc.submit("m", jnp.ones((17, 32)))

    def test_replace_mid_queue_fails_only_stale_tickets(self):
        """Satellite: tickets queued for a model that is then
        register(replace=True)d with a different in_dim must fail alone
        with a clear message at flush — not explode the whole group inside
        jnp.concatenate."""
        model = _model()                          # in_dim 32
        svc, _ = _service(model)
        stale = [svc.submit("m", jnp.ones((r, 32))) for r in (5, 3)]
        new_model = _model(m=16)                  # in_dim 16
        svc.register("m", new_model, new_model.init(jax.random.PRNGKey(1)),
                     replace=True)
        fresh = svc.submit("m", jnp.ones((4, 16)))
        svc.flush()
        for t in stale:
            with pytest.raises(ValueError, match="replaced"):
                t.result()
        assert fresh.result().shape == (4, 8)     # the valid ticket served
        assert svc.batcher.queue_depth() == 0

    def test_request_validation(self):
        svc, _ = _service(_model())
        with pytest.raises(ValueError, match=r"\(B, 32\)"):
            svc.transform("m", jnp.ones((4, 31)))
        with pytest.raises(ValueError):
            svc.transform("m", jnp.ones((4,)))
        with pytest.raises(KeyError):
            svc.transform("ghost", jnp.ones((4, 32)))

    def test_warmup_precompiles_buckets(self):
        svc, _ = _service(_model())
        n = svc.warmup("m")
        assert n == len(svc.buckets.buckets())
        assert svc.warmup("m") == 0               # all cached now

    def test_ensemble_serving_layout(self):
        """Acceptance: ensemble output layout (k, B, n), ragged B."""
        model = _model()
        k = 3
        est = model.ensemble(k).init(jax.random.PRNGKey(4))
        svc = DRService(buckets=BucketPolicy(min_bucket=4, max_bucket=16))
        svc.register("ens", model, est, ensemble=k)
        xs = [jax.random.normal(jax.random.PRNGKey(i), (s, 32))
              for i, s in enumerate((5, 11, 3))]
        tickets = [svc.submit("ens", x) for x in xs]
        svc.flush()
        for t, x in zip(tickets, xs):
            y = t.result()
            assert y.shape == (k, x.shape[0], 8)
            np.testing.assert_allclose(
                np.asarray(y),
                np.asarray(model.ensemble(k).transform(est, x)),
                rtol=1e-5, atol=1e-6)
        # oversize ensemble request chunks along the batch (middle) axis
        xb = jax.random.normal(jax.random.PRNGKey(9), (37, 32))
        assert svc.transform("ens", xb).shape == (k, 37, 8)

    def test_microbatcher_fifo_groups(self):
        mb = MicroBatcher(max_queue=100)
        mb.submit("a", "x0", 1)
        mb.submit("b", "x1", 2)
        mb.submit("a", "x2", 3)
        groups = mb.drain()
        assert [g[0] for g in groups] == ["a", "b"]
        assert [p for p, _ in groups[0][1]] == ["x0", "x2"]
        assert mb.drain() == []


class TestHostFlush:
    """A flush of host (numpy) payloads joins, pads and slices them in
    numpy around the same bucket programs as the device path."""

    @staticmethod
    def _flush(svc, name, xs):
        tickets = [svc.submit(name, x) for x in xs]
        assert svc.flush() == -(-sum(x.shape[0] for x in xs)
                                // svc.buckets.max_bucket)
        return [t.result() for t in tickets]

    @staticmethod
    def _xs(sizes, m=32, seed=0):
        rng = np.random.default_rng(seed)
        return [rng.standard_normal((s, m)).astype(np.float32)
                for s in sizes]

    @pytest.mark.parametrize("sizes, ensemble", [
        ((3, 7, 1, 5), None),           # one batch of 16
        ((30, 21, 9, 33, 2), None),     # 95 rows: two full batches and 31
        ((5, 11, 3), 3),                # ensemble, (k, rows, n) answers
        ((13, 30), 2),                  # ensemble over max_bucket
    ])
    def test_host_answers_and_counters_equal_the_device_path(
            self, sizes, ensemble):
        model = _model()
        state = (model.init(jax.random.PRNGKey(4)) if ensemble is None else
                 model.ensemble(ensemble).init(jax.random.PRNGKey(4)))
        services = []
        for _ in range(2):
            svc = DRService(buckets=BucketPolicy(min_bucket=4, max_bucket=32))
            svc.register("m", model, state, ensemble=ensemble)
            services.append(svc)
        xs = self._xs(sizes)
        host = self._flush(services[0], "m", xs)
        dev = self._flush(services[1], "m", [jnp.asarray(x) for x in xs])
        for h, d, x in zip(host, dev, xs):
            assert type(h) is np.ndarray and isinstance(d, jax.Array)
            want = (x.shape[0], 8) if ensemble is None else \
                (ensemble, x.shape[0], 8)
            assert h.shape == want
            np.testing.assert_array_equal(h, np.asarray(d))
        mh, md = services[0].metrics(), services[1].metrics()
        rows = sum(sizes)
        last = rows % 32 or 32
        for key, val in (("served_rows", rows), ("batches_run", -(-rows // 32)),
                         ("padded_rows",
                          services[0].buckets.bucket_for(last) - last)):
            assert mh[key] == md[key] == val, key
        assert mh["host_batches"] == mh["batches_run"]
        assert md["host_batches"] == 0

    def test_new_host_size_mixes_compile_nothing_after_warmup(self):
        model = _model()
        svc, _ = _service(model)                # buckets 4..32
        svc.warmup("m")
        events = []

        def listen(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                events.append(secs)

        rng = np.random.default_rng(7)
        mixes = [tuple(int(s) for s in rng.integers(1, 12, size=n))
                 for n in (1, 2, 3, 2, 4, 1, 3)]
        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            for i, sizes in enumerate(mixes):
                self._flush(svc, "m", self._xs(sizes, seed=i))
            host_compiles = len(events)
            # the same mixes as device payloads compile their joins, pads
            # and slices: the listener does see those
            for i, sizes in enumerate(mixes):
                self._flush(svc, "m", [jnp.asarray(x) for x in
                                       self._xs(sizes, seed=i)])
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
        assert host_compiles == 0
        assert len(events) > 0
        assert svc.metrics()["host_batches"] == len(mixes)

    def test_a_mixed_group_takes_the_device_path(self):
        model = _model()
        svc, st = _service(model)
        xh, xd = self._xs((5, 6))
        xd = jnp.asarray(xd)
        yh, yd = self._flush(svc, "m", [xh, xd])
        assert isinstance(yh, jax.Array) and isinstance(yd, jax.Array)
        alone, = self._flush(svc, "m", [xh])
        assert type(alone) is np.ndarray
        np.testing.assert_array_equal(np.asarray(yh), alone)
        met = svc.metrics()
        assert (met["batches_run"], met["host_batches"]) == (2, 1)

    def test_stale_host_payload_fails_alone(self):
        model = _model()                          # in_dim 32
        svc, _ = _service(model)
        stale = [svc.submit("m", x) for x in self._xs((5, 3))]
        new_model = _model(m=16)                  # in_dim 16
        svc.register("m", new_model, new_model.init(jax.random.PRNGKey(1)),
                     replace=True)
        fresh = svc.submit("m", self._xs((4,), m=16)[0])
        svc.flush()
        for t in stale:
            with pytest.raises(ValueError, match="replaced"):
                t.result()
        y = fresh.result()
        assert type(y) is np.ndarray and y.shape == (4, 8)
        met = svc.metrics()
        assert (met["served_rows"], met["host_batches"]) == (4, 1)


class TestTrainWhileServe:
    def test_round_trip_equals_offline_fit(self):
        """Acceptance: register → serve_and_update → promote → transform.
        The promoted state equals `model.fit` over the same block order."""
        model = _model(block=4)
        svc, st = _service(model)
        x = jax.random.normal(jax.random.PRNGKey(5), (64, 32))
        blocks = x.reshape(16, 4, 32)
        for blk in blocks:
            y = svc.serve_and_update("m", blk)
            # serving answers come from the LIVE (v0) state throughout
            np.testing.assert_allclose(np.asarray(y),
                                       np.asarray(model.transform(st, blk)),
                                       rtol=1e-6, atol=1e-7)
        # not live until promoted
        assert svc.registry.get("m").version == 0
        assert svc.staged_state("m") is not None
        v = svc.promote("m")
        assert v == 1 and svc.registry.get("m").version == 1

        fitted = model.fit(st, x, epochs=1)
        promoted = svc.registry.get("m").state
        for a, b in zip(jax.tree.leaves(promoted), jax.tree.leaves(fitted)):
            np.testing.assert_allclose(np.asarray(a, np.float64),
                                       np.asarray(b, np.float64),
                                       rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(svc.transform("m", x[:8])),
                                   np.asarray(model.transform(fitted, x[:8])),
                                   rtol=1e-5, atol=1e-6)
        svc.rollback("m")
        np.testing.assert_allclose(np.asarray(svc.transform("m", x[:8])),
                                   np.asarray(model.transform(st, x[:8])),
                                   rtol=1e-6, atol=1e-7)

    def test_update_fraction_half(self):
        model = _model(block=4)
        svc, st = _service(model, update_fraction=0.5)
        blocks = jax.random.normal(jax.random.PRNGKey(6), (8, 4, 32))
        for blk in blocks:
            svc.serve_and_update("m", blk)
        assert svc.metrics()["updates_applied"]["m"] == 4
        svc.promote("m")
        # equals offline fit over every OTHER block (the updated half)
        manual = st
        for i in range(1, 8, 2):
            manual = model.update(manual, blocks[i])
        for a, b in zip(jax.tree.leaves(svc.registry.get("m").state),
                        jax.tree.leaves(manual)):
            np.testing.assert_allclose(np.asarray(a, np.float64),
                                       np.asarray(b, np.float64),
                                       rtol=1e-5, atol=1e-6)

    def test_promote_without_staged_raises(self):
        svc, _ = _service(_model())
        with pytest.raises(RuntimeError, match="nothing staged"):
            svc.promote("m")

    def test_fused_compile_happens_outside_tws_lock(self):
        """Blocking-under-lock regression: the fused transform+update
        program must be fetched/compiled BEFORE the per-name
        train-while-serve lock is taken — a cold compile under the lock
        convoys every concurrent update/promote for the name.  The spy
        records whether the name's lock is held at every compile-cache
        entry (owner-agnostic: this thread IS the one that would hold
        it)."""
        model = _model(block=4)
        svc, st = _service(model)
        held_at_build = []
        real = svc.cache.get_or_build

        def spy(key, build):
            lock = svc._tws_locks.get("m")
            held_at_build.append(lock.locked() if lock is not None else False)
            return real(key, build)

        svc.cache.get_or_build = spy
        x = jax.random.normal(jax.random.PRNGKey(7), (12, 4, 32))
        for blk in x:          # first block creates the lock; later
            y = svc.serve_and_update("m", blk)   # blocks must still
            np.testing.assert_allclose(          # pre-build outside it
                np.asarray(y), np.asarray(model.transform(st, blk)),
                rtol=1e-6, atol=1e-7)
        # wider batch after the lock exists: a genuinely fresh compile
        wide = jax.random.normal(jax.random.PRNGKey(8), (8, 32))
        svc.serve_and_update("m", wide)
        assert held_at_build and not any(held_at_build)
        assert svc.metrics()["updates_applied"]["m"] == 13

    @pytest.mark.slow
    def test_threaded_stream_vs_promote_loses_no_update(self):
        """Satellite bugfix regression: one thread streams blocks through
        serve_and_update while another hammers promote().  Without the
        per-name lock, an update landing between promote's staged-pop and
        registry-push chains onto a pre-promote base and is silently
        orphaned.  With it, the final live state must equal the offline
        fold of EVERY block in stream order, no matter where the promotes
        landed.  Runs 20 races per PR (the multidev job); the nightly
        soak sets CHAOS_ITERS=100 for the full-length hunt."""
        model = _model(block=4)
        svc = DRService(buckets=BucketPolicy(min_bucket=4, max_bucket=32))
        upd = jax.jit(model.update)
        for run in range(int(os.environ.get("CHAOS_ITERS", "20"))):
            name = f"m{run}"
            st = model.init(jax.random.PRNGKey(run))
            svc.register(name, model, st)
            blocks = jax.random.normal(jax.random.PRNGKey(1000 + run),
                                       (8, 4, 32))
            errors = []

            def stream(name=name, blocks=blocks):
                try:
                    for blk in blocks:
                        svc.serve_and_update(name, blk)
                except Exception as e:            # noqa: BLE001
                    errors.append(repr(e))

            def promoter(name=name):
                try:
                    for _ in range(16):
                        try:
                            svc.promote(name)
                        except RuntimeError:      # nothing staged right now
                            pass
                except Exception as e:            # noqa: BLE001
                    errors.append(repr(e))

            ts = [threading.Thread(target=stream),
                  threading.Thread(target=promoter)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(60.0)
            assert not errors, (run, errors)
            try:
                svc.promote(name)                 # land any remaining staged
            except RuntimeError:
                pass
            assert svc.metrics()["updates_applied"][name] == 8, run
            manual = st
            for blk in blocks:
                manual = upd(manual, blk)
            final = svc.registry.get(name).state
            for a, b in zip(jax.tree.leaves(final), jax.tree.leaves(manual)):
                np.testing.assert_allclose(np.asarray(a, np.float64),
                                           np.asarray(b, np.float64),
                                           rtol=1e-5, atol=1e-6,
                                           err_msg=f"run {run}")

    def test_ensemble_is_serve_only(self):
        model = _model()
        svc = DRService()
        svc.register("e", model, model.ensemble(2).init(jax.random.PRNGKey(0)),
                     ensemble=2)
        with pytest.raises(NotImplementedError):
            svc.serve_and_update("e", jnp.ones((4, 32)))


class TestEpochCompileCache:
    def test_repeated_fit_reuses_compiled_epoch(self):
        """Satellite: the general-cascade epoch program compiles once per
        (stage suffix, execution), not once per fit call."""
        model_mod._epoch_fn.cache_clear()
        model = DRModel(stages=(RPStage(16, 8),
                                EASIStage.whiten(8, 6),
                                EASIStage.rotation(6, 4)), block_size=8)
        st = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
        for _ in range(3):
            st = model.fit(st, x, epochs=2)
        info = model_mod._epoch_fn.cache_info()
        assert info.misses == 1 and info.hits >= 2
        # a different execution policy is a different program
        model2 = model.with_execution(model.execution.__class__(backend="xla",
                                                                easi_block_m=256))
        model2.fit(model2.init(jax.random.PRNGKey(2)), x, epochs=1)
        assert model_mod._epoch_fn.cache_info().misses == 2


class TestModelStateAccessors:
    def test_mask_driven_r_b(self):
        """Satellite: r = first non-trainable stage, b = last trainable —
        by stage type, not dtype sniffing."""
        model = DRModel(stages=(RPStage(32, 16),
                                EASIStage.whiten(16, 12),
                                EASIStage.rotation(12, 8)))
        st = model.init(jax.random.PRNGKey(0))
        assert st.trainable == (False, True, True)
        assert st.r is st.stages[0]
        assert st.b is st.stages[2]               # LAST trainable, not first

    def test_all_static_and_all_trainable(self):
        rp_only = DRModel(stages=(RPStage(16, 8),))
        st = rp_only.init(jax.random.PRNGKey(1))
        assert st.b is None and st.r is st.stages[0]
        easi_only = DRModel(stages=(EASIStage.full(16, 8),))
        st = easi_only.init(jax.random.PRNGKey(2))
        assert st.r is None and st.b is st.stages[0]

    def test_bf16_trainable_stage_still_resolves(self):
        model = DRModel(stages=(RPStage(16, 8),
                                EASIStage.rotation(8, 4, dtype=jnp.bfloat16)))
        st = model.init(jax.random.PRNGKey(3))
        assert st.b is st.stages[1] and st.b.dtype == jnp.bfloat16

    def test_maskless_fallback_sniffs_dtypes(self):
        r = jnp.zeros((8, 16), jnp.int8)
        b = jnp.zeros((4, 8), jnp.float32)
        st = ModelState(stages=(r, b), steps=jnp.int32(0))
        assert st.trainable is None
        assert st.r is r and st.b is b

    def test_mask_survives_tracing_and_tree_ops(self):
        model = _model()
        st = model.init(jax.random.PRNGKey(4))
        st2 = jax.jit(lambda s: s._replace(steps=s.steps + 1))(st)
        assert st2.trainable == st.trainable
        st3 = jax.tree.map(lambda a: a, st)
        assert st3.trainable == st.trainable
        est = model.ensemble(2).init(jax.random.PRNGKey(5))
        assert est.trainable == st.trainable
        # checkpoint-style flatten keeps the NamedTuple-era key paths
        flat, _ = jax.tree_util.tree_flatten_with_path(st)
        paths = [jax.tree_util.keystr(kp) for kp, _ in flat]
        assert paths == [".stages[0]", ".stages[1]", ".steps"]


MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core.execution import Execution
from repro.dr import DRModel, EASIStage, RPStage
from repro.launch.mesh import make_mesh
from repro.serve import DRService, BucketPolicy, dr_serve, serve_step

mesh = make_mesh((4, 2), ("data", "model"))
model = DRModel(stages=(RPStage(32, 16), EASIStage.rotation(16, 8)))
st = model.init(jax.random.PRNGKey(0))

# ragged batch: 63 % n_dp(=4) != 0 -> layout degrades to replicated
x_odd = jax.random.normal(jax.random.PRNGKey(1), (63, 32))
y_odd = dr_serve.dr_transform(model, st, x_odd, mesh=mesh)
np.testing.assert_allclose(np.asarray(y_odd), np.asarray(model.transform(st, x_odd)),
                           rtol=1e-5, atol=1e-6)
assert y_odd.sharding.is_fully_replicated, y_odd.sharding

# divisible batch stays sharded over the DP axis
x_even = jax.random.normal(jax.random.PRNGKey(2), (64, 32))
y_even = dr_serve.dr_transform(model, st, x_even, mesh=mesh)
np.testing.assert_allclose(np.asarray(y_even), np.asarray(model.transform(st, x_even)),
                           rtol=1e-5, atol=1e-6)
assert not y_even.sharding.is_fully_replicated, y_even.sharding

# the engine's bucketed path pads every request to a pow2 bucket, which the
# DP axes divide -> sharded micro-batches even for ragged client requests
svc = DRService(mesh=mesh, buckets=BucketPolicy(min_bucket=8, max_bucket=64))
svc.register("m", model, st)
for rows in (3, 17, 63):
    xr = jax.random.normal(jax.random.PRNGKey(rows), (rows, 32))
    np.testing.assert_allclose(np.asarray(svc.transform("m", xr)),
                               np.asarray(model.transform(st, xr)),
                               rtol=1e-5, atol=1e-6)
assert svc.cache.misses == 3

# a Pallas-backed model serves on the same mesh: the transform runs per
# shard, which is what lets the Mosaic kernel into a multi-device program
kern = DRModel(stages=model.stages, execution=Execution(backend="pallas"))
ksvc = DRService(mesh=mesh, buckets=BucketPolicy(min_bucket=8, max_bucket=64))
ksvc.register("k", kern, st)
yk = ksvc.transform("k", x_even)
np.testing.assert_allclose(np.asarray(yk), np.asarray(model.transform(st, x_even)),
                           rtol=1e-5, atol=1e-6)
assert len(yk.sharding.device_set) == 8 and not yk.sharding.is_fully_replicated

# jax.make_mesh's default Explicit axes are refused, with the fix named, at
# every entry point that takes a caller's mesh; DRService meets the refusal
# when it builds its first sharded program
explicit = jax.make_mesh((4, 2), ("data", "model"))
esvc = DRService(mesh=explicit, buckets=BucketPolicy(min_bucket=8, max_bucket=64))
esvc.register("m", model, st)
refusals = (lambda: esvc.transform("m", x_even),
            lambda: dr_serve.dr_transform(model, st, x_even, mesh=explicit),
            lambda: serve_step.make_prefill(None, explicit, {}, {}, 8),
            lambda: serve_step.make_decode(None, explicit, {}, {}))
for call in refusals:
    try:
        call()
    except ValueError as e:
        assert "AxisType.Auto" in str(e) and "make_mesh" in str(e), e
    else:
        raise AssertionError("an Explicit mesh was accepted")
print("MULTIDEV_SERVE_OK")
"""


@pytest.mark.slow
def test_ragged_batch_multidevice_subprocess():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT],
                         capture_output=True, text=True, cwd=repo,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MULTIDEV_SERVE_OK" in out.stdout
