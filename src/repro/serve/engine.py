"""`DRService` — the unified online serving engine for DR models.

The paper's point is one reconfigurable datapath for BOTH training and
deployment; this is that story at service level.  One `DRService` owns:

  * a model registry (`repro.serve.registry`) — named models, versioned
    states, atomic hot-swap: a retrained state is `push`ed as a new
    version and `promote()`d under a lock, so in-flight requests always
    see one consistent (model, state) pair;
  * dynamic micro-batching (`repro.serve.batching`) — ragged client
    requests coalesce through an admission queue into powers-of-two
    bucketed batch shapes, so the compile universe is O(log max_bucket)
    programs per model instead of one per client batch size, all held in
    a bounded LRU compile cache (evicting actually frees the jitted
    closure and any mesh it pins).  Without a mesh, a flush of host
    (numpy) payloads joins, zero-pads and slices them in numpy: one
    transfer into the bucket program per batch, one copy of the answers
    back, and tickets that resolve with numpy rows — no XLA program per
    mix of request sizes.  Device (`jax.Array`) payloads, a group mixing
    the two, and every group of a mesh service are joined, padded and
    sliced on the device and resolve with `jax.Array`s;
  * train-while-serve — `serve_and_update` answers a request with the
    LIVE state while streaming the same traffic (a configurable fraction
    of it) through `model.update` into a STAGED state; `promote()` makes
    the staged state live, `rollback()` reverts.  Streaming every block
    through `serve_and_update` then promoting reproduces an offline
    `model.fit` with the same block order — tests pin that equivalence;
  * the Execution fast path — a model registered with
    `Execution(backend="pallas")` serves its bucketed transform through
    the fused pad+project+whiten kernel and folds streamed traffic
    through `kernels.ops.easi_update` (both via the model's own
    dispatch), with kernel tiles autotuned per (bucket, device) at
    register time (`repro.kernels.autotune`); the tuned winner is cached
    beside the compiled program in the bounded compile cache.

Typical use:

    svc = DRService(mesh=make_production_mesh())
    svc.register("waveform", model, state)
    y = svc.transform("waveform", x)          # one-shot, bucket-padded

    t1 = svc.submit("waveform", x1)           # ragged micro-batched path
    t2 = svc.submit("waveform", x2)
    svc.flush()
    y1, y2 = t1.result(), t2.result()

    y = svc.serve_and_update("waveform", block)   # train-while-serve
    svc.promote("waveform")                       # retrained state goes live
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import (Any, Callable, Dict, Hashable, Iterator, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.kernels import autotune
from repro.serve import dr_serve, serve_step
from repro.serve.batching import (BoundedCompileCache, BucketPolicy,
                                  MicroBatcher, Ticket)
from repro.serve.clock import Clock, MonotonicClock
from repro.serve.registry import ModelRegistry, Snapshot
from repro.serve.replication import ReplicatedRegistry, state_hash
from repro.serve.slo import SLOTracker
from repro.serve.transport import LocalBus

PyTree = Any


def _pad_rows(x: jax.Array, bucket: int) -> jax.Array:
    pad = bucket - x.shape[0]
    if pad == 0:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)


@dataclasses.dataclass(frozen=True)
class _HostRows:
    """A flush group joined and zero-padded on the host: `rows` request
    rows laid out as the bucket batches `_serve_rows` runs, i.e. chunk
    `i` of `max_bucket` rows starts at row `i * max_bucket` and `buf`
    ends at the last chunk's bucket."""
    buf: np.ndarray
    rows: int


@dataclasses.dataclass(frozen=True)
class _StepKey:
    """Queue key for non-DR work (LM prefill/decode steps) — wrapping the
    caller's tag keeps step groups disjoint from DR model names."""
    tag: Hashable
    kind: str


@dataclasses.dataclass
class _StepWork:
    """Queued callable: run at flush, its return value resolves the ticket.
    Steps are admitted (ordering, backpressure, deadlines, SLO accounting)
    but not coalesced — an LM step is already a batch."""
    fn: Callable[..., Any]
    args: Tuple[Any, ...]


@dataclasses.dataclass(frozen=True)
class StagedExtraction:
    """What a fleet-merge collect pulls out of the engine under the
    per-name train-while-serve lock: the staged chain (None when nothing
    is staged), the state the chain was folded FROM (`staged − chain_base`
    is this host's delta — measured against the chain's own base, so the
    delta stays exactly this host's folds even if the live pointer moved
    under the chain), the registry op seq at extraction time (what the
    merger's carry record and the merge-op log are compared against), and
    how many updates the chain folds.  Extraction CONSUMES the chain:
    from here on the delta lives in the merger's durable carry, and a
    late `serve_and_update` starts a fresh chain from the current live
    state — so delta ownership is never split between engine and merger."""
    staged: Optional[PyTree]
    chain_base: Optional[PyTree]
    seq: int
    updates: int


class DRService:
    """Online serving engine: registry + micro-batching + train-while-serve."""

    def __init__(self, *, mesh: Optional[Mesh] = None,
                 buckets: BucketPolicy = BucketPolicy(),
                 compile_cache_size: int = 32,
                 max_queue: int = 4096,
                 update_fraction: float = 1.0,
                 clock: Optional[Clock] = None,
                 registry: Optional[Any] = None,
                 data_dir: Optional[str] = None):
        if not 0.0 <= update_fraction <= 1.0:
            raise ValueError("update_fraction must be in [0, 1]")
        self.mesh = mesh
        self.buckets = buckets
        self.clock: Clock = clock if clock is not None else MonotonicClock()
        # `registry` hook: anything with the ModelRegistry surface — e.g. a
        # `repro.serve.replication.ReplicatedRegistry` so this service's
        # register/push/promote go fleet-wide (get() semantics unchanged).
        # `data_dir` is the single-host durability hook: the service runs
        # over a solo durable ReplicatedRegistry (quorum=1, private bus),
        # so every register/push/promote is WAL'd + snapshotted and a
        # restart with the same data_dir restores the whole registry.
        # Fleet hosts configure data_dir on their own ReplicatedRegistry
        # instead and pass it via `registry=` — both at once is ambiguous.
        if data_dir is not None:
            if registry is not None:
                raise ValueError(
                    "pass data_dir OR registry, not both — a fleet host "
                    "configures data_dir on its ReplicatedRegistry")
            registry = ReplicatedRegistry(
                LocalBus().attach("solo"), role="leader", quorum=1,
                data_dir=data_dir)
        self.registry = registry if registry is not None else ModelRegistry()
        self.slo = SLOTracker(clock=self.clock)
        self.cache = BoundedCompileCache(compile_cache_size, tracker=self.slo)
        # compute-dtype copies of LM params trees, made once per tree
        self.lm_params = serve_step.ComputeParamsCache(tracker=self.slo)
        self.batcher = MicroBatcher(max_queue=max_queue)
        self.update_fraction = update_fraction
        # train-while-serve bookkeeping (per model name).  All three dicts
        # are mutated from caller threads AND read by promote(), so every
        # access goes through the per-name lock (`_tws_lock`): promote's
        # pop → push → promote must be atomic w.r.t. a concurrent
        # serve_and_update, or an update chained onto the pre-promote base
        # lands between the pop and the push and is silently orphaned.
        self._staged: Dict[str, PyTree] = {}        # guarded-by: _tws_guard
        self._accum: Dict[str, float] = {}          # guarded-by: _tws_guard
        self._updates: Dict[str, int] = {}          # guarded-by: _tws_guard
        # (staged object, version) of a push whose promote failed — a retry
        # with the SAME chain re-promotes that version instead of pushing a
        # duplicate (a replicated push re-ships the full state to the fleet)
        self._staged_pushed: Dict[str, Tuple[PyTree, int]] = {}  # guarded-by: _tws_guard
        # fleet-merge bookkeeping: the state each staged chain was folded
        # FROM (set when the chain starts, so a merge round can extract
        # `staged − chain_base` as this host's delta) and how many updates
        # the CURRENT chain folds (`_updates` is the cumulative metrics
        # counter; this one resets per chain and rides the extraction).
        self._staged_from: Dict[str, PyTree] = {}   # guarded-by: _tws_guard
        self._chain_updates: Dict[str, int] = {}    # guarded-by: _tws_guard
        self._tws_guard = threading.Lock()          # guards the lock table
        self._tws_locks: Dict[str, threading.Lock] = {}  # guarded-by: _tws_guard
        # serving metrics — counters are bumped from caller threads AND a
        # DeadlineScheduler loop, so mutations AND reads hold this lock
        self._metrics_lock = threading.Lock()
        self.served_rows = 0                        # guarded-by: _metrics_lock
        self.padded_rows = 0                        # guarded-by: _metrics_lock
        self.batches_run = 0                        # guarded-by: _metrics_lock
        self.host_batches = 0                       # guarded-by: _metrics_lock
        self.autotunes = 0                          # guarded-by: _metrics_lock

    def _tws_lock(self, name: str) -> threading.Lock:
        with self._tws_guard:
            lock = self._tws_locks.get(name)
            if lock is None:
                lock = self._tws_locks[name] = threading.Lock()
            return lock

    @contextlib.contextmanager
    def _tws_lock_timed(self, name: str) -> Iterator[None]:
        """Hold `_tws_lock(name)`, its acquire timed as `tws.lock_wait`."""
        lock = self._tws_lock(name)
        with self.slo.span("tws.lock_wait"):
            lock.acquire()
        try:
            yield
        finally:
            lock.release()

    # ---- registry facade ---------------------------------------------------
    def register(self, name: str, model: Any, state: PyTree, *,
                 ensemble: Optional[int] = None, replace: bool = False) -> int:
        v = self.registry.register(name, model, state, ensemble=ensemble,
                                   replace=replace)
        # Registry-register time is when a pallas model's bucket programs
        # get their tile sweep: tune every bucket of the policy now (the
        # winners land in the compile cache keyed by config hash + bucket),
        # so the first real request pays neither tuning nor tile regret.
        # A later promote reuses these entries (same config hash); only an
        # eviction — which drops program AND tiles together — re-tunes.
        exe = getattr(model, "execution", None)
        if (ensemble is None and self.mesh is None and exe is not None
                and getattr(exe, "use_kernel", False)):
            snap = self.registry.get(name)
            dtype = jnp.dtype(exe.dtype)
            for b in self.buckets.buckets():    # empty for EXACT policies
                self._transform_fn(snap, b, dtype)
        return v

    def promote(self, name: str, version: Optional[int] = None) -> int:
        """Make a state version live.  With no explicit `version`, promotes
        the state staged by `serve_and_update` (pushing it as a new
        version first) — the online-retrain hot-swap.  The whole
        pop → push → promote runs under the per-name train-while-serve
        lock, so a concurrent `serve_and_update` either lands before the
        pop (its update is in the promoted state) or after the promote
        (it chains onto the newly-live state) — never in between."""
        with self.slo.span("promote"):
            return self._promote(name, version)

    def _promote(self, name: str, version: Optional[int]) -> int:
        with self._tws_lock(name):
            if version is None:
                with self._tws_guard:
                    staged = self._staged.pop(name, None)
                    pushed = self._staged_pushed.pop(name, None)
                    chain_base = self._staged_from.pop(name, None)
                    chain_updates = self._chain_updates.pop(name, None)
                if staged is None:
                    raise RuntimeError(
                        f"nothing staged for {name!r}; run serve_and_update "
                        f"first or pass an explicit version")
                try:
                    if pushed is not None and pushed[0] is staged and \
                            self._pushed_still_valid(name, pushed[1], staged):
                        # this exact chain was already pushed by a promote
                        # that then failed — reuse its version, don't ship
                        # a duplicate state to the registry (or the fleet)
                        version = pushed[1]
                    else:
                        version = self.registry.push(name, staged)
                except Exception:
                    with self._tws_guard:
                        self._staged[name] = staged
                        if chain_base is not None:
                            self._staged_from[name] = chain_base
                        if chain_updates is not None:
                            self._chain_updates[name] = chain_updates
                    raise
                try:
                    result = self.registry.promote(name, version)
                except Exception:
                    # promote can fail after the pop+push (e.g. a replicated
                    # registry aborting on lost quorum) — restore the staged
                    # state so the update chain isn't orphaned, and remember
                    # the pushed version so a retry promotes it instead of
                    # pushing again.  We hold the per-name lock, so nothing
                    # staged in between.
                    with self._tws_guard:
                        self._staged[name] = staged
                        self._staged_pushed[name] = (staged, version)
                        if chain_base is not None:
                            self._staged_from[name] = chain_base
                        if chain_updates is not None:
                            self._chain_updates[name] = chain_updates
                    raise
                return result
            return self.registry.promote(name, version)

    def _pushed_still_valid(self, name: str, version: int,
                            staged: PyTree) -> bool:
        """Is a previously-pushed staged version still safe to re-promote?
        Over a plain registry, always (nothing can unseat a pushed
        version).  Over a replicated registry, ask whether the CURRENT
        leader holds that version with the staged content — after a
        failover the new leader may never have seen the push, or hold
        different bytes under the same version id; re-promoting blind
        would flip the fleet to the wrong state."""
        holds = getattr(self.registry, "holds_content", None)
        if holds is None:
            return True
        return holds(name, version, state_hash(staged))

    def rollback(self, name: str) -> int:
        return self.registry.rollback(name)

    def leader_status(self) -> Dict[str, Any]:
        """Who leads the registry this service mutates through, and at
        what election term.  Over a plain `ModelRegistry` the service IS
        its own (static) leader; over a `ReplicatedRegistry` with an
        elector attached this tracks failovers — and `promote()` keeps
        working across them, because the replicated registry re-routes
        mutations to whichever host currently leads."""
        status = getattr(self.registry, "leader_status", None)
        if status is not None:
            return status()
        return {"host": None, "role": "leader", "leader": None, "term": 0}

    def staged_state(self, name: str) -> Optional[PyTree]:
        with self._tws_guard:
            return self._staged.get(name)

    # ---- fleet-merge hooks (repro.serve.fleet_merge) -----------------------
    def extract_staged(self, name: str) -> StagedExtraction:
        """Consume the staged chain for a merge round.  Under the
        per-name train-while-serve lock: pop the chain and its base — the
        delta is now the merger's to account for (its durable carry
        record), and the next `serve_and_update` starts a fresh chain
        from whatever state is live by then.  The delta math itself
        happens in the caller, outside every lock."""
        with self._tws_lock(name):
            applied = getattr(self.registry, "applied_seq", None)
            seq = applied(name) if applied is not None else -1
            with self._tws_guard:
                staged = self._staged.pop(name, None)
                base = self._staged_from.pop(name, None)
                updates = self._chain_updates.pop(name, 0)
                self._staged_pushed.pop(name, None)
            return StagedExtraction(staged=staged, chain_base=base,
                                    seq=seq, updates=updates)

    # ---- one-shot serving --------------------------------------------------
    def transform(self, name: str, x: jax.Array) -> jax.Array:
        """Serve one request (B, m) → (B, n) (ensembles: (k, B, n)) with the
        live state, padded to the bucket shape and run through the bounded
        compile cache.  Requests above max_bucket are chunked."""
        snap = self.registry.get(name)
        self._check_request(snap, x)
        return self._serve_rows(snap, x)

    # ---- micro-batched serving ---------------------------------------------
    def submit(self, name: str, x: jax.Array, *,
               max_delay_ms: Optional[float] = None) -> Ticket:
        """Enqueue a ragged request; returns a Ticket resolved by `flush`.
        On a service without a mesh the ticket resolves with the kind of
        array it was given: a host (numpy) request with numpy rows, a
        `jax.Array` request with a `jax.Array` (see `flush`).  Raises
        `batching.QueueFull` past max_queue rows (backpressure; transient
        — retry after a flush) and `ValueError` for requests larger than
        max_queue outright (never admittable — chunk them).
        `max_delay_ms` sets the ticket's deadline relative to now — a
        `DeadlineScheduler` wrapping this service flushes the bucket when
        it expires; without one it only bounds the SLO miss accounting."""
        snap = self.registry.get(name)          # fail fast on unknown names
        self._check_request(snap, x)
        now = self.clock.now()
        deadline = None if max_delay_ms is None else now + max_delay_ms
        return self.batcher.submit(name, x, int(x.shape[0]),
                                   submitted_at=now, deadline=deadline)

    def submit_step(self, tag: Hashable, kind: str,
                    fn: Callable[..., Any], *args: Any,
                    rows: int = 1,
                    max_delay_ms: Optional[float] = None) -> Ticket:
        """Admit a non-DR step (an already-batched callable, e.g. an LM
        prefill or decode) through the SAME queue as DR traffic: it shares
        backpressure, FIFO ordering, deadline scheduling, and SLO
        accounting (under bucket label `kind`).  The ticket resolves with
        `fn(*args)` at flush time."""
        now = self.clock.now()
        deadline = None if max_delay_ms is None else now + max_delay_ms
        return self.batcher.submit(_StepKey(tag, kind), _StepWork(fn, args),
                                   int(rows), submitted_at=now,
                                   deadline=deadline)

    def flush(self, keys: Optional[Sequence[Hashable]] = None) -> int:
        """Coalesce the queue into bucketed batches, run them, resolve every
        ticket with its own rows.  A DR group whose payloads are all host
        arrays, on a service without a mesh, is joined and zero-padded to
        its buckets in numpy, sent in one transfer per batch, and its
        answers come back in one copy and are sliced on the host (numpy
        results); otherwise the join, pad and per-ticket slices are
        device ops (`jax.Array` results), which compile per new mix of
        request sizes.  With `keys`, only those groups flush (the deadline
        scheduler's partial flush).  Returns the number of device batches
        THIS call ran (counted locally — a concurrent caller's batches
        never leak into the return value)."""
        n_batches = 0
        for name, items in self.batcher.drain(keys):
            tickets = [t for _, t in items]
            t_flush = self.clock.now()
            try:
                if isinstance(name, _StepKey):
                    # steps are independent (never coalesced): one failing
                    # step fails only its own ticket, the rest still run
                    stage = f"step.{name.kind}"
                    for work, t in items:
                        t_start = self.clock.now()
                        try:
                            with self.slo.span(stage):
                                out = work.fn(*work.args)
                        except Exception as e:  # noqa: BLE001
                            t._fail(e)
                            continue
                        with self._metrics_lock:
                            self.batches_run += 1
                        n_batches += 1
                        # record BEFORE resolve: a waiter woken by the
                        # ticket must find its sample already counted
                        self._record_slo(str(name.tag), name.kind, t,
                                         started=t_start, flushed=t_flush)
                        t._resolve(out)
                    continue
                with self.slo.span("flush.dr"):
                    n_batches += self._flush_dr(name, items, t_flush)
            except Exception as e:          # noqa: BLE001 — fail the tickets
                for t in tickets:
                    if not t.done:
                        t._fail(e)
        return n_batches

    def _flush_dr(self, name: str, items: Sequence[Tuple[Any, Ticket]],
                  t_flush: float) -> int:
        """One drained DR group: coalesce its payloads, serve them in
        bucketed batches, resolve each ticket with its rows.  Host
        payloads are joined and padded in numpy (`_host_rows`) and the
        answers sliced from one host copy; any device payload keeps the
        whole group on the device.  Returns the device batches run."""
        with self.slo.span("flush.coalesce"):
            snap = self.registry.get(name)
            # validate every payload against the FLUSH-TIME snapshot:
            # `register(replace=True)` may have swapped the model since
            # submit, and a stale-shaped request must fail alone with a
            # clear message — not blow up the whole group inside the join
            # with an opaque shape error
            good = []
            for payload, t in items:
                if payload.ndim != 2 or \
                        payload.shape[-1] != snap.model.in_dim:
                    t._fail(ValueError(
                        f"request shaped {tuple(payload.shape)} no longer "
                        f"matches {name!r} at flush time (model expects "
                        f"(B, {snap.model.in_dim}) — it was replaced "
                        f"after this request was submitted)"))
                else:
                    good.append((payload, t))
            if not good:
                return 0
            payloads = [p for p, _ in good]
            host = self.mesh is None and \
                all(isinstance(p, np.ndarray) for p in payloads)
            if host:
                xcat = self._host_rows(payloads)
            else:
                xcat = payloads[0] if len(payloads) == 1 else \
                    jnp.concatenate(payloads, axis=0)
        with self.slo.span("serve_rows"):
            ycat = self._serve_rows(snap, xcat)
            if host:
                ycat = np.asarray(ycat)     # the one copy of the answers
        with self.slo.span("flush.resolve"):
            off = 0
            for _, t in good:
                sl = ycat[:, off:off + t.rows] if snap.ensemble \
                    else ycat[off:off + t.rows]
                off += t.rows
                self._record_slo(name, self.buckets.bucket_for(t.rows), t,
                                 started=t_flush, flushed=t_flush)
                t._resolve(sl)
        # _serve_rows consumes max_bucket rows per device batch
        return -(-off // self.buckets.max_bucket)

    def _host_rows(self, payloads: Sequence[np.ndarray]) -> _HostRows:
        """Join host payloads into one zero-filled numpy buffer laid out
        as `_serve_rows`' bucket batches.  The dtype is the one a device
        join would give (JAX's promotion, canonicalized), so the bucket
        program and its answers are the device path's."""
        dtype = jax.dtypes.canonicalize_dtype(
            functools.reduce(jnp.promote_types, [p.dtype for p in payloads]))
        rows = sum(p.shape[0] for p in payloads)
        step = self.buckets.max_bucket
        last = (rows - 1) // step * step        # first row of the last batch
        buf = np.zeros((last + self.buckets.bucket_for(rows - last),
                        payloads[0].shape[1]), dtype)
        off = 0
        for p in payloads:
            buf[off:off + p.shape[0]] = p
            off += p.shape[0]
        return _HostRows(buf, rows)

    # ---- LM steps through the same queue ------------------------------------
    # The *_step builders are the single source of truth for how an LM step
    # is constructed (params copy, cache key, rows derivation, donation
    # contract); both the direct lm_* methods and the DeadlineScheduler's
    # LM helpers call them, so the two admission paths can't drift apart.
    # Each returns the params the program takes: the compute-dtype copy of
    # the caller's tree (`serve_step.ComputeParamsCache`), or the caller's
    # tree itself where it needs no cast.
    def prefill_step(self, cfg: Any, mesh: Mesh, params: PyTree,
                     batch: PyTree, cache_size: int,
                     ) -> Tuple[Callable[..., Any], PyTree, int]:
        """(jitted prefill, its params, batch rows) — the jit comes from
        THIS service's bounded compile cache, shared with the DR bucket
        programs."""
        params = self.lm_params.get(cfg, params)
        fn = serve_step.make_prefill(cfg, mesh, params, batch, cache_size,
                                     cache=self.cache)
        rows = jax.tree.leaves(batch)[0].shape[0]
        return fn, params, int(rows)

    def decode_step(self, cfg: Any, mesh: Mesh, params: PyTree,
                    token: jax.Array, kv_cache: PyTree,
                    ) -> Tuple[Callable[..., Any], PyTree, int]:
        """(jitted decode, its params, batch rows); the kv cache is donated
        — don't reuse the argument after the step runs."""
        params = self.lm_params.get(cfg, params)
        fn = serve_step.make_decode(cfg, mesh, params, kv_cache,
                                    cache=self.cache)
        return fn, params, int(token.shape[0])

    def lm_prefill(self, cfg: Any, mesh: Mesh, params: PyTree, batch: PyTree,
                   cache_size: int, *, tag: Hashable = "lm",
                   max_delay_ms: Optional[float] = None) -> Ticket:
        """Admit one LM prefill through the queue; resolves with
        `(logits, kv_cache)`."""
        fn, params, rows = self.prefill_step(cfg, mesh, params, batch,
                                             cache_size)
        return self.submit_step(tag, "prefill", fn, params, batch,
                                rows=rows, max_delay_ms=max_delay_ms)

    def lm_decode(self, cfg: Any, mesh: Mesh, params: PyTree, token: jax.Array,
                  kv_cache: PyTree, *, tag: Hashable = "lm",
                  max_delay_ms: Optional[float] = None) -> Ticket:
        """Admit one LM decode step through the queue (same contract as
        `lm_prefill`)."""
        fn, params, rows = self.decode_step(cfg, mesh, params, token,
                                            kv_cache)
        return self.submit_step(tag, "decode", fn, params, token, kv_cache,
                                rows=rows, max_delay_ms=max_delay_ms)

    # ---- train-while-serve -------------------------------------------------
    def _fused_update_fn(self, snap: Snapshot, x: jax.Array):
        """Fetch (or build) the jitted fused transform+update program for
        this (config, batch shape) — and make sure a cache miss pays its
        trace+compile HERE, not at first real use.  `jax.jit` is lazy, so
        the builder drives one dummy batch (zeros, result discarded)
        through the fresh program before returning it.

        Called OUTSIDE the per-name train-while-serve lock on purpose:
        holding `_tws_lock(name)` across a multi-second jit compile would
        convoy every concurrent `serve_and_update`/`promote` for the name
        behind one cold shape (the blocking-under-lock hazard the
        analysis suite now flags).  The build closes over the model
        CONFIG only — live/staged states are call arguments."""
        key = ("fused", snap.chash, x.shape, str(x.dtype))
        model = snap.model  # close over the config only, never the state
        state = snap.state

        def build():
            fn = jax.jit(
                lambda live, st, xb: (model.transform(live, xb),
                                      model.update(st, xb)))
            jax.block_until_ready(
                fn(state, state, jnp.zeros_like(x)))
            return fn

        return self.cache.get_or_build(key, build)

    def serve_and_update(self, name: str, x: jax.Array) -> jax.Array:
        """Answer `x` with the LIVE state and stream it through
        `model.update` into the STAGED state (every `1/update_fraction`-th
        block on average, deterministically via an accumulator).  The
        staged state chains across calls, so a full stream followed by
        `promote()` equals an offline `fit` with the same block order.

        The update step runs under the per-name train-while-serve lock:
        the snapshot read, the update, and the staged write are one atomic
        step w.r.t. a concurrent `promote()` — updates for the same name
        serialize (they must: staged states chain), different names stream
        in parallel.  The fused program is built BEFORE the lock (see
        `_fused_update_fn`); a `register(replace=True)` racing the
        pre-build is detected by config-hash mismatch under the lock and
        rebuilt there (rare, waived)."""
        with self.slo.span("serve_and_update"):
            return self._serve_and_update(name, x)

    def _serve_and_update(self, name: str, x: jax.Array) -> jax.Array:
        snap0 = self.registry.get(name)
        self._check_request(snap0, x)
        if snap0.ensemble:
            raise NotImplementedError(
                "train-while-serve targets single models; ensembles are "
                "serve-only (fit them offline via DREnsemble.fit)")
        with self._tws_guard:
            acc = self._accum.get(name, 0.0) + self.update_fraction
            skip = acc < 1.0 - 1e-9
            self._accum[name] = acc if skip else acc - 1.0
        if skip:                                # no update on this block
            return self._serve_rows(snap0, x)

        fused = self._fused_update_fn(snap0, x)
        with self._tws_lock_timed(name):
            snap = self.registry.get(name)
            if snap.chash != snap0.chash:
                # a replace raced the pre-build: re-validate and rebuild
                # for the new config (compiles under the lock — reviewed:
                # losing this race is as rare as the replace itself)
                self._check_request(snap, x)
                fused = self._fused_update_fn(snap, x)  # analysis: allow(blocking-under-lock)
            with self._tws_guard:
                staged = self._staged.get(name)
                if staged is None:
                    # a fresh chain starts here: remember the base it is
                    # folded from, so a merge round can extract the delta
                    staged = snap.state
                    self._staged_from[name] = snap.state
                    self._chain_updates[name] = 0
            y, new_staged = fused(snap.state, staged, x)
            with self._tws_guard:
                self._staged[name] = new_staged
                self._updates[name] = self._updates.get(name, 0) + 1
                self._chain_updates[name] = \
                    self._chain_updates.get(name, 0) + 1
        with self._metrics_lock:
            self.served_rows += int(x.shape[0])
            self.batches_run += 1
        return y

    # ---- warmup / metrics --------------------------------------------------
    def warmup(self, name: str, *, dtype=jnp.float32,
               buckets: Optional[Sequence[int]] = None) -> int:
        """Pre-compile the transform for every bucket shape (or the given
        subset) so first-request latency doesn't eat the trace."""
        snap = self.registry.get(name)
        n0 = self.cache.misses
        for b in (buckets if buckets is not None else self.buckets.buckets()):
            fn = self._transform_fn(snap, b, jnp.dtype(dtype))
            # jax.jit is lazy — drive one dummy batch so the trace+compile
            # happens here, not on the first real request
            jax.block_until_ready(
                fn(snap.state, jnp.zeros((b, snap.model.in_dim), dtype)))
        return self.cache.misses - n0

    def metrics(self) -> Dict[str, Any]:
        met, missed = self.slo.deadline_counts()
        # counters are written under these locks from caller threads and the
        # scheduler loop — read them the same way, or a report racing a
        # flush returns torn (partially bumped) numbers
        with self._metrics_lock:
            served = self.served_rows
            padded = self.padded_rows
            batches = self.batches_run
            host_batches = self.host_batches
            autotunes = self.autotunes
        with self._tws_guard:
            updates = dict(self._updates)
            staged = sorted(self._staged)
        return {
            "served_rows": served,
            "padded_rows": padded,
            "batches_run": batches,
            "host_batches": host_batches,
            "autotunes": autotunes,
            "updates_applied": updates,
            "staged": staged,
            "compile_cache": self.cache.stats(),
            "queue": self.batcher.stats(),
            "slo": self.slo.report(),
            "stages": self.slo.stages(),
            "deadline_met": met,
            "deadline_missed": missed,
        }

    # ---- internals ---------------------------------------------------------
    def _record_slo(self, name: str, bucket: Hashable, t: Ticket, *,
                    started: float, flushed: float) -> None:
        # `bucket` is the ticket's NOMINAL size class (bucket_for(rows)) —
        # a coalesced flush may physically run a larger batch, but keeping
        # attribution per-request gives each size class one stable cell.
        # The queue delay ends when the ticket's own work `started` (a
        # step drained behind others waits for them too).  `deadline_ok`
        # is judged on FLUSH START, not post-compute resolution:
        # max_delay_ms bounds the batching window (how long the queue may
        # hold a request), so a deadline-triggered flush that starts on
        # time IS met — judging on resolution would brand every
        # deadline-expiry flush a miss by construction.
        if t.submitted_at is None:
            return
        now = self.clock.now()
        self.slo.record(
            name, bucket,
            queue_delay_ms=max(0.0, started - t.submitted_at),
            e2e_ms=max(0.0, now - t.submitted_at),
            deadline_ok=None if t.deadline is None else flushed <= t.deadline)

    def _check_request(self, snap: Snapshot, x: jax.Array) -> None:
        if x.ndim != 2 or x.shape[-1] != snap.model.in_dim:
            raise ValueError(
                f"request for {snap.name!r} must be (B, {snap.model.in_dim}); "
                f"got {x.shape}")
        if x.shape[0] < 1:
            raise ValueError("empty request")

    def _transform_fn(self, snap: Snapshot, bucket: int, dtype):
        key = ("transform", snap.chash, snap.ensemble, self.mesh is not None,
               bucket, str(dtype))

        def build():
            if self.mesh is not None:
                return dr_serve.make_dr_transform(
                    snap.model, self.mesh, batch_size=bucket,
                    ensemble=snap.ensemble)
            if snap.ensemble:
                return jax.jit(snap.model.ensemble(snap.ensemble).transform)
            exe = getattr(snap.model, "execution", None)
            if exe is not None and getattr(exe, "use_kernel", False):
                return self._tuned_transform(snap.model, snap.state,
                                             bucket, dtype)
            return jax.jit(snap.model.transform)

        return self.cache.get_or_build(key, build)

    def _tuned_transform(self, model: Any, state: PyTree, bucket: int, dtype):
        """Sweep the Pallas tile knobs for this (bucket, device) and return
        the winning jitted bucket program.  The returned `TunedProgram`
        carries the winning `TileConfig` alongside the compiled callable,
        and it is THE value cached under the transform key — a promote
        (same config hash) hits the cache and never re-tunes, an eviction
        drops the program and its tiles in one step, and a post-eviction
        rebuild runs the sweep again."""
        stages = getattr(model, "stages", None)
        if not stages:                      # no tile surface to tune
            return jax.jit(model.transform)
        exe = model.execution
        # the leading matmul's dims bound the effective tile shapes; the
        # policy's own tiles race first so a hand-tiled Execution wins ties
        cands = autotune.candidates(
            bucket, stages[0].out_dim, model.in_dim,
            first=autotune.TileConfig(exe.tmm_block_m, exe.tmm_block_p,
                                      exe.tmm_block_k))

        def build_candidate(tiles: autotune.TileConfig):
            exe2 = dataclasses.replace(
                exe, tmm_block_m=tiles.block_m, tmm_block_p=tiles.block_p,
                tmm_block_k=tiles.block_k)
            return jax.jit(model.with_execution(exe2).transform)

        prog = autotune.tune(
            cands, build_candidate,
            (state, jnp.zeros((bucket, model.in_dim), dtype)),
            timer=self.clock.now)
        with self._metrics_lock:
            self.autotunes += 1
        return prog

    def _serve_rows(self, snap: Snapshot,
                    x: jax.Array | _HostRows) -> jax.Array:
        """Run (R, m) rows through bucketed batches; returns (R, n) rows in
        order ((k, R, n) for ensembles).  Given `_HostRows`, each batch is
        a bucket-shaped view of its buffer, sent as it is, and the
        bucket-padded outputs come back joined in the buffer's layout:
        row r of the result (axis 1 for ensembles) is row r of the
        buffer."""
        host = isinstance(x, _HostRows)
        total = x.rows if host else x.shape[0]
        dtype = x.buf.dtype if host else x.dtype
        outs = []
        i, step = 0, self.buckets.max_bucket
        while i < total:
            rows = min(step, total - i)
            bucket = self.buckets.bucket_for(rows)
            fn = self._transform_fn(snap, bucket, dtype)
            if host:
                outs.append(fn(snap.state, x.buf[i:i + bucket]))
            else:
                y = fn(snap.state, _pad_rows(x[i:i + rows], bucket))
                outs.append(y[:, :rows] if snap.ensemble else y[:rows])
            with self._metrics_lock:
                self.padded_rows += bucket - rows
                self.served_rows += rows
                self.batches_run += 1
                self.host_batches += int(host)
            i += rows
        if len(outs) == 1:
            return outs[0]
        return jnp.concatenate(outs, axis=1 if snap.ensemble else 0)
