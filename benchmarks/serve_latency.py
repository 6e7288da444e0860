"""Serving-engine latency: open-loop synthetic load vs batch-bucket policy.

Protocol (EXPERIMENTS.md §Serving): a ragged request stream (lognormal row
counts, fixed seed) is submitted to a `DRService` in fixed-size admission
windows — open-loop: the window arrives regardless of service progress —
then `flush()` coalesces each window into bucketed micro-batches.  Per
request we record submit→result wall time; rows report p50/p99 latency,
steady-state throughput, the compile count, and the padding overhead for
each bucket policy:

  pow2     — powers-of-two padding (the engine default): O(log max/min)
             compiled programs, some padded rows.
  exact    — no coalescing headroom (`batching.EXACT`), the pre-engine
             behavior: one compiled program per distinct request size.
  deadline — pow2 buckets behind the `DeadlineScheduler` event loop: no
             explicit flush at all; each request carries `max_delay_ms`
             and the loop flushes on fill-or-deadline.  Reports the
             deadline-miss rate next to the same compile count as pow2
             (deadline flushes reuse the bucketed programs).

A train-while-serve row exercises the full register → serve_and_update →
promote → transform round trip on the same stream.

`--backend pallas` reruns the backend-dependent rows (pow2, train-while-
serve) with the model registered under `Execution(backend="pallas")` —
the bucketed transform dispatches to the fused pad+project+whiten Pallas
kernel and the streamed updates to `kernels.ops.easi_update`, autotuned
per bucket at register time.  Those rows are suffixed `@pallas` so the
XLA baselines don't mis-gate them; the exact/deadline and fleet rows are
backend-independent and are skipped.

A kernels row (emitted under EVERY backend flag) serves bucket-shaped
batches through an autotuned pallas service and converts best-of wall
times to achieved FLOP/s (model FLOPs: 2mp + 2pn per row — the paper's
project-then-whiten datapath).  On a device kind with a published peak
(`repro.launch.roofline.DEVICE_PEAKS`) it also reports
`utilization_frac` against that peak; elsewhere the row carries no
utilization at all.

A replicated-promote row runs a 3-host `LocalBus` fleet (one leader +
two follower `ReplicatedRegistry`s, each behind its own `DRService`) and
measures the two-phase flip: `flip_ms` is time-to-consistency (promote
call → every host uniformly on the new version, i.e. quorum-ack on the
synchronous bus), while reader threads hammering the follower engines
count how many requests were answered against the stale version during
the flip window.

A failover row runs the same fleet with an `Elector` per host (real
`MonotonicClock`, loopless polling) and measures `failover_ms`: the time
from killing the leader to the FIRST successful promote on the newly
elected leader — the fleet-availability number the election layer exists
to bound (≈ election timeout + one vote round + one two-phase flip).

A durability row builds a solo durable host (`ReplicatedRegistry` with
`data_dir=`), pushes a stack of versions, promotes, compacts, then cold
restarts from disk: `restore_ms` is the full bootstrap (WAL scan + torn
tail truncate + snapshot load + op replay) and `snapshot_bytes` the
compacted on-disk footprint.

Three fleet-merge rows (`fleet_merge_{1,8,32}x`) run a 3-host fleet with
a `FleetMerger` per host: every host streams a disjoint shard through
`serve_and_update`, then the leader drives one compressed delta-merge
round end to end.  `merge_wall_ms` is the warm round (collect + sketch
all-reduce + projection decode + quorum promote + commit) and
`wire_bytes` what actually crossed the bus — both CEILING-gated, so a
compression regression (sketches silently riding the raw path) or a
merge-path slowdown fails CI's fleet-merge job.

`--json out.json` additionally writes the rows machine-readably (the
`derived` k=v pairs parsed into fields); CI uploads that artifact and
gates `flip_ms` / `p99_us` / `failover_ms` / `restore_ms` /
`snapshot_bytes` / `merge_wall_ms` / `wire_bytes` against
`benchmarks/baseline.json` at a generous 2x via
`benchmarks/check_regression.py`.

Run: PYTHONPATH=src python benchmarks/serve_latency.py [--smoke] [--full]
[--json out.json] (or through `python -m benchmarks.run --only
serve_latency`).
"""

from __future__ import annotations

import argparse
import json
import shutil
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.execution import Execution
from repro.dist.compress import CompressConfig, collective_bytes_saved
from repro.dr import DRModel, EASIStage, RPStage
from repro.launch import roofline
from repro.launch.compile_cache import use_compile_cache
from repro.serve import (BucketPolicy, DRService, DeadlineScheduler, Elector,
                         FleetMerger, LocalBus, ReplicatedRegistry,
                         ReplicationError, state_hash)
from repro.serve.batching import EXACT


def _model(m=32, p=16, n=8, block=8, backend="xla"):
    return DRModel(stages=(RPStage(m, p), EASIStage.rotation(p, n, mu=5e-4)),
                   execution=Execution(backend=backend), block_size=block)


def _requests(n_req: int, m: int, *, seed: int = 0, max_rows: int = 48):
    """Ragged synthetic load: lognormal row counts in [1, max_rows]."""
    rng = np.random.RandomState(seed)
    sizes = np.clip(np.rint(rng.lognormal(mean=1.6, sigma=0.9, size=n_req)),
                    1, max_rows).astype(int)
    return [jnp.asarray(rng.randn(s, m).astype(np.float32)) for s in sizes]


def _drive(svc: DRService, name: str, reqs, window: int, *,
           direct: bool = False, scheduler: DeadlineScheduler = None):
    """Submit in open-loop windows; returns per-request latencies (s) and
    the wall time of the measured phase.  `direct=True` bypasses the
    micro-batcher — one device step per request, the pre-engine serving
    shape.  With `scheduler`, nothing ever calls flush(): the deadline
    loop answers, and the driver just waits on the tickets."""
    lat = []
    t_start = time.perf_counter()
    for w0 in range(0, len(reqs), window):
        batch = reqs[w0:w0 + window]
        if direct:
            for x in batch:
                s = time.perf_counter()
                jax.block_until_ready(svc.transform(name, x))
                lat.append(time.perf_counter() - s)
            continue
        submit_t, tickets = [], []
        for x in batch:
            submit_t.append(time.perf_counter())
            tickets.append(scheduler.submit(name, x) if scheduler is not None
                           else svc.submit(name, x))
        if scheduler is None:
            svc.flush()
        for t in tickets:
            if scheduler is not None:
                t.wait(30.0)
            jax.block_until_ready(t.result())
        done = time.perf_counter()
        lat.extend(done - s for s in submit_t)
    return np.asarray(lat), time.perf_counter() - t_start


def run(fast: bool = True, backend: str = "xla"):
    n_req = 64 if fast else 512
    window = 8
    suffix = "" if backend == "xla" else f"@{backend}"
    model = _model(backend=backend)
    state = model.init(jax.random.PRNGKey(0))
    reqs = _requests(n_req, model.in_dim)
    total_rows = int(sum(r.shape[0] for r in reqs))

    rows = []
    policies = (("pow2", BucketPolicy(min_bucket=4, max_bucket=64)),
                ("exact", EXACT),
                ("deadline", BucketPolicy(min_bucket=4, max_bucket=64)))
    if suffix:
        # non-default backends rerun only the backend-dependent rows: exact
        # compiles one interpret-mode kernel per distinct request size (an
        # unbounded universe — pointless and slow), and the deadline row is
        # a real-clock scheduler benchmark, independent of the datapath
        policies = policies[:1]
    for tag, policy in policies:
        direct = policy.exact
        svc = DRService(buckets=policy, compile_cache_size=128)
        svc.register("dr", model, state)
        sched = DeadlineScheduler(svc, default_max_delay_ms=2.0,
                                  wake_lead_ms=1.0) \
            if tag == "deadline" else None
        _drive(svc, "dr", reqs, window, direct=direct,
               scheduler=sched)                          # warmup: pay compiles
        compiles = svc.cache.misses
        met0, missed0 = svc.slo.deadline_counts()
        lat, wall = _drive(svc, "dr", reqs, window, direct=direct,
                           scheduler=sched)
        met = svc.metrics()
        p50, p99 = np.percentile(lat, 50), np.percentile(lat, 99)
        pad_frac = met["padded_rows"] / max(1, met["padded_rows"] + met["served_rows"])
        derived = (f"p99_us={p99 * 1e6:.1f};rows_per_s={total_rows / wall:.0f};"
                   f"compiles={compiles};padded_frac={pad_frac:.3f};"
                   f"batches={met['batches_run']}")
        if sched is not None:
            got, missed = (met["deadline_met"] - met0,
                           met["deadline_missed"] - missed0)
            derived += (f";deadline_miss_rate="
                        f"{missed / max(1, got + missed):.3f}")
            sched.shutdown()
        rows.append((f"serve_latency/{tag}{suffix}", p50 * 1e6, derived))

    # train-while-serve: the full round trip on the same stream
    svc = DRService(buckets=BucketPolicy(min_bucket=4, max_bucket=64))
    svc.register("dr", model, state)
    bs = model.block_size
    stream = jnp.concatenate(reqs, axis=0)
    blocks = stream[: (stream.shape[0] // bs) * bs].reshape(-1, bs, model.in_dim)
    t0 = time.perf_counter()
    for blk in blocks:
        jax.block_until_ready(svc.serve_and_update("dr", blk))
    wall = time.perf_counter() - t0
    v = svc.promote("dr")
    y = svc.transform("dr", reqs[0])
    assert bool(jnp.isfinite(y).all()) and v == 1
    rows.append((f"serve_latency/train_while_serve{suffix}",
                 wall / max(1, len(blocks)) * 1e6,
                 f"blocks={len(blocks)};promoted_version={v};"
                 f"updates={svc.metrics()['updates_applied']['dr']}"))

    # the roofline judge rides every backend flag: it builds its own
    # pallas service either way (gated by the same floor in baseline.json)
    rows.append(_kernels_row(fast))
    if suffix:
        return rows     # fleet + durability rows are backend-independent

    # replicated promote: 3-host fleet, two-phase flip under live traffic
    bus = LocalBus()
    leader = ReplicatedRegistry(bus.attach("h0"), role="leader")
    regs = [leader] + [ReplicatedRegistry(bus.attach(f"h{i}"),
                                          role="follower", leader="h0")
                       for i in (1, 2)]
    svcs = [DRService(registry=r,
                      buckets=BucketPolicy(min_bucket=4, max_bucket=64))
            for r in regs]
    leader.register("dr", model, state)
    retrained = model.fit(state, stream[:256], epochs=1)
    v = leader.push("dr", retrained)                 # replicated, NOT live
    x_probe = reqs[0]
    for s in svcs:                                   # warm every host's jit
        jax.block_until_ready(s.transform("dr", x_probe))
    lock = threading.Lock()
    samples = []                                     # (snapshot time, version)
    stop = threading.Event()

    def reader(s):
        while not stop.is_set():
            t_read = time.perf_counter()
            served_v = s.registry.get("dr").version  # epoch this request sees
            jax.block_until_ready(s.transform("dr", x_probe))
            with lock:
                samples.append((t_read, served_v))

    readers = [threading.Thread(target=reader, args=(s,)) for s in svcs[1:]]
    for th in readers:
        th.start()
    t0 = time.perf_counter()
    leader.promote("dr", v)                          # two-phase fleet flip
    t1 = time.perf_counter()
    flip_ms = (t1 - t0) * 1e3
    finals = [r.get("dr").version for r in regs]
    stop.set()
    for th in readers:
        th.join(30.0)
    # only requests whose SNAPSHOT landed inside [promote start, quorum-ack]
    # count toward the flip window — anything earlier legitimately serves old
    window = [v_ for t, v_ in samples if t0 <= t <= t1]
    stale = sum(1 for v_ in window if v_ == 0)
    rows.append(("serve_latency/replicated_promote", flip_ms * 1e3,
                 f"hosts=3;flip_ms={flip_ms:.2f};"
                 f"stale_served_during_flip={stale};"
                 f"reads_during_flip_window={len(window)};"
                 f"final_versions={'/'.join(map(str, finals))}"))

    # failover: kill the leader, elect, first successful promote on the
    # winner.  Electors run loopless on the REAL clock (this is a wall-time
    # benchmark): the driver polls them the way a background loop would.
    bus = LocalBus()
    leader = ReplicatedRegistry(bus.attach("h0"), role="leader")
    regs = [leader] + [ReplicatedRegistry(bus.attach(f"h{i}"),
                                          role="follower", leader="h0")
                       for i in (1, 2)]
    electors = [Elector(r, seed=i, election_timeout_ms=(30.0, 60.0),
                        heartbeat_interval_ms=10.0)
                for i, r in enumerate(regs)]
    leader.register("dr", model, state)
    v = leader.push("dr", retrained)            # committed fleet-wide
    bus.partition("h0")                         # the leader dies
    t0 = time.perf_counter()
    deadline = t0 + 30.0
    new_v = None
    while time.perf_counter() < deadline:
        for e in electors[1:]:
            e.poll()
        cands = [r for r in regs[1:] if r.role == "leader"]
        if not cands:
            time.sleep(1e-3)
            continue
        try:
            new_v = cands[0].promote("dr", v)   # first promote on the winner
            break
        except ReplicationError:
            time.sleep(1e-3)                    # vote round still settling
    failover_ms = (time.perf_counter() - t0) * 1e3
    assert new_v == v, "failover benchmark never promoted on a new leader"
    winners = [r.transport.host_id for r in regs[1:] if r.role == "leader"]
    term = max(r.term for r in regs[1:])
    finals = sorted(r.get("dr").version for r in regs[1:])
    rows.append(("serve_latency/failover", failover_ms * 1e3,
                 f"hosts=3;failover_ms={failover_ms:.2f};"
                 f"winner={winners[0]};term={term};"
                 f"final_versions={'/'.join(map(str, finals))}"))

    # durability: WAL + blobs + compacted snapshot on a solo durable host,
    # then a cold restart from disk.  `restore_ms` is the full bootstrap
    # (open WAL, truncate any torn tail, load snapshot, replay ops through
    # the registry) and `snapshot_bytes` the total on-disk footprint after
    # compaction — both gated at 2x against baseline.json.
    n_states = 8 if fast else 32
    data_dir = tempfile.mkdtemp(prefix="serve-durability-")
    try:
        reg = ReplicatedRegistry(LocalBus().attach("h0"), role="leader",
                                 quorum=1, data_dir=data_dir)
        reg.register("dr", model, state)
        v = 0
        for i in range(1, n_states):
            v = reg.push("dr", model.init(jax.random.PRNGKey(i)))
        reg.promote("dr", v)
        reg.compact()
        want_hash = state_hash(reg.get("dr").state)
        snapshot_bytes = reg.durable.size_bytes()
        del reg                                     # crash: no close
        t0 = time.perf_counter()
        reg2 = ReplicatedRegistry(LocalBus().attach("h0"), role="leader",
                                  quorum=1, data_dir=data_dir)
        restore_ms = (time.perf_counter() - t0) * 1e3
        restored_v = reg2.get("dr").version
        assert state_hash(reg2.get("dr").state) == want_hash, \
            "durability benchmark restored different bytes"
        rows.append(("serve_latency/durability", restore_ms * 1e3,
                     f"restore_ms={restore_ms:.2f};"
                     f"snapshot_bytes={snapshot_bytes};"
                     f"versions={n_states};restored_version={restored_v}"))
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)

    # fleet merge: 3 hosts stream DISJOINT shards through serve_and_update,
    # then the leader runs one compressed delta-merge round per ratio
    # (collect -> sketch all-reduce -> projection decode -> quorum promote
    # -> commit).  `merge_wall_ms` is the full round on a warm fleet;
    # `wire_bytes` is what actually crossed the bus (round report) and
    # `sketch_ratio` the accounting from `collective_bytes_saved` — the
    # wall time and wire bytes are gated 2x per ratio in baseline.json.
    bs = model.block_size
    n_blocks = 6 if fast else 24
    for ratio in (1, 8, 32):
        cfg = CompressConfig(ratio=ratio, min_size=64)
        bus = LocalBus()
        leader = ReplicatedRegistry(bus.attach("h0"), role="leader")
        regs = [leader] + [ReplicatedRegistry(bus.attach(f"h{i}"),
                                              role="follower", leader="h0")
                           for i in (1, 2)]
        svcs = [DRService(registry=r,
                          buckets=BucketPolicy(min_bucket=4, max_bucket=64))
                for r in regs]
        mergers = [FleetMerger(s, compress_cfg=cfg) for s in svcs]
        leader.register("dr", model, state)
        rng = np.random.RandomState(11 + ratio)

        def _feed():
            for si, s in enumerate(svcs):
                for _ in range(n_blocks):
                    blk = jnp.asarray(
                        rng.randn(bs, model.in_dim).astype(np.float32)
                        + 0.25 * si)
                    jax.block_until_ready(s.serve_and_update("dr", blk))

        _feed()
        mergers[0].merge_round("dr")    # warmup: pay the sketch-path jits
        _feed()
        rep = mergers[0].merge_round("dr")
        assert rep["version"] is not None and len(rep["contributors"]) == 3, rep
        acct = collective_bytes_saved(state, cfg)
        rows.append((f"serve_latency/fleet_merge_{ratio}x",
                     rep["wall_ms"] * 1e3,
                     f"hosts=3;ratio={ratio};"
                     f"merge_wall_ms={rep['wall_ms']:.2f};"
                     f"wire_bytes={rep['bytes_sketched']};"
                     f"uncompressed_bytes={rep['bytes_uncompressed']};"
                     f"sketch_ratio={acct['ratio']:.2f};"
                     f"contributors={len(rep['contributors'])};"
                     f"updates_folded={rep['updates_folded']};"
                     f"version={rep['version']}"))
    return rows


def _kernels_row(fast: bool):
    """Achieved FLOP/s of the autotuned fused serve transform per bucket
    (EXPERIMENTS.md §Kernels).

    Model FLOPs per served row are the paper datapath's useful work —
    2mp (ternary project) + 2pn (whiten/rotate map) — the same
    model-vs-achieved accounting as SNIPPETS.md's MODEL_FLOPS_PER_SAMPLE
    tables.  `utilization_frac` (best bucket's achieved/peak) is reported
    only where the device kind has a published peak."""
    m, p, n = 32, 16, 8
    model = _model(m, p, n, backend="pallas")
    state = model.init(jax.random.PRNGKey(0))
    buckets = (16, 64) if fast else (16, 64, 256)
    svc = DRService(buckets=BucketPolicy(min_bucket=buckets[0],
                                         max_bucket=buckets[-1]),
                    compile_cache_size=64)
    svc.register("dr", model, state)            # register-time tile sweep
    flops_per_row = 2 * m * p + 2 * p * n
    kind = jax.devices()[0].device_kind
    try:
        peak, _ = roofline.device_peak_flops(kind)
    except KeyError:                    # no published peak: no utilization
        peak = None
    rng = np.random.RandomState(0)
    best_achieved, parts, t_best = 0.0, [], float("inf")
    for b in buckets:
        x = jnp.asarray(rng.randn(b, m).astype(np.float32))
        jax.block_until_ready(svc.transform("dr", x))       # warm
        t_best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(svc.transform("dr", x))
            t_best = min(t_best, time.perf_counter() - t0)
        achieved = b * flops_per_row / t_best
        best_achieved = max(best_achieved, achieved)
        parts.append(f"gflops_b{b}={achieved / 1e9:.4f}")
    if peak is not None:
        parts.append(f"utilization_frac={best_achieved / peak:.6f}"
                     f";peak_gflops={peak / 1e9:.1f}")
    derived = (";".join(parts)
               + f";device_kind={kind.replace(';', ',')}"
               f";autotunes={svc.metrics()['autotunes']}"
               f";flops_per_row={flops_per_row}"
               f";platform={jax.default_backend()}")
    return ("serve_latency/kernels", t_best * 1e6, derived)


def _parse_derived(derived: str):
    out = {}
    for kv in derived.split(";"):
        if "=" not in kv:
            continue
        k, v = kv.split("=", 1)
        try:
            out[k] = float(v) if "." in v or "e" in v.lower() else int(v)
        except ValueError:
            out[k] = v
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast run + sanity assertions (CI)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", metavar="PATH",
                    help="also write machine-readable rows (CI artifact + "
                         "regression gate input)")
    ap.add_argument("--backend", choices=("xla", "pallas"), default="xla",
                    help="Execution backend the served model registers "
                         "with; pallas reruns the backend-dependent rows "
                         "through the fused kernels (rows suffixed @pallas)")
    args = ap.parse_args()
    use_compile_cache()

    rows = run(fast=not args.full, backend=args.backend)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if args.json:
        payload = [{"name": name, "us_per_call": us, **_parse_derived(d)}
                   for name, us, d in rows]
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {args.json} ({len(payload)} rows)")

    if args.smoke:
        sfx = "" if args.backend == "xla" else f"@{args.backend}"
        by = {n: d for n, _, d in rows}
        pow2_compiles = int(by[f"serve_latency/pow2{sfx}"]
                            .split("compiles=")[1].split(";")[0])
        # the bucketed compile universe must be tiny — for pallas that
        # includes the register-time autotuned bucket programs
        assert pow2_compiles <= 6, pow2_compiles
        assert "promoted_version=1" in by[f"serve_latency/train_while_serve{sfx}"]
        kd = by["serve_latency/kernels"]
        assert int(kd.split("autotunes=")[1].split(";")[0]) >= 1, kd
        if not sfx:
            exact_compiles = int(by["serve_latency/exact"].split("compiles=")[1].split(";")[0])
            ddl_compiles = int(by["serve_latency/deadline"].split("compiles=")[1].split(";")[0])
            # bucketing must beat exact shapes
            assert pow2_compiles < exact_compiles, (pow2_compiles, exact_compiles)
            # deadline flushes reuse the same bucketed programs — no new compiles
            assert ddl_compiles <= 6, ddl_compiles
            # miss = flush STARTED past the budget; a scheduler that only ever
            # drains at shutdown would miss everything — that must not pass
            miss = float(by["serve_latency/deadline"]
                         .split("deadline_miss_rate=")[1].split(";")[0])
            assert 0.0 <= miss < 1.0, miss
            # the fleet flip must end uniformly on the new version — a mixed
            # final epoch means the two-phase promote tore the deployment
            assert "final_versions=1/1/1" in by["serve_latency/replicated_promote"]
            # failover: both SURVIVING hosts must be uniformly on the promoted
            # version, flipped by a leader elected at a real (>0) term
            assert "final_versions=1/1" in by["serve_latency/failover"]
            assert int(by["serve_latency/failover"]
                       .split("term=")[1].split(";")[0]) >= 1
            # durability: the cold restart must come back on the promoted
            # version (the content-hash identity is asserted inside run())
            dur = by["serve_latency/durability"]
            n_states = int(dur.split("versions=")[1].split(";")[0])
            restored = int(dur.split("restored_version=")[1].split(";")[0])
            assert restored == n_states - 1, (restored, n_states)
            assert int(dur.split("snapshot_bytes=")[1].split(";")[0]) > 0
        print("SERVE_LATENCY_SMOKE_OK")


if __name__ == "__main__":
    main()
