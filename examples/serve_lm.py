"""Batched LM serving driven through the serving engine.

Two request paths, ONE admission queue, one deadline scheduler:

  * DR features — each request carries a ragged block of feature frames
    (the paper's deployment side).  Requests are submitted with a
    latency budget (`max_delay_ms`); the `DeadlineScheduler` event loop
    coalesces them into powers-of-two buckets and flushes on
    fill-or-deadline — no explicit flush() anywhere.  The same traffic
    also streams through `model.update` (train-while-serve) and the
    retrained state is promoted live at the end.
  * LM tokens — prefill a batch of prompts, decode greedily with the KV
    cache.  The steps route through the SAME queue (`svc.lm_prefill` /
    `svc.lm_decode` via the scheduler), compiled into the SAME bounded
    cache as the DR bucket programs — one scheduler, one LRU, shared
    backpressure and SLO accounting for both workloads.

The DR model lives in a replicated 3-host registry (one leader + two
follower `ReplicatedRegistry`s on a `LocalBus`): the serving engine runs
on the leader, and the train-while-serve promote is a two-phase
fleet-wide flip — after it returns, every host in the fleet answers with
the retrained state, not just the host that retrained.

The finale is a leader FAILOVER: each host gets an `Elector`
(term-numbered election over the same bus, real `MonotonicClock`), the
leader host is partitioned away, a follower wins a higher term, and the
next retrained state is promoted through the NEW leader — issued on a
follower and forwarded automatically.  The healed old leader is fenced
by the higher term, rejoins as a follower, and converges by
anti-entropy: retraining keeps shipping no matter which host dies.

Run: PYTHONPATH=src python examples/serve_lm.py [--tokens 16] [--batch 4]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.dr import DRModel, EASIStage, RPStage
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_smoke_mesh
from repro.models import api
from repro.serve import (BucketPolicy, DRService, DeadlineScheduler, Elector,
                         LocalBus, ReplicatedRegistry, ReplicationError)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o_danube3_4b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--frame-dim", type=int, default=32)
    args = ap.parse_args()
    use_compile_cache()

    cfg = registry.get_smoke(args.arch)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab_size)
    cache_size = args.prompt_len + args.tokens

    # ---- one engine, one deadline scheduler for BOTH workloads ------------
    # the DR registry is REPLICATED: this engine serves on the leader, two
    # follower hosts shadow every register/push/promote over the bus
    dr = DRModel(stages=(RPStage(args.frame_dim, 16),
                         EASIStage.rotation(16, 8, mu=5e-4)), block_size=8)
    bus = LocalBus()
    leader = ReplicatedRegistry(bus.attach("h0"), role="leader")
    followers = [ReplicatedRegistry(bus.attach(f"h{i}"), role="follower",
                                    leader="h0") for i in (1, 2)]
    svc = DRService(registry=leader,
                    buckets=BucketPolicy(min_bucket=8, max_bucket=64))
    svc.register("frames", dr, dr.init(jax.random.PRNGKey(2)))
    # wake_lead_ms=1: wake the loop ~1 ms before each deadline so flushes
    # start inside their budget despite real-clock wakeup latency
    sched = DeadlineScheduler(svc, default_max_delay_ms=5.0, wake_lead_ms=1.0)

    # DR feature path: ragged traffic with a 5 ms latency budget — the
    # scheduler flushes on fill-or-deadline, nobody calls flush()
    rng = np.random.RandomState(3)
    frames = [jnp.asarray(rng.randn(int(n), args.frame_dim).astype(np.float32))
              for n in rng.randint(5, 40, size=args.batch)]
    tickets = [sched.submit("frames", f) for f in frames]
    for t in tickets:
        t.wait(30.0)
    reduced = [t.result() for t in tickets]

    # train-while-serve on the same traffic, then hot-swap the state
    stream = jnp.concatenate(frames, axis=0)
    blocks = stream[: (stream.shape[0] // 8) * 8].reshape(-1, 8, args.frame_dim)
    for blk in blocks:
        svc.serve_and_update("frames", blk)
    live_version = svc.promote("frames")    # two-phase FLEET-wide flip
    fleet_live = {h: s["live"].get("frames")
                  for h, s in leader.fleet_status().items()}
    assert set(fleet_live.values()) == {live_version}, fleet_live

    # LM path: prefill + greedy decode admitted through the SAME queue,
    # jitted into the SAME bounded compile cache as the DR buckets.
    # Decode is sequential, so each step takes a tight 2 ms batching
    # budget — the loop flushes almost immediately and the step still
    # counts as deadline-met (the budget bounds queue delay, not compute).
    mesh = make_smoke_mesh()
    tp = sched.lm_prefill(cfg, mesh, params, {"tokens": prompts}, cache_size,
                          max_delay_ms=2.0)
    tp.wait(60.0)
    logits, cache = tp.result()

    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    t0 = time.perf_counter()
    for _ in range(args.tokens - 1):
        td = sched.lm_decode(cfg, mesh, params, tok, cache, max_delay_ms=2.0)
        td.wait(60.0)
        logits, cache = td.result()
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0

    gen = jnp.stack(out, axis=1)
    print(f"arch={cfg.name} (smoke) window={cfg.sliding_window} "
          f"cache={cache['k'].shape}")
    for i in range(args.batch):
        print(f"req {i}: prompt={prompts[i, :8].tolist()}… -> {gen[i].tolist()} "
              f"| frames {frames[i].shape[0]}x{args.frame_dim} -> "
              f"{tuple(reduced[i].shape)}")
    print(f"decode: {args.tokens - 1} steps × batch {args.batch} in {dt*1e3:.0f} ms "
          f"({(args.tokens-1)*args.batch/dt:.0f} tok/s on CPU smoke config)")
    sched.shutdown()
    met = svc.metrics()
    print(f"engine: {met['served_rows']} rows in {met['batches_run']} "
          f"micro-batches, {met['compile_cache']['misses']} compiles in ONE "
          f"cache (DR buckets + LM prefill/decode), "
          f"({met['padded_rows']} padded rows), "
          f"train-while-serve promoted v{live_version} "
          f"after {met['updates_applied']['frames']} updates")
    print(f"fleet: live version per host {fleet_live} "
          f"(two-phase promote — no host serves a stale epoch)")
    print(f"deadlines: {met['deadline_met']} met / {met['deadline_missed']} "
          f"missed")
    for name, cells in met["slo"].items():
        for bucket, cell in cells.items():
            e2e = cell["e2e"]
            print(f"  slo[{name}/{bucket}]: n={e2e['count']} "
                  f"p50={e2e['p50_ms']:.2f}ms p99={e2e['p99_ms']:.2f}ms "
                  f"queue_p50={cell['queue_delay']['p50_ms']:.2f}ms")

    # ---- leader failover: kill h0, elect a successor, keep promoting ------
    regs = [leader] + followers
    electors = [Elector(r, seed=i, election_timeout_ms=(30.0, 60.0),
                        heartbeat_interval_ms=10.0)
                for i, r in enumerate(regs)]
    bus.partition("h0")                     # the leader host dies
    t0 = time.perf_counter()
    new_lead = None
    while new_lead is None:
        for e in electors[1:]:              # the survivors' election loops
            e.poll()
        new_lead = next((r for r in followers if r.role == "leader"), None)
        time.sleep(1e-3)
    # retrain once more and promote through the OTHER follower — the
    # replicated registry forwards the mutation to whoever leads now
    other = next(r for r in followers if r is not new_lead)
    state2 = dr.update(new_lead.get("frames").state, blocks[0])
    v2 = None
    while v2 is None:
        try:
            v2 = other.promote("frames", other.push("frames", state2))
        except ReplicationError:            # vote round still settling
            time.sleep(1e-3)
    failover_ms = (time.perf_counter() - t0) * 1e3
    bus.heal()                              # h0 returns from the dead...
    while leader.role == "leader":          # ...and gets fenced by a beat
        for e in electors:
            e.poll()
        time.sleep(1e-3)
    leader.sync()                           # anti-entropy catch-up
    final = {r.transport.host_id: r.get("frames").version for r in regs}
    assert set(final.values()) == {v2}, final
    st = new_lead.leader_status()
    print(f"failover: killed h0 -> {st['leader']} leads term {st['term']} "
          f"(kill -> promote v{v2} on the new leader in {failover_ms:.0f} ms, "
          f"issued on follower {other.transport.host_id} and forwarded); "
          f"healed h0 rejoined as {leader.role!r}, fleet live={final}")


if __name__ == "__main__":
    main()
