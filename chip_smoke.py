"""Bring-up check: drive the DR train-and-serve path and a full-width LM on a TPU.

Run from the root of a checkout, on a machine whose JAX sees a TPU:

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # four chips: only the sharded paths

One chip runs four phases, each through the entry points a user calls and
each checked against a plain reference on the same chip:

  dr_paper  the paper's RP+EASI pair (m=32 -> p=16 -> n=8) registered in a
            `DRService` under the Pallas backend: ragged requests through a
            `DeadlineScheduler`, train-while-serve blocks, a promote, and
            the requests again.  Reference: the same model on the XLA
            backend in f32 under `jax.default_matmul_precision("highest")`.
  dr_wide   the same path at m=1024 -> p=256 -> n=64 in 1024-row buckets,
            where every axis of the fused kernel's grid has several steps
            and the tile autotuner races several candidates.
  kernels   the program the service cached for one bucket was compiled by
            Mosaic (`tpu_custom_call` in its compiled text), not run in the
            Pallas interpreter.
  lm        SmolLM-135M at its published widths (30 layers, d=576, vocab
            49152; random weights from the seed): prefill of 4x128 tokens
            and 8 greedy decode steps through `DRService.lm_prefill` /
            `lm_decode` on a one-chip mesh.  Reference: the model's plain
            forward pass in f32 compute under "highest" precision over the
            prompt and the decoded tokens; a second run must decode the
            same tokens.

`--chips 4` runs only what exists across chips, each against the same work
on one device: a `DRService` on a 4x1 `data` mesh, and SmolLM prefill and
decode with the batch of 4 sharded over `data`; outputs and caches must
span all four devices.

Every phase raises on a mismatch; nothing catches it.  The last line of
standard output is one JSON object, `{"ok": true, "device": {...}}`.  With
no TPU the script exits 2 before any phase and prints no such line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

_SRC = str(pathlib.Path(__file__).resolve().parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import registry, waveform_paper  # noqa: E402
from repro.core.execution import Execution  # noqa: E402
from repro.dr import DRModel  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import api, transformer  # noqa: E402
from repro.models.config import ArchConfig  # noqa: E402
from repro.serve import BucketPolicy, DRService, QueueFull  # noqa: E402
from repro.serve.scheduler import DeadlineScheduler  # noqa: E402

PALLAS = Execution(backend="pallas")
XLA = Execution(backend="xla")

# Tolerances.  Every comparison is max|got - want| / max|want| over the
# whole output, so one number bounds every element against the output's
# own scale.  The measured values quoted are from one TPU v5e chip.
#
# DR outputs: the served path runs at the chip's default matmul precision,
# where both XLA and the Mosaic kernels round f32 operands to bf16 (8
# significant bits, 2^-9 relative per operand; under "highest" the same
# kernels match the reference to 1e-7).  An output's error is then up to
# 2^-8 times the sum of its terms' magnitudes, which exceeds the output
# itself where terms cancel: measured 6.5e-3 at paper widths and 3.7e-3
# at m=1024.  2e-2 leaves 3x; a wrong tile or a dropped grid step gives
# errors of order one, and the pre-promote outputs sit 0.24 (paper widths)
# and 0.43 (m=1024) from the post-promote reference.  This limit cannot
# tell f32 operands from bf16 ones: bf16 requests scored the same error to
# the digit, because the default precision already rounds them.
TOL_DR_OUT = 2e-2
# DR train-while-serve, compared as differences so that a state that never
# moved scores 1: the promoted B minus the B it was folded from, against
# the reference's same difference, and the outputs served after the
# promote minus those served before, against the reference's.  The update
# cubes y, tripling its bf16 error, and H - H^T cancels most of H, which
# magnifies it again: measured 1.1e-2 at paper widths and 8.5e-3 at
# m=1024 for B.  The served difference carries B's bf16 rounding (2^-9 of
# B) against a change of 0.1-0.3 of B: measured 3.0e-2 and 1.4e-2.  1e-1
# leaves 3x; a stale state, or an update skipped or applied twice, gives 1.
TOL_DR_DELTA = 1e-1
# Update blocks are drawn at UPDATE_SCALE times the requests' scale: a
# stream whose level drifted, which the rotation term (fourth order in y)
# follows, moving the reference outputs by 0.22 (paper widths) and 0.43
# (m=1024) of their scale.  At unit scale B moves 4e-4 and 1e-3 of itself,
# under the bf16 noise of the served outputs, so no check could see
# whether the promote reached the served path.
UPDATE_SCALE = 4.0
# LM logits: SmolLM's configuration computes in bf16 (compute_dtype), the
# reference in f32.  Thirty residual layers of bf16 rounding moved the
# logits by 1.5e-2 of their scale (prefill and decode alike).  5e-2 leaves
# 3x and still fails a wrong cache slot, position or head grouping: logits
# one position off scored 1.5, and the last token's logits with its
# context dropped 0.65.
TOL_LM_LOGITS = 5e-2
# The four-chip phases compare sharded against one-device results with
# the same two tolerances: each side already sits within them of the f32
# reference, and the sharded side runs other tile and batch shapes (one
# LM row per chip moved bf16 decode logits by 2.0e-2 against the same row
# in a batch of four).  A shard holding the wrong rows, or a cache split
# the wrong way, still gives errors of order one.


# ---------------------------------------------------------------------------
# cases
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DRCase:
    """One DR model through register -> serve -> train-while-serve ->
    promote -> serve."""
    name: str
    model: DRModel
    buckets: BucketPolicy
    n_requests: int          # ragged requests through the scheduler
    max_rows: int            # request rows drawn uniformly from 1..max_rows
    update_rows: int         # rows per serve_and_update block
    n_updates: int           # blocks folded before the promote


@dataclasses.dataclass(frozen=True)
class LMCase:
    """One LM through prefill + greedy decode."""
    cfg: ArchConfig
    batch: int
    prompt_len: int
    cache_size: int
    decode_steps: int


DR_PAPER = DRCase(
    name="dr_paper", model=waveform_paper.TABLE2_PAIR["rp16_easi_8"],
    buckets=BucketPolicy(min_bucket=8, max_bucket=1024),
    n_requests=48, max_rows=200, update_rows=256, n_updates=4)

DR_WIDE = DRCase(
    name="dr_wide",
    model=waveform_paper.rp_easi_model(1024, 256, 64, mu=5e-4),
    buckets=BucketPolicy(min_bucket=1024, max_bucket=1024),
    n_requests=32, max_rows=200, update_rows=1024, n_updates=4)

LM_SMOLLM = LMCase(cfg=registry.get("smollm_135m"), batch=4, prompt_len=128,
                   cache_size=192, decode_steps=8)


# ---------------------------------------------------------------------------
# comparison helpers
# ---------------------------------------------------------------------------

def rel_err(got: Any, want: Any) -> float:
    """max|got - want| / max|want| (see the tolerances above)."""
    g = np.asarray(got, np.float64)
    w = np.asarray(want, np.float64)
    if g.shape != w.shape:
        raise AssertionError(f"shape {g.shape} != reference {w.shape}")
    if not np.all(np.isfinite(g)):
        raise AssertionError("non-finite values in the output")
    return float(np.max(np.abs(g - w)) / max(float(np.max(np.abs(w))), 1e-30))


def check(label: str, got: Sequence[Any], want: Sequence[Any],
          tol: float) -> float:
    """Largest `rel_err` over paired outputs; raises past `tol`."""
    if len(got) != len(want):
        raise AssertionError(f"{label}: {len(got)} outputs, "
                             f"{len(want)} references")
    err = max(rel_err(g, w) for g, w in zip(got, want))
    if not err <= tol:
        raise AssertionError(f"{label}: relative error {err!r} > {tol!r}")
    return err


def _submit(sched: DeadlineScheduler, name: str, x: jax.Array):
    """Admit one request, backing off while the queue is full (`QueueFull`
    is the service's transient backpressure signal)."""
    give_up = time.monotonic() + 600.0
    while True:
        try:
            return sched.submit(name, x)
        except QueueFull:
            if time.monotonic() > give_up:
                raise
            time.sleep(1e-3)


def _serve_via_scheduler(svc: DRService, name: str,
                         xs: Sequence[jax.Array]) -> List[jax.Array]:
    """Submit every request through a `DeadlineScheduler` and return each
    ticket's result (a failed ticket raises here)."""
    with DeadlineScheduler(svc, default_max_delay_ms=5.0) as sched:
        tickets = [_submit(sched, name, x) for x in xs]
        for t in tickets:
            if not t.wait(600.0):
                raise TimeoutError(f"{name}: a ticket was not served")
    return [jax.block_until_ready(t.result()) for t in tickets]


def _requests(case: DRCase, seed: int):
    rng = np.random.default_rng(seed)
    m = case.model.in_dim
    rows = rng.integers(1, case.max_rows + 1, size=case.n_requests)
    xs = [jnp.asarray(rng.standard_normal((int(r), m), dtype=np.float32))
          for r in rows]
    blocks = [jnp.asarray(UPDATE_SCALE * rng.standard_normal(
        (case.update_rows, m), dtype=np.float32))
              for _ in range(case.n_updates)]
    return xs, blocks


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def dr_phase(case: DRCase, seed: int = 0) -> Tuple[Dict[str, Any], DRService]:
    """Serve, train while serving, promote and serve again through a
    Pallas-backed `DRService`; every output and the promoted state are
    checked against the XLA-backend reference at "highest" precision.
    Returns (report, service)."""
    model = case.model.with_execution(PALLAS)
    ref_model = case.model.with_execution(XLA)
    state0 = model.init(jax.random.PRNGKey(seed))
    xs, blocks = _requests(case, seed)
    ref_transform = jax.jit(ref_model.transform)
    ref_update = jax.jit(ref_model.update)

    svc = DRService(buckets=case.buckets)
    t0 = time.perf_counter()
    svc.register("dr", model, state0)
    svc.warmup("dr")
    t_register = time.perf_counter() - t0
    served = _serve_via_scheduler(svc, "dr", xs)
    streamed = [jax.block_until_ready(svc.serve_and_update("dr", b))
                for b in blocks]
    version = svc.promote("dr")
    if version != 1:
        raise AssertionError(f"promote returned version {version}, not 1")
    promoted = svc.registry.get("dr").state
    served_after = _serve_via_scheduler(svc, "dr", xs)

    with jax.default_matmul_precision("highest"):
        want = [ref_transform(state0, x) for x in xs]
        want_streamed = [ref_transform(state0, b) for b in blocks]
        ref_state = state0
        for b in blocks:
            ref_state = ref_update(ref_state, b)
        want_after = [ref_transform(ref_state, x) for x in xs]

    report = {"register_and_warmup_s": t_register}
    report["err_serve"] = check(f"{case.name} serve", served, want,
                                TOL_DR_OUT)
    report["err_serve_and_update"] = check(
        f"{case.name} serve_and_update", streamed, want_streamed,
        TOL_DR_OUT)
    report["err_state_delta"] = _check_state(case, state0, promoted,
                                             ref_state)
    report["err_serve_after_promote"] = check(
        f"{case.name} serve after promote", served_after, want_after,
        TOL_DR_OUT)
    report["err_serve_promote_delta"] = check(
        f"{case.name} served change across the promote",
        _diffs(served_after, served), _diffs(want_after, want), TOL_DR_DELTA)
    met = svc.metrics()
    report.update(compiles=met["compile_cache"]["misses"],
                  autotunes=met["autotunes"],
                  served_rows=met["served_rows"])
    return report, svc


def _diffs(after: Sequence[Any], before: Sequence[Any]) -> List[np.ndarray]:
    return [np.asarray(a, np.float64) - np.asarray(b, np.float64)
            for a, b in zip(after, before)]


def _check_state(case: DRCase, state0: Any, got: Any, want: Any) -> float:
    if int(got.steps) != int(want.steps):
        raise AssertionError(f"{case.name}: promoted state counts "
                             f"{int(got.steps)} updates, reference "
                             f"{int(want.steps)}")
    err = 0.0
    for s0, g, w in zip(state0.stages, got.stages, want.stages):
        if not jnp.issubdtype(w.dtype, jnp.floating):
            if not np.array_equal(np.asarray(g), np.asarray(w)):
                raise AssertionError(f"{case.name}: a static stage changed")
            continue
        d_want = np.asarray(w, np.float64) - np.asarray(s0, np.float64)
        if not np.any(d_want):
            continue
        d_got = np.asarray(g, np.float64) - np.asarray(s0, np.float64)
        err = max(err, check(f"{case.name} promoted state delta",
                             [d_got], [d_want], TOL_DR_DELTA))
    return err


def kernel_phase(svc: DRService, name: str, bucket: int) -> Dict[str, Any]:
    """The program `svc` cached for `bucket` was compiled by Mosaic."""
    snap = svc.registry.get(name)
    exe = snap.model.execution
    if exe.resolved_interpret():
        raise AssertionError("Pallas resolved to interpret mode")
    misses = svc.cache.misses
    prog = svc._transform_fn(snap, bucket, jnp.dtype(jnp.float32))
    if svc.cache.misses != misses:
        raise AssertionError(f"bucket {bucket} was not in the compile cache")
    fn = getattr(prog, "fn", prog)      # autotuned programs wrap the jit
    x = jax.ShapeDtypeStruct((bucket, snap.model.in_dim), jnp.float32)
    text = fn.lower(snap.state, x).compile().as_text()
    n_calls = text.count("tpu_custom_call")
    if n_calls == 0:
        raise AssertionError(f"no tpu_custom_call in the bucket-{bucket} "
                             f"program")
    tiles = getattr(prog, "tiles", None)
    return {"bucket": bucket, "tpu_custom_calls": n_calls,
            "tiles": None if tiles is None else dataclasses.astuple(tiles)}


def _lm_run(case: LMCase, svc: DRService, mesh: Any, params: Any,
            prompt: jax.Array, forced: Optional[np.ndarray] = None):
    """Prefill then `decode_steps` greedy steps through the service's queue.
    Returns (prefill logits, per-step decode logits, tokens fed to decode,
    final cache).  `forced` feeds those tokens instead of the argmax."""
    t = svc.lm_prefill(case.cfg, mesh, params, {"tokens": prompt},
                       case.cache_size)
    svc.flush()
    logits, cache = t.result()
    prefill_logits = logits
    step_logits, fed = [], []
    for i in range(case.decode_steps):
        tok = (jnp.argmax(logits, axis=-1).astype(jnp.int32) if forced is None
               else jnp.asarray(forced[:, i]))
        fed.append(np.asarray(tok))
        t = svc.lm_decode(case.cfg, mesh, params, tok, cache)
        svc.flush()
        logits, cache = t.result()
        step_logits.append(logits)
    jax.block_until_ready((logits, cache))
    return prefill_logits, step_logits, np.stack(fed, axis=1), cache


def _lm_inputs(case: LMCase, seed: int):
    params = jax.jit(api.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), case.cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (case.batch, case.prompt_len), 0,
                                case.cfg.vocab_size, jnp.int32)
    return params, prompt


def lm_phase(case: LMCase, seed: int = 0) -> Dict[str, Any]:
    """Prefill + greedy decode through `DRService` on a one-chip mesh,
    against the plain f32 forward pass over the same tokens."""
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    params, prompt = _lm_inputs(case, seed)
    svc = DRService()
    t0 = time.perf_counter()
    pre, steps, fed, _ = _lm_run(case, svc, mesh, params, prompt)
    t_first = time.perf_counter() - t0
    _, _, fed2, _ = _lm_run(case, svc, mesh, params, prompt)
    if not np.array_equal(fed, fed2):
        raise AssertionError("lm: two runs decoded different tokens")

    # teacher-forced reference: the plain forward over prompt + fed tokens
    # gives the prefill logits at position S-1 and step i's at S+i
    ref_cfg = dataclasses.replace(case.cfg, compute_dtype="float32")
    seq = jnp.concatenate([prompt, jnp.asarray(fed)], axis=1)
    with jax.default_matmul_precision("highest"):
        ref_logits, _ = jax.jit(
            lambda p, s: transformer.forward(p, {"tokens": s}, ref_cfg,
                                             remat=False))(params, seq)
    ref_logits = np.asarray(ref_logits)[..., :case.cfg.vocab_size]
    s = case.prompt_len
    got = [pre] + steps
    want = [ref_logits[:, s - 1 + i] for i in range(case.decode_steps + 1)]
    got = [np.asarray(g)[..., :case.cfg.vocab_size] for g in got]
    report = {"first_run_s": t_first,
              "err_prefill": check("lm prefill logits", got[:1], want[:1],
                                   TOL_LM_LOGITS),
              "err_decode": check("lm decode logits", got[1:], want[1:],
                                  TOL_LM_LOGITS),
              "tokens": fed.tolist(),
              "compiles": svc.cache.misses}
    return report


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------

def _spans(arr: jax.Array, devices: Sequence[Any]) -> None:
    """`arr` is split over every device of `devices`, not replicated."""
    held = {s.device for s in arr.addressable_shards}
    if held != set(devices):
        raise AssertionError(f"array lives on {sorted(d.id for d in held)}, "
                             f"not on all of {[d.id for d in devices]}")
    if arr.sharding.is_fully_replicated:
        raise AssertionError("array is replicated, not sharded")


def dr_sharded_phase(case: DRCase, devices: Sequence[Any],
                     seed: int = 0) -> Dict[str, Any]:
    """The same ragged requests through a one-device `DRService` and one
    on a (len(devices), 1) `data` mesh."""
    model = case.model.with_execution(PALLAS)
    state0 = model.init(jax.random.PRNGKey(seed))
    xs, _ = _requests(case, seed)
    mesh = make_mesh((len(devices), 1), ("data", "model"), devices=devices)
    one = DRService(buckets=case.buckets)
    many = DRService(mesh=mesh, buckets=case.buckets)
    for svc in (one, many):
        svc.register("dr", model, state0)
        svc.warmup("dr")
    want = _serve_via_scheduler(one, "dr", xs)
    got = _serve_via_scheduler(many, "dr", xs)
    err = check(f"{case.name} sharded serve", got, want, TOL_DR_OUT)
    full = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (case.buckets.max_bucket, model.in_dim), dtype=np.float32))
    _spans(many.transform("dr", full), devices)
    return {"err_sharded_serve": err,
            "compiles": many.cache.misses}


def lm_sharded_phase(case: LMCase, devices: Sequence[Any],
                     seed: int = 0) -> Dict[str, Any]:
    """Prefill + decode with the batch sharded over a (len(devices), 1)
    `data` mesh, against the same tokens on one device."""
    params, prompt = _lm_inputs(case, seed)
    mesh1 = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    meshn = make_mesh((len(devices), 1), ("data", "model"), devices=devices)
    svc = DRService()
    pre1, steps1, fed, _ = _lm_run(case, svc, mesh1, params, prompt)
    pren, stepsn, _, cache = _lm_run(case, svc, meshn, params, prompt,
                                     forced=fed)
    _spans(pren, devices)
    _spans(stepsn[-1], devices)
    _spans(cache["k"], devices)
    _spans(cache["v"], devices)
    return {"err_sharded_prefill": check("lm sharded prefill", [pren],
                                         [pre1], TOL_LM_LOGITS),
            "err_sharded_decode": check("lm sharded decode", stepsn, steps1,
                                        TOL_LM_LOGITS),
            "compiles": svc.cache.misses}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _phase(name: str, fn: Callable[[], Dict[str, Any]]) -> Dict[str, Any]:
    t0 = time.perf_counter()
    report = fn()
    report = {"phase": name, "wall_s": time.perf_counter() - t0, **report,
              "peak_bytes_in_use": _peak_bytes()}
    print(json.dumps(report), flush=True)
    return report


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the sharded "
                         "phases, on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()

    if args.chips == 1:
        services: Dict[str, DRService] = {}

        def run_dr(case: DRCase) -> Dict[str, Any]:
            report, services[case.name] = dr_phase(case)
            return report

        for case in (DR_PAPER, DR_WIDE):
            _phase(case.name, lambda: run_dr(case))
        _phase("kernels", lambda: {
            case.name: kernel_phase(services[case.name], "dr",
                                    case.buckets.max_bucket)
            for case in (DR_PAPER, DR_WIDE)})
        _phase("lm", lambda: lm_phase(LM_SMOLLM))
    else:
        four = devices[:4]
        _phase("dr_sharded", lambda: dr_sharded_phase(DR_PAPER, four))
        _phase("lm_sharded", lambda: lm_sharded_phase(LM_SMOLLM, four))

    entries = (len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
               else 0)
    print(json.dumps({"compile_cache_dir": cache_dir,
                      "compile_cache_entries": entries}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
