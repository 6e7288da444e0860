"""LM steps on a compute-dtype copy of the params, made once per tree.

`DRService.prefill_step` / `decode_step` hand the programs
`serve_step.compute_params(cfg, params)`: a float32-stored tree cast to the
compute dtype once, by the rule the steps apply inside themselves, so the
logits are bit-identical to the steps on the float32 tree, and the decode
program holds no cast of a weight.
"""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry as cfg_reg
from repro.launch.mesh import make_smoke_mesh
from repro.models import api
from repro.serve import DRService, DeadlineScheduler, serve_step
from repro.serve.clock import VirtualClock
from repro.serve.slo import SLOTracker

CACHE = 16


def _llama():
    cfg = cfg_reg.get_smoke("smollm_135m")
    assert cfg.param_dtype == "float32" and cfg.compute_dtype == "bfloat16"
    return cfg, api.init_params(jax.random.PRNGKey(0), cfg)


def _casts(tracker: SLOTracker) -> int:
    return tracker.stages().get("lm.params_cast", {}).get("count", 0)


def test_scheduler_steps_bit_identical_to_f32_steps_with_one_copy():
    cfg, params = _llama()
    mesh = make_smoke_mesh()
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                cfg.vocab_size)
    prefill = jax.jit(lambda p, b: api.prefill(p, b, cfg, CACHE))
    decode = jax.jit(lambda p, t, c: api.decode_step(p, t, c, cfg))

    want, cache_w = prefill(params, {"tokens": prompt})
    svc = DRService(clock=VirtualClock())
    sched = DeadlineScheduler(svc, default_max_delay_ms=0.0, start=False)
    t = sched.lm_prefill(cfg, mesh, params, {"tokens": prompt}, CACHE)
    sched.poll()
    got, cache_g = t.result()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for _ in range(4):
        tok = jnp.argmax(want, -1).astype(jnp.int32)
        want, cache_w = decode(params, tok, cache_w)
        t = sched.lm_decode(cfg, mesh, params, tok, cache_g)
        sched.poll()
        got, cache_g = t.result()
        assert np.array_equal(np.asarray(got), np.asarray(want))
    assert _casts(svc.slo) == 1                   # one copy for all 5 steps
    assert len(svc.lm_params) == 1
    # both programs take the copy, and were built once for it
    copy = svc.lm_params.get(cfg, params)
    assert copy["layers"]["wq"].dtype == jnp.bfloat16
    assert svc.prefill_step(cfg, mesh, params, {"tokens": prompt},
                            CACHE)[1] is copy
    assert svc.decode_step(cfg, mesh, params, tok, cache_g)[1] is copy
    assert svc.cache.misses == 2
    sched.shutdown()


def test_copy_casts_matrices_and_keeps_router_and_vectors():
    cfg, params = _llama()
    copy = serve_step.compute_params(cfg, params)
    assert copy["embed"].dtype == jnp.bfloat16
    assert copy["final_norm"].dtype == jnp.float32
    layers = copy["layers"]
    for k in ("wq", "wk", "wv", "wo", "w_in", "w_gate", "w_out"):
        assert layers[k].dtype == jnp.bfloat16, k
        np.testing.assert_array_equal(
            np.asarray(layers[k]),
            np.asarray(params["layers"][k].astype(jnp.bfloat16)))
    # stacked norms are (L, d) but vectors per layer: kept as stored
    assert layers["ln1"].dtype == layers["ln2"].dtype == jnp.float32
    assert layers["ln1"] is params["layers"]["ln1"]

    moe = cfg_reg.get_smoke("moonlight_16b_a3b")
    assert moe.param_dtype == "float32"
    p = api.init_params(jax.random.PRNGKey(0), moe)
    c = serve_step.compute_params(moe, p)
    for stack in ("dense_layers", "layers"):
        for k, a in c[stack].items():
            if k == "router" or p[stack][k].ndim == 2:    # router; vectors
                assert a is p[stack][k], (stack, k)
                assert a.dtype == jnp.float32, (stack, k)
            else:
                assert a.dtype == jnp.bfloat16, (stack, k)
    assert c["layers"]["select_bias"].dtype == jnp.float32
    assert c["lm_head"].dtype == jnp.bfloat16


def test_tree_stored_in_compute_dtype_passes_through_uncached():
    moe = dataclasses.replace(cfg_reg.get_smoke("moonlight_16b_a3b"),
                              param_dtype="bfloat16")
    params = api.init_params(jax.random.PRNGKey(0), moe)
    assert params["layers"]["router"].dtype == jnp.float32
    svc = DRService()
    fn, got, rows = svc.prefill_step(
        moe, make_smoke_mesh(), params,
        {"tokens": jnp.zeros((1, 8), jnp.int32)}, CACHE)
    assert got is params and rows == 1
    _, got, _ = svc.decode_step(moe, make_smoke_mesh(), params,
                                jnp.zeros((1,), jnp.int32),
                                api.init_cache(moe, 1, CACHE))
    assert got is params
    assert all(a is b for a, b in zip(jax.tree.leaves(got),
                                      jax.tree.leaves(params)))
    assert _casts(svc.slo) == 0 and len(svc.lm_params) == 0


def test_new_leaves_get_a_copy_and_the_oldest_ages_out():
    cfg, params = _llama()
    tracker = SLOTracker()
    copies = serve_step.ComputeParamsCache(tracker)
    assert copies.MAXSIZE == 2
    a = copies.get(cfg, params)
    assert copies.get(cfg, params) is a and _casts(tracker) == 1
    # the same values in new leaves: a new version, its own copy
    params2 = jax.tree.map(lambda x: x + 0, params)
    b = copies.get(cfg, params2)
    assert b is not a and _casts(tracker) == 2
    assert copies.get(cfg, params) is a            # both held, no copy
    params3 = jax.tree.map(lambda x: x * 1, params)
    copies.get(cfg, params3)                       # evicts params2's copy
    assert _casts(tracker) == 3 and len(copies) == 2
    assert copies.get(cfg, params) is a            # recently used: kept
    assert copies.get(cfg, params2) is not b       # aged out: copied anew
    assert _casts(tracker) == 4 and len(copies) == 2


def _weight_converts(hlo: str, shapes) -> list:
    """`convert` instructions whose operand is a float32 of a weight's shape."""
    defs = {}                                      # name -> (dtype, dims)
    converts = []                                  # operand names
    for m in re.finditer(
            r"^\s*(?:ROOT )?(\S+) = (\w+)\[([0-9,]*)\]\S* (\w[\w-]*)\(([^)]*)\)",
            hlo, re.M):
        name, dtype, dims, op, args = m.groups()
        defs[name] = (dtype, tuple(int(d) for d in dims.split(",") if d))
        if op == "convert":
            converts.append(args.strip())
    return [defs[a] for a in converts
            if defs.get(a, (None,))[0] == "f32" and defs[a][1] in shapes]


def test_decode_program_on_the_copy_casts_no_weight():
    cfg, params = _llama()
    mesh = make_smoke_mesh()
    shapes = set()
    for a in jax.tree.leaves(params["layers"]):
        if a.ndim >= 3:
            shapes |= {a.shape, a.shape[1:]}       # stacked and per layer
    shapes |= {params["embed"].shape, params["embed"].shape[::-1]}
    cache = api.init_cache(cfg, 1, CACHE)
    tok = jnp.zeros((1,), jnp.int32)

    def hlo(p):
        fn = serve_step.make_decode(cfg, mesh, p, cache)
        return fn.lower(p, tok, cache).as_text(dialect="hlo")

    # the guard sees the per-step casts of the float32 tree ...
    assert _weight_converts(hlo(params), shapes)
    # ... and none in the program the service runs
    assert _weight_converts(hlo(serve_step.compute_params(cfg, params)),
                            shapes) == []
