"""Moonlight-16B-A3B's layer pattern (latent attention, a dense layer, then
MoE layers with sigmoid routing, shared experts and a held share of the
routed experts) against the plain reference `bench/reference/mla_moe.py`,
at the SMOKE width on seeded random weights; and the Llama-layout path
held to the logits the code before it gave.

Tolerances: with float32 compute the program and the reference differ
only in the order of float32 sums (flash attention's online softmax, the
grouped matmul's tiles, the absorbed decode's re-association), a few
1e-6 relative on logits of order 1, so 1e-4 holds with room; a rounding
step to bfloat16 anywhere (the configuration's compute type) moves logits
by 1e-2, which 1e-4 would catch.
"""

from __future__ import annotations

import dataclasses
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import mla_moe as ref
from repro.configs import registry
from repro.models import api, blocks, transformer

F32 = dataclasses.replace(registry.get_smoke("moonlight_16b_a3b"),
                          compute_dtype="float32")
TOL = dict(rtol=1e-4, atol=1e-4)
DATA = pathlib.Path(__file__).parent / "data"


def ref_config(cfg):
    """The reference's configuration dict of an `ArchConfig`."""
    m, mo = cfg.mla, cfg.moe
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "kv_lora_rank": m.kv_lora_rank,
            "qk_nope_head_dim": m.qk_nope_head_dim,
            "qk_rope_head_dim": m.qk_rope_head_dim,
            "v_head_dim": m.v_head_dim, "intermediate_size": cfg.d_ff,
            "moe_intermediate_size": mo.d_ff_expert,
            "n_routed_experts": mo.e_held, "num_experts_per_tok": mo.top_k,
            "deployment": {"experts_published": mo.n_experts,
                           "held_offset": mo.held_offset},
            "n_shared_experts": mo.n_shared, "vocab_size": cfg.vocab_size,
            "num_hidden_layers": cfg.n_layers,
            "first_k_dense_replace": cfg.first_dense,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "routed_scaling_factor": mo.routed_scale,
            "run": {"param_dtype": cfg.param_dtype}}


def _params(cfg, seed):
    """Program-initialised weights with a random selection bias."""
    p = api.init_params(jax.random.PRNGKey(seed), cfg)
    bias = p["layers"]["select_bias"]
    p["layers"]["select_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), bias.shape, jnp.float32)
    return p


@pytest.fixture(scope="module")
def model():
    params = _params(F32, 21)
    toks = jax.random.randint(jax.random.PRNGKey(22), (2, 24), 0,
                              F32.vocab_size)
    want = np.stack([np.asarray(ref.forward(params, toks[i], ref_config(F32)))
                     for i in range(2)])                      # (2, 24, V)
    return params, toks, want


def test_prefill_logits_equal_the_reference(model):
    params, toks, want = model
    logits, cache = jax.jit(lambda p, b: api.prefill(p, b, F32, 32))(
        params, {"tokens": toks[:, :20]})
    np.testing.assert_allclose(np.asarray(logits), want[:, 19], **TOL)
    assert cache["c_kv"].shape == (F32.n_layers, 2, 32, F32.mla.kv_lora_rank)
    assert cache["k_pe"].shape == (F32.n_layers, 2, 32,
                                   F32.mla.qk_rope_head_dim)
    assert "k" not in cache and "v" not in cache


def test_decode_through_the_latent_cache_equals_the_full_forward(model):
    params, toks, want = model
    logits, cache = api.prefill(params, {"tokens": toks[:, :12]}, F32, 32)
    step = jax.jit(lambda p, t, c: api.decode_step(p, t, c, F32))
    for i in range(12, 24):
        logits, cache = step(params, toks[:, i], cache)
        np.testing.assert_allclose(np.asarray(logits), want[:, i], **TOL)
    assert int(cache["len"]) == int(cache["pos"]) == 24


def test_absorbed_decode_equals_expanded_attention():
    """The last query of the expanded form (per-head keys and values built
    from the latents) against the absorbed form over the same cache."""
    b, s, h, c, dn, dr, dv = 2, 40, 4, 32, 16, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    q_nope = jax.random.normal(ks[0], (b, s, h, dn))
    q_pe = jax.random.normal(ks[1], (b, s, h, dr))
    c_kv = jax.random.normal(ks[2], (b, s, c))
    k_pe = jax.random.normal(ks[3], (b, s, dr))
    w_uk = jax.random.normal(ks[4], (c, h, dn)) / math.sqrt(c)
    w_uv = jax.random.normal(ks[5], (c, h, dv)) / math.sqrt(c)
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([jnp.einsum("bsc,chd->bshd", c_kv, w_uk),
                         jnp.broadcast_to(k_pe[:, :, None], (b, s, h, dr))], -1)
    v = jnp.einsum("bsc,chd->bshd", c_kv, w_uv)
    expanded = blocks.flash_attention(q, k, v, q_chunk=8, kv_chunk=16)[:, -1]
    # the absorbed form reads a cache longer than the valid prefix
    pad = ((0, 0), (0, 8), (0, 0))
    absorbed = blocks.mla_decode_attention(
        q_nope[:, -1], q_pe[:, -1], jnp.pad(c_kv, pad), jnp.pad(k_pe, pad),
        w_uk, w_uv, jnp.int32(s), scale=1 / math.sqrt(dn + dr))
    assert absorbed.shape == (b, h, dv)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               rtol=1e-5, atol=1e-5)


def _layer_params(seed, spec, d=64):
    """One MoE layer's router, bias and all experts of `spec` (uncut)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    e, f = spec.n_experts, spec.d_ff_expert
    fs = spec.n_shared * f
    return {"router": jax.random.normal(ks[0], (d, e)) / math.sqrt(d),
            "select_bias": 0.05 * jax.random.normal(ks[1], (e,)),
            "w_in": jax.random.normal(ks[2], (e, d, f)) / math.sqrt(d),
            "w_gate": jax.random.normal(ks[3], (e, d, f)) / math.sqrt(d),
            "w_out": jax.random.normal(ks[4], (e, f, d)) / math.sqrt(f),
            "shared_in": jax.random.normal(ks[5], (d, fs)) / math.sqrt(d),
            "shared_gate": jax.random.normal(ks[6], (d, fs)) / math.sqrt(d),
            "shared_out": jax.random.normal(ks[7], (fs, d)) / math.sqrt(fs)}


def _share(p, spec, offset, n):
    q = dict(p)
    for k in ("w_in", "w_gate", "w_out"):
        q[k] = p[k][offset:offset + n]
    return q, dataclasses.replace(spec, n_held=n, held_offset=offset)


def _ref_layer(p, x, spec):
    """The reference's expert layer over every expert, shared experts once."""
    sz = {"k": spec.top_k, "e0": 0, "eh": spec.n_experts}
    top_e, w = ref.route(x, p["router"], p["select_bias"], sz,
                         spec.routed_scale, None)
    y = ref.experts_held(x, p, top_e, w, sz, None)
    return y + ref._mlp(x, p["shared_in"], p["shared_gate"], p["shared_out"],
                        None)


def test_shares_add_up_to_the_uncut_layer():
    """Four chips of two experts each: their held parts, with the shared
    experts counted once, equal the whole layer — the program's and the
    reference's."""
    spec = F32.moe                     # 8 experts, 3 a token, 2 shared
    p = _layer_params(31, spec)
    x = jax.random.normal(jax.random.PRNGKey(32), (1, 40, 64))
    parts, pairs = [], []
    for off in range(0, spec.n_experts, 2):
        q, sp = _share(p, spec, off, 2)
        y, n = blocks.moe_routed_held(q, x[0], sp, "silu")
        parts.append(y)
        pairs.append(n)
    shared = blocks.mlp({"w_in": p["shared_in"], "w_gate": p["shared_gate"],
                         "w_out": p["shared_out"]}, x[0], "silu")
    total = sum(parts) + shared
    whole, aux = blocks.moe_dropless(
        p, x, dataclasses.replace(spec, n_held=0), "silu")
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole[0]), **TOL)
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(_ref_layer(p, x[0], spec)), **TOL)
    # every (token, expert) pair is computed on exactly one chip
    assert int(sum(int(n.sum()) for n in pairs)) == 40 * spec.top_k
    assert int(aux["pairs"].sum()) == 40 * spec.top_k
    # and each share differs from the whole: no chip stands in for another
    assert not np.allclose(np.asarray(parts[0] + shared), np.asarray(whole[0]))


def test_a_router_that_sends_every_token_to_one_expert_drops_none():
    spec = F32.moe
    p = _layer_params(41, spec)
    # every token's first choice is expert 3 (and 5, 6 fill its top 3)
    p["select_bias"] = jnp.zeros(spec.n_experts).at[jnp.array([3, 5, 6])].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(42), (1, 96, 64))
    q, sp = _share(p, spec, 2, 2)                 # experts 2 and 3 here
    y, n = blocks.moe_routed_held(q, x[0], sp, "silu")
    assert n.tolist() == [0, 96]                  # all 96 tokens, none dropped
    sz = {"k": spec.top_k, "e0": 2, "eh": 2}
    top_e, w = ref.route(x[0], p["router"], p["select_bias"], sz,
                         spec.routed_scale, None)
    want = ref.experts_held(x[0], q, top_e, w, sz, None)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), **TOL)
    # the capacity layer of the softmax-routed models drops past capacity
    cap = dataclasses.replace(spec, scoring="softmax")
    assert blocks.moe_capacity(96, cap) < 96


def test_the_selection_bias_moves_the_choice_and_not_the_weights():
    spec = F32.moe
    p = _layer_params(51, spec)
    x = jax.random.normal(jax.random.PRNGKey(52), (64, 64))
    s = jax.nn.sigmoid(x @ p["router"])
    e0, w0 = blocks.route_sigmoid(x, p["router"], jnp.zeros(8), spec)
    e1, w1 = blocks.route_sigmoid(x, p["router"], p["select_bias"] * 20, spec)
    assert not np.array_equal(np.sort(e0, -1), np.sort(e1, -1))
    for e, w in ((e0, w0), (e1, w1)):
        s_top = jnp.take_along_axis(s, e, axis=-1)
        want = spec.routed_scale * s_top / s_top.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(w), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
    # the choice is the top k of score plus bias
    _, want_e = jax.lax.top_k(s + p["select_bias"] * 20, spec.top_k)
    assert np.array_equal(np.sort(e1, -1), np.sort(want_e, -1))


def test_llama_layout_logits_are_those_of_the_code_before():
    """SmolLM-135M's SMOKE prefill and three decode steps give, bit for
    bit, the logits recorded on the CPU from the code before latent
    attention and leading dense layers came in."""
    cfg = registry.get_smoke("smollm_135m")
    params = api.init_params(jax.random.PRNGKey(11), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(12), (2, 20), 0,
                              cfg.vocab_size)
    want = np.load(DATA / "smollm_smoke_parent_logits.npz")
    logits, cache = jax.jit(lambda p, b: api.prefill(p, b, cfg, 32))(
        params, {"tokens": toks[:, :16]})
    assert np.array_equal(np.asarray(logits), want["prefill"])
    step = jax.jit(lambda p, t, c: api.decode_step(p, t, c, cfg))
    for i in range(16, 19):
        logits, cache = step(params, toks[:, i], cache)
        assert np.array_equal(np.asarray(logits), want[f"decode_{i}"]), i


def test_param_counts_count_the_held_share():
    """The tree and the formula agree, and a share of 8 of 64 experts
    counts 8 experts' weights and 6 * 8 / 64 of them active."""
    cfg = registry.get("moonlight_16b_a3b")
    total, active = api.exact_param_counts(cfg)
    assert total == cfg.param_count() == 3_364_615_296
    assert active == cfg.param_count(active_only=True)
    expert = 3 * 2048 * 1408
    assert total - active == 26 * expert * (8 - 6 * 8 / 64)
