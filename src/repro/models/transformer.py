"""Config-driven transformer LM: GQA + RoPE (+ SWA, MoE, encoder, VLM/audio),
and the DeepSeek-V3 layout (latent attention, dropless held-share experts
with shared experts, leading dense layers).

Scan-over-stacked-layers everywhere (one traced layer body → small HLO and
fast multi-hundred-layer compiles), remat-wrapped in training, flash-style
chunked attention (blocks.flash_attention) so no S×S tensor ever
materialises.  Decode uses an explicit KV cache pytree (serve.kv_cache).
Leading dense layers (`cfg.first_dense`) are a stack of their own,
`params["dense_layers"]`, run before the scan over `params["layers"]`.
A latent-attention model caches `c_kv` (L, B, S, kv_lora_rank) and `k_pe`
(L, B, S, qk_rope_head_dim) and no per-head keys or values.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import blocks
from repro.models.config import ArchConfig

PyTree = Any


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(key: jax.Array, cfg: ArchConfig) -> PyTree:
    cfg.validate()
    dtype = jnp.dtype(cfg.param_dtype)
    d, dh = cfg.d_model, cfg.dh
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    v = cfg.padded_vocab
    k_embed, k_layers, k_head, k_front = jax.random.split(key, 4)

    def layer_init(i, moe=cfg.moe is not None):
        ks = jax.random.split(jax.random.fold_in(k_layers, i), 8)
        p = {
            "ln1": jnp.ones((d,), dtype),
            "ln2": jnp.ones((d,), dtype),
        }
        if cfg.mla is not None:
            p.update(_mla_init(ks, cfg, dtype))
        else:
            p.update({
                "wq": blocks.dense_init(ks[0], d, hq * dh, dtype),
                "wk": blocks.dense_init(ks[1], d, hkv * dh, dtype),
                "wv": blocks.dense_init(ks[2], d, hkv * dh, dtype),
                "wo": blocks.dense_init(ks[3], hq * dh, d, dtype,
                                        scale=1.0 / math.sqrt(2 * cfg.n_layers * hq * dh)),
            })
        if moe and cfg.moe.dropless:
            p.update(_dropless_init(ks, cfg, dtype))
        elif moe:
            e, f = cfg.moe.n_experts, cfg.moe.d_ff_expert
            p["router"] = blocks.dense_init(ks[4], d, e, jnp.float32)
            p["w_in"] = jnp.stack([blocks.dense_init(jax.random.fold_in(ks[5], j), d, f, dtype) for j in range(e)])
            p["w_gate"] = jnp.stack([blocks.dense_init(jax.random.fold_in(ks[6], j), d, f, dtype) for j in range(e)])
            p["w_out"] = jnp.stack([blocks.dense_init(jax.random.fold_in(ks[7], j), f, d, dtype,
                                                      scale=1.0 / math.sqrt(2 * cfg.n_layers * f)) for j in range(e)])
        else:
            f = cfg.d_ff
            p["w_in"] = blocks.dense_init(ks[4], d, f, dtype)
            if cfg.gated_mlp:
                p["w_gate"] = blocks.dense_init(ks[5], d, f, dtype)
            p["w_out"] = blocks.dense_init(ks[6], f, d, dtype,
                                           scale=1.0 / math.sqrt(2 * cfg.n_layers * f))
        return p

    n0 = cfg.first_dense
    params = {
        "embed": blocks.dense_init(k_embed, v, d, dtype, scale=1.0),
        "layers": blocks.stacked(lambda i: layer_init(n0 + i), cfg.n_layers - n0),
        "final_norm": jnp.ones((d,), dtype),
    }
    if n0:
        params["dense_layers"] = blocks.stacked(
            lambda i: layer_init(i, moe=False), n0)
    if not cfg.tie_embeddings:
        params["lm_head"] = blocks.dense_init(k_head, d, v, dtype)
    if cfg.frontend is not None:
        # with a DR front-end the projection reads the REDUCED features
        f_in = cfg.dr_frontend.n if cfg.dr_frontend is not None else cfg.frontend_dim
        params["frontend_proj"] = blocks.dense_init(k_front, f_in, d, dtype)
    return params


def _mla_init(ks, cfg: ArchConfig, dtype) -> PyTree:
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    c = m.kv_lora_rank
    return {
        "wq": blocks.dense_init(ks[0], d, h * m.qk_head_dim, dtype),
        "w_kva": blocks.dense_init(ks[1], d, c + m.qk_rope_head_dim, dtype),
        "kv_norm": jnp.ones((c,), dtype),
        "w_uk": blocks.dense_init(ks[2], c, h * m.qk_nope_head_dim, dtype),
        "w_uv": blocks.dense_init(jax.random.fold_in(ks[2], 1), c,
                                  h * m.v_head_dim, dtype),
        "wo": blocks.dense_init(ks[3], h * m.v_head_dim, d, dtype,
                                scale=1.0 / math.sqrt(2 * cfg.n_layers * h * m.v_head_dim)),
    }


def _dropless_init(ks, cfg: ArchConfig, dtype) -> PyTree:
    """Router and selection bias (f32), the held experts, the shared
    experts as one gated MLP of width n_shared * d_ff_expert."""
    mo, d = cfg.moe, cfg.d_model
    f, eh = mo.d_ff_expert, mo.e_held
    out_scale = 1.0 / math.sqrt(2 * cfg.n_layers * f)

    def experts(key, d_in, d_out, scale=None):
        return jnp.stack([blocks.dense_init(
            jax.random.fold_in(key, mo.held_offset + j), d_in, d_out, dtype,
            scale=scale) for j in range(eh)])

    p = {
        "router": blocks.dense_init(ks[4], d, mo.n_experts, jnp.float32),
        "select_bias": jnp.zeros((mo.n_experts,), jnp.float32),
        "w_in": experts(ks[5], d, f),
        "w_gate": experts(ks[6], d, f),
        "w_out": experts(ks[7], f, d, out_scale),
    }
    if mo.n_shared:
        fs = mo.n_shared * f
        k_sh = jax.random.split(jax.random.fold_in(ks[4], 1), 3)
        p["shared_in"] = blocks.dense_init(k_sh[0], d, fs, dtype)
        p["shared_gate"] = blocks.dense_init(k_sh[1], d, fs, dtype)
        p["shared_out"] = blocks.dense_init(k_sh[2], fs, d, dtype,
                                            scale=out_scale)
    return p


def _stacks(params: PyTree):
    """The stacked layer groups in order: leading dense layers, then the rest."""
    return [params[k] for k in ("dense_layers", "layers") if k in params]


def _casts(kp, a, cdt, lead: int = 0) -> bool:
    """Whether `_to_compute` casts this leaf: a float32 matrix (past `lead`
    stacked-layer axes) other than the router, and a compute dtype that
    differs."""
    return (a.dtype == jnp.float32 and jnp.dtype(cdt) != jnp.float32
            and a.ndim - lead >= 2
            and not jax.tree_util.keystr(kp).endswith("['router']"))


def _to_compute(tree: PyTree, cdt, lead: int = 0) -> PyTree:
    """Float32 matrices to the compute dtype; the router stays float32."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, a: a.astype(cdt) if _casts(kp, a, cdt, lead) else a, tree)


def _lead(key: str) -> int:
    """Stacked-layer axes of a top-level params entry."""
    return int(key in ("dense_layers", "layers"))


def casts_any(params: PyTree, cdt) -> bool:
    """Whether `compute_params` would change the dtype of any leaf."""
    return any(_casts(kp, a, cdt, _lead(kp[0].key)) for kp, a in
               jax.tree_util.tree_flatten_with_path(params)[0])


def compute_params(params: PyTree, cdt) -> PyTree:
    """The params as prefill and decode compute with them: each layer's
    matrices cast as `_to_compute` casts them inside the steps, and the
    embedding, head and front-end projection as the steps cast them, so a
    step on the copy rounds the same values to the same operands."""
    return {k: _to_compute(v, cdt, _lead(k)) for k, v in params.items()}


# ---------------------------------------------------------------------------
# layer body (shared by train forward and prefill)
# ---------------------------------------------------------------------------

def _mla_project(lp, h, cfg, positions):
    """Latent attention's projections: (q_nope, q_pe) (B, S, H, ·), the
    normed latent c_kv (B, S, C) and the rotated shared key k_pe (B, S, Dr)."""
    m = cfg.mla
    b, s, _ = h.shape
    q = (h @ lp["wq"]).reshape(b, s, cfg.n_heads, m.qk_head_dim)
    q_nope = q[..., : m.qk_nope_head_dim]
    q_pe = blocks.apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    kva = h @ lp["w_kva"]
    c_kv = blocks.rms_norm(kva[..., : m.kv_lora_rank], lp["kv_norm"], cfg.norm_eps)
    k_pe = blocks.apply_rope(kva[..., None, m.kv_lora_rank:], positions,
                             cfg.rope_theta)[:, :, 0]
    return q_nope, q_pe, c_kv, k_pe


def _mla_attention(lp, h, cfg, positions):
    """Expanded latent attention over the whole sequence (prefill, train):
    per-head keys [c_kv·W_UK | k_pe] (192 wide) and values c_kv·W_UV (128
    wide).  Returns (attention output (B, S, d), (c_kv, k_pe))."""
    m = cfg.mla
    b, s, _ = h.shape
    hq = cfg.n_heads
    q_nope, q_pe, c_kv, k_pe = _mla_project(lp, h, cfg, positions)
    k_nope = (c_kv @ lp["w_uk"]).reshape(b, s, hq, m.qk_nope_head_dim)
    vv = (c_kv @ lp["w_uv"]).reshape(b, s, hq, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe[:, :, None], (b, s, hq, m.qk_rope_head_dim))], axis=-1)
    attn = blocks.flash_attention(q, k, vv, causal=True,
                                  q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
    return attn.reshape(b, s, -1) @ lp["wo"], (c_kv, k_pe)


def _ffn(lp, h, cfg):
    """The layer's MLP: experts where the layer has a router, else dense."""
    if "router" in lp and cfg.moe.dropless:
        return blocks.moe_dropless(lp, h, cfg.moe, cfg.act)
    if "router" in lp:
        return blocks.moe_layer(
            {k_: lp[k_] for k_ in ("router", "w_in", "w_gate", "w_out")},
            h, cfg.moe, cfg.act)
    y = blocks.mlp({k_: lp[k_] for k_ in ("w_in", "w_gate", "w_out") if k_ in lp}, h, cfg.act)
    return y, {"moe_lb": jnp.zeros((), jnp.float32), "moe_z": jnp.zeros((), jnp.float32)}


def _attn_proj(lp, x, cfg, positions):
    b, s, d = x.shape
    dh, hq, hkv = cfg.dh, cfg.n_heads, cfg.n_kv_heads
    q = (x @ lp["wq"]).reshape(b, s, hq, dh)
    k = (x @ lp["wk"]).reshape(b, s, hkv, dh)
    vv = (x @ lp["wv"]).reshape(b, s, hkv, dh)
    if cfg.causal:  # decoder LMs use RoPE; the encoder stub keeps raw proj
        q = blocks.apply_rope(q, positions, cfg.rope_theta)
        k = blocks.apply_rope(k, positions, cfg.rope_theta)
    return q, k, vv


def _layer(lp: PyTree, x: jax.Array, cfg: ArchConfig, positions: jax.Array,
           return_kv: bool = False):
    from repro.dist.sharding import constrain

    # Megatron-style sequence parallelism on the residual stream: the layer
    # carry (= the remat residual saved per layer) shards S over `model`, so
    # the per-layer saved activation is 1/TP of the full stream; XLA inserts
    # the all-gather before attention and the reduce-scatter after.  The MoE
    # a2a dispatch consumes the token-sharded layout directly (§Perf).
    x = constrain(x, "batch", "model", None)
    b, s, d = x.shape
    h = blocks.rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.mla is not None:
        attn_out, kv = _mla_attention(lp, h, cfg, positions)
        x = x + attn_out
    else:
        q, k, vv = _attn_proj(lp, h, cfg, positions)
        kv = (k, vv)
        attn = blocks.flash_attention(
            q, k, vv, causal=cfg.causal, window=cfg.sliding_window,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
        x = x + (attn.reshape(b, s, -1) @ lp["wo"])

    h = blocks.rms_norm(x, lp["ln2"], cfg.norm_eps)
    y, aux = _ffn(lp, h, cfg)
    x = x + y
    if return_kv:
        return x, aux, kv
    return x, aux


# ---------------------------------------------------------------------------
# embedding / frontend
# ---------------------------------------------------------------------------

def embed_inputs(params: PyTree, batch: Dict[str, jax.Array], cfg: ArchConfig,
                 compute_dtype) -> Tuple[jax.Array, int]:
    """Returns (x (B, S_total, d), n_prefix) where n_prefix positions carry
    modality-frontend content (no LM loss there)."""
    if cfg.frontend == "audio":
        x = batch["frames"].astype(compute_dtype) @ params["frontend_proj"].astype(compute_dtype)
        return x, 0
    tok = jnp.take(params["embed"], batch["tokens"], axis=0).astype(compute_dtype)
    if cfg.frontend == "vision":
        px = batch["patches"].astype(compute_dtype) @ params["frontend_proj"].astype(compute_dtype)
        return jnp.concatenate([px, tok], axis=1), px.shape[1]
    return tok, 0


# ---------------------------------------------------------------------------
# train forward + loss
# ---------------------------------------------------------------------------

def hidden_states(params: PyTree, batch: Dict[str, jax.Array], cfg: ArchConfig,
                  *, remat: bool = True) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence backbone -> (final normed hidden (B, S_total, d), aux)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x, n_prefix = embed_inputs(params, batch, cfg, cdt)
    b, s, d = x.shape
    positions = jnp.arange(s)[None, :]

    def body(carry, lp):
        x, lb, lz = carry
        x, aux = _layer(lp, x, cfg, positions)
        return (x, lb + aux["moe_lb"], lz + aux["moe_z"]), None

    body_fn = jax.checkpoint(body) if remat else body
    # Cast the stacked weights to compute dtype OUTSIDE the scan: the FSDP
    # re-gather inside each layer iteration then moves bf16, not f32 —
    # halving the dominant all-gather volume of FSDP training (§Perf).
    carry = (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    for stack in _stacks(params):
        carry, _ = jax.lax.scan(body_fn, carry, _to_compute(stack, cdt))
    x, lb, lz = carry
    x = blocks.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, {"moe_lb": lb / cfg.n_layers, "moe_z": lz / cfg.n_layers,
               "n_prefix": n_prefix}


def _head(params, cfg):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def forward(params: PyTree, batch: Dict[str, jax.Array], cfg: ArchConfig,
            *, remat: bool = True) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full logits (tests / small-scale use; training uses chunked CE)."""
    x, aux = hidden_states(params, batch, cfg, remat=remat)
    cdt = jnp.dtype(cfg.compute_dtype)
    logits = (x @ _head(params, cfg).astype(cdt)).astype(jnp.float32)
    return logits, aux


def loss_fn(params: PyTree, batch: Dict[str, jax.Array], cfg: ArchConfig,
            *, remat: bool = True) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    x, aux = hidden_states(params, batch, cfg, remat=remat)
    n_prefix = aux["n_prefix"]
    if cfg.causal:
        # next-token prediction over the text region (skip modality prefix)
        targets = batch["tokens"][:, 1:]
        xs = x[:, n_prefix : n_prefix + targets.shape[1]]
    else:
        # encoder-only (masked-prediction stub): predict the token at each pos
        targets = batch["tokens"]
        xs = x[:, : targets.shape[1]]
    loss = blocks.chunked_softmax_xent(xs, _head(params, cfg), targets)
    total = loss + 0.01 * aux["moe_lb"] + aux["moe_z"]
    return total, {"ce": loss, **{k: v for k, v in aux.items() if k != "n_prefix"}}


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------

_KV_RP_SEED = 20180615  # fixed: serving-time constant, reproducible everywhere


def _kv_rp_matrix(cfg: ArchConfig) -> Optional[jax.Array]:
    """Ternary JL sketch R (dh, dh//kv_rp) for key compression.  With the
    paper's s=p sparsity, E⟨Rq, Rk⟩ = ⟨q, k⟩ exactly (no rescale), so the
    softmax keeps its original 1/sqrt(dh) temperature (scale_dh)."""
    if cfg.kv_rp is None:
        return None
    from repro.core import random_projection as rp_mod

    rcfg = rp_mod.RPConfig(m=cfg.dh, p=cfg.dh // cfg.kv_rp, normalize="isometry")
    r = rp_mod.sample_ternary(jax.random.PRNGKey(_KV_RP_SEED), rcfg)
    return r.astype(jnp.float32).T * rcfg.scale          # (dh, dh_r)


def _sketch_k(k: jax.Array, r: Optional[jax.Array]) -> jax.Array:
    if r is None:
        return k
    return (k.astype(jnp.float32) @ r).astype(k.dtype)   # (..., H, dh_r)


def prefill(params: PyTree, batch: Dict[str, jax.Array], cfg: ArchConfig,
            cache_size: int) -> Tuple[jax.Array, PyTree]:
    """Runs the prompt, returns (last-position logits, kv cache pytree)."""
    if cfg.mla is not None:
        return _mla_prefill(params, batch, cfg, cache_size)
    cdt = jnp.dtype(cfg.compute_dtype)
    x, _ = embed_inputs(params, batch, cfg, cdt)
    b, s, d = x.shape
    positions = jnp.arange(s)[None, :]
    win = cfg.sliding_window
    keep = min(cache_size, win) if win else cache_size
    rp_r = _kv_rp_matrix(cfg)

    def body(x, lp):
        x, _, (k, vv) = _layer(_to_compute(lp, cdt), x, cfg, positions, return_kv=True)
        k = _sketch_k(k, rp_r)
        # retain the cache tail (ring start at 0 == oldest kept position)
        k_keep = k[:, -keep:] if s >= keep else jnp.pad(k, ((0, 0), (0, keep - s), (0, 0), (0, 0)))
        v_keep = vv[:, -keep:] if s >= keep else jnp.pad(vv, ((0, 0), (0, keep - s), (0, 0), (0, 0)))
        return x, (k_keep.astype(cdt), v_keep.astype(cdt))

    x, kvs = jax.lax.scan(body, x, params["layers"])
    x = blocks.rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head.astype(cdt)).astype(jnp.float32)
    cache = {"k": kvs[0], "v": kvs[1],                      # (L, B, keep, Hkv, Dh)
             "len": jnp.full((), min(s, keep), jnp.int32),
             "pos": jnp.full((), s, jnp.int32)}
    return logits[:, 0], cache


def decode_step(params: PyTree, token: jax.Array, cache: PyTree, cfg: ArchConfig
                ) -> Tuple[jax.Array, PyTree]:
    """One token: token (B,) int32 -> (logits (B, V), updated cache)."""
    if cfg.mla is not None:
        return _mla_decode_step(params, token, cache, cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"], token[:, None], axis=0).astype(cdt)  # (B,1,d)
    b = x.shape[0]
    s_max = cache["k"].shape[2]
    pos = cache["pos"]
    slot = jnp.where(cache["len"] < s_max, cache["len"], pos % s_max)  # ring for SWA
    positions = jnp.full((b, 1), pos, jnp.int32)
    rp_r = _kv_rp_matrix(cfg)

    def body(x, inputs):
        lp, k_c, v_c = inputs
        lp = _to_compute(lp, cdt)
        h = blocks.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, vv = _attn_proj(lp, h, cfg, positions)
        q = _sketch_k(q, rp_r)
        k = _sketch_k(k, rp_r)
        k_c = jax.lax.dynamic_update_slice(k_c, k.astype(k_c.dtype), (0, slot, 0, 0))
        v_c = jax.lax.dynamic_update_slice(v_c, vv.astype(v_c.dtype), (0, slot, 0, 0))
        new_len = jnp.minimum(cache["len"] + 1, s_max)
        attn = blocks.decode_attention(q, k_c, v_c, new_len, window=cfg.sliding_window,
                                       scale_dh=cfg.dh)
        x = x + attn.reshape(b, 1, -1) @ lp["wo"]
        h2 = blocks.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            y, _ = blocks.moe_layer(
                {k_: lp[k_] for k_ in ("router", "w_in", "w_gate", "w_out")},
                h2, cfg.moe, cfg.act)
        else:
            y = blocks.mlp({k_: lp[k_] for k_ in ("w_in", "w_gate", "w_out") if k_ in lp}, h2, cfg.act)
        x = x + y
        return x, (k_c, v_c)

    x, kvs = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    x = blocks.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x[:, 0] @ head.astype(cdt)).astype(jnp.float32)
    new_cache = {"k": kvs[0], "v": kvs[1],
                 "len": jnp.minimum(cache["len"] + 1, s_max),
                 "pos": cache["pos"] + 1}
    return logits, new_cache


# ---------------------------------------------------------------------------
# latent attention: prefill and decode through the latent cache
# ---------------------------------------------------------------------------

def _pairs_zero(cfg: ArchConfig) -> Optional[jax.Array]:
    """The (token, expert) pair counter of the held experts, or None."""
    if cfg.moe is None or not cfg.moe.dropless:
        return None
    return jnp.zeros((cfg.moe.e_held,), jnp.int32)


def _add_pairs(total, aux):
    return total if total is None or "pairs" not in aux else total + aux["pairs"]


def _mla_prefill(params: PyTree, batch: Dict[str, jax.Array], cfg: ArchConfig,
                 cache_size: int) -> Tuple[jax.Array, PyTree]:
    """The prompt through every layer (expanded latent attention); the
    cache holds each layer's c_kv and k_pe padded to `cache_size`, and the
    pairs the held experts computed (`pairs_prefill`; `pairs_decode` counts
    on from zero in decode)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x, _ = embed_inputs(params, batch, cfg, cdt)
    b, s, d = x.shape
    if s > cache_size:
        raise ValueError(f"a prompt of {s} tokens does not fit a cache of {cache_size}")
    positions = jnp.arange(s)[None, :]
    pad = ((0, 0), (0, cache_size - s), (0, 0))

    def body(carry, lp):
        x, pairs = carry
        x, aux, (c_kv, k_pe) = _layer(_to_compute(lp, cdt), x, cfg, positions,
                                      return_kv=True)
        return (x, _add_pairs(pairs, aux)), (jnp.pad(c_kv, pad).astype(cdt),
                                             jnp.pad(k_pe, pad).astype(cdt))

    carry, c_kv, k_pe = (x, _pairs_zero(cfg)), [], []
    for stack in _stacks(params):
        carry, (c, kp) = jax.lax.scan(body, carry, stack)
        c_kv.append(c)
        k_pe.append(kp)
    x, pairs = carry
    x = blocks.rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = (x @ _head(params, cfg).astype(cdt)).astype(jnp.float32)
    cache = {"c_kv": jnp.concatenate(c_kv), "k_pe": jnp.concatenate(k_pe),
             "len": jnp.full((), s, jnp.int32), "pos": jnp.full((), s, jnp.int32)}
    if pairs is not None:
        cache["pairs_prefill"] = pairs
        cache["pairs_decode"] = jnp.zeros_like(pairs)
    return logits[:, 0], cache


def _mla_decode_step(params: PyTree, token: jax.Array, cache: PyTree,
                     cfg: ArchConfig) -> Tuple[jax.Array, PyTree]:
    """One token through the absorbed latent attention.  The whole cache
    rides in the scan's carry and each layer writes its one new slot in
    place; no layer's cache is copied."""
    m = cfg.mla
    cdt = jnp.dtype(cfg.compute_dtype)
    hq = cfg.n_heads
    x = jnp.take(params["embed"], token[:, None], axis=0).astype(cdt)  # (B,1,d)
    b = x.shape[0]
    s_max = cache["c_kv"].shape[2]
    slot = jnp.minimum(cache["len"], s_max - 1)
    new_len = jnp.minimum(cache["len"] + 1, s_max)
    positions = jnp.full((b, 1), cache["pos"], jnp.int32)
    scale = 1.0 / math.sqrt(m.qk_head_dim)

    def body(carry, inputs):
        x, c_all, kpe_all, pairs = carry
        lp, li = inputs
        lp = _to_compute(lp, cdt)
        h = blocks.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q_nope, q_pe, c_new, kpe_new = _mla_project(lp, h, cfg, positions)
        c_all = jax.lax.dynamic_update_slice(
            c_all, c_new[None].astype(cdt), (li, 0, slot, 0))
        kpe_all = jax.lax.dynamic_update_slice(
            kpe_all, kpe_new[None].astype(cdt), (li, 0, slot, 0))
        attn = blocks.mla_decode_attention(
            q_nope[:, 0], q_pe[:, 0],
            jax.lax.dynamic_index_in_dim(c_all, li, keepdims=False),
            jax.lax.dynamic_index_in_dim(kpe_all, li, keepdims=False),
            lp["w_uk"].reshape(m.kv_lora_rank, hq, m.qk_nope_head_dim),
            lp["w_uv"].reshape(m.kv_lora_rank, hq, m.v_head_dim),
            new_len, scale=scale)
        x = x + attn.reshape(b, 1, -1) @ lp["wo"]
        h2 = blocks.rms_norm(x, lp["ln2"], cfg.norm_eps)
        y, aux = _ffn(lp, h2, cfg)
        return (x + y, c_all, kpe_all, _add_pairs(pairs, aux)), None

    carry = (x, cache["c_kv"], cache["k_pe"], cache.get("pairs_decode"))
    first = 0
    for stack in _stacks(params):
        n = jax.tree.leaves(stack)[0].shape[0]
        carry, _ = jax.lax.scan(body, carry, (stack, first + jnp.arange(n)))
        first += n
    x, c_kv, k_pe, pairs = carry
    x = blocks.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x[:, 0] @ _head(params, cfg).astype(cdt)).astype(jnp.float32)
    new_cache = dict(cache, c_kv=c_kv, k_pe=k_pe, len=new_len,
                     pos=cache["pos"] + 1)
    if pairs is not None:
        new_cache["pairs_decode"] = pairs
    return logits, new_cache
