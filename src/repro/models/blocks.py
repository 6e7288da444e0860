"""Shared model building blocks: norms, RoPE, flash-style attention, MLP, MoE.

Everything is a pure function over explicit param pytrees (no framework),
scan-friendly (stacked-layer leading dim) and sharding-agnostic (pjit decides
layout from the rules in repro.dist.sharding).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig, MoESpec


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
    return out.astype(x.dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(dh: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (..., S, H, Dh), positions (..., S) int32."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta)                       # (dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, dh/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, dh/2)
    sin = jnp.sin(angles)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : dh // 2], x32[..., dh // 2 :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention — flash-style double-chunked scan (memory-bounded at any S)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _chunk_mask(q_pos, k_pos, *, causal: bool, window: Optional[int]):
    """(cq, ck) boolean mask of allowed attention."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


def _flash_forward(q, k, v, *, causal, window, cq, ck, q_offset, skv_true):
    """Core double-chunked online-softmax pass.

    q: (b, nq, cq, hkv, g, dh) f32; k/v: (nk, b, ck, hkv, dh) f32.
    Returns (out (b, nq, cq, hkv, g, dh), lse (b, nq, cq, hkv, g)).
    """
    b, nq, cq_, hkv, g, dh = q.shape
    nk = k.shape[0]
    scale = 1.0 / math.sqrt(dh)

    def q_step(_, qi):
        q_blk, q_idx = qi
        q_pos = q_offset + q_idx * cq + jnp.arange(cq)

        def kv_step(carry, ki):
            acc, m_run, l_run = carry
            k_blk, v_blk, k_idx = ki
            k_pos = k_idx * ck + jnp.arange(ck)
            # inputs stay in compute dtype (bf16 in models); accumulate f32
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            mask = _chunk_mask(q_pos, k_pos, causal=causal, window=window)
            mask &= k_pos[None, :] < skv_true
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, hkv, g, cq, v.shape[-1]), jnp.float32)
        m0 = jnp.full((b, hkv, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq), jnp.float32)
        (acc, m_run, l_run), _ = jax.lax.scan(kv_step, (acc0, m0, l0), (k, v, jnp.arange(nk)))
        l_safe = jnp.maximum(l_run, 1e-30)
        out = (acc / l_safe[..., None]).astype(q.dtype)      # residual in compute dtype
        lse = m_run + jnp.log(l_safe)                        # (b,hkv,g,cq) f32
        return None, (out.transpose(0, 3, 1, 2, 4), lse.transpose(0, 3, 1, 2))

    _, (outs, lses) = jax.lax.scan(q_step, None, (q.transpose(1, 0, 2, 3, 4, 5), jnp.arange(nq)))
    return outs.transpose(1, 0, 2, 3, 4, 5), lses.transpose(1, 0, 2, 3, 4)


def _flash_backward(q, k, v, out, lse, dout, *, causal, window, cq, ck, q_offset, skv_true):
    """FlashAttention-style backward: recompute p tiles from (q, k, lse).

    Two passes (dq; then dk/dv) so no full-size carry crosses scan steps;
    residual memory is O(S·dh) + one (cq, ck) tile.
    """
    b, nq, cq_, hkv, g, dh = q.shape
    nk = k.shape[0]
    scale = 1.0 / math.sqrt(dh)
    delta = jnp.einsum("...d,...d->...", dout, out,
                       preferred_element_type=jnp.float32)   # (b,nq,cq,hkv,g)

    def mask_for(q_idx, k_idx):
        q_pos = q_offset + q_idx * cq + jnp.arange(cq)
        k_pos = k_idx * ck + jnp.arange(ck)
        m = _chunk_mask(q_pos, k_pos, causal=causal, window=window)
        return m & (k_pos[None, :] < skv_true)

    def p_tile(q_blk, k_blk, lse_blk, q_idx, k_idx):
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask_for(q_idx, k_idx)[None, None, None], s, NEG_INF)
        return jnp.exp(s - lse_blk.transpose(0, 2, 3, 1)[..., None])  # (b,hkv,g,cq,ck)

    # pass 1: dq per q chunk (scan q outer, kv inner)
    def dq_step(_, qi):
        q_blk, lse_blk, do_blk, dl_blk, q_idx = qi

        def kv_step(dq_acc, ki):
            k_blk, v_blk, k_idx = ki
            p = p_tile(q_blk, k_blk, lse_blk, q_idx, k_idx)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", do_blk, v_blk,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - dl_blk.transpose(0, 2, 3, 1)[..., None])
            dq_acc = dq_acc + jnp.einsum("bhgqk,bkhd->bqhgd", ds.astype(k_blk.dtype),
                                         k_blk, preferred_element_type=jnp.float32) * scale
            return dq_acc, None

        dq0 = jnp.zeros(q_blk.shape, jnp.float32)
        dq_blk, _ = jax.lax.scan(kv_step, dq0, (k, v, jnp.arange(nk)))
        return None, dq_blk.astype(q_blk.dtype)

    _, dq = jax.lax.scan(
        dq_step, None,
        (q.transpose(1, 0, 2, 3, 4, 5), lse.transpose(1, 0, 2, 3, 4),
         dout.transpose(1, 0, 2, 3, 4, 5), delta.transpose(1, 0, 2, 3, 4),
         jnp.arange(nq)))
    dq = dq.transpose(1, 0, 2, 3, 4, 5)

    # pass 2: dk/dv per kv chunk (scan kv outer, q inner)
    def dkv_step(_, ki):
        k_blk, v_blk, k_idx = ki

        def q_step(carry, qi):
            dk_acc, dv_acc = carry
            q_blk, lse_blk, do_blk, dl_blk, q_idx = qi
            p = p_tile(q_blk, k_blk, lse_blk, q_idx, k_idx)
            dv_acc = dv_acc + jnp.einsum("bhgqk,bqhgd->bkhd", p.astype(do_blk.dtype),
                                         do_blk, preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", do_blk, v_blk,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - dl_blk.transpose(0, 2, 3, 1)[..., None])
            dk_acc = dk_acc + jnp.einsum("bhgqk,bqhgd->bkhd", ds.astype(q_blk.dtype),
                                         q_blk, preferred_element_type=jnp.float32) * scale
            return (dk_acc, dv_acc), None

        z = jnp.zeros(k_blk.shape, jnp.float32)
        (dk_blk, dv_blk), _ = jax.lax.scan(
            q_step, (z, jnp.zeros(v_blk.shape, jnp.float32)),
            (q.transpose(1, 0, 2, 3, 4, 5), lse.transpose(1, 0, 2, 3, 4),
             dout.transpose(1, 0, 2, 3, 4, 5), delta.transpose(1, 0, 2, 3, 4),
             jnp.arange(nq)))
        return None, (dk_blk.astype(k_blk.dtype), dv_blk.astype(v_blk.dtype))

    _, (dk, dv) = jax.lax.scan(dkv_step, None, (k, v, jnp.arange(nk)))
    return dq, dk, dv


@functools.lru_cache(maxsize=None)
def _build_flash(causal, window, cq, ck, q_offset, skv_true):
    kw = dict(causal=causal, window=window, cq=cq, ck=ck,
              q_offset=q_offset, skv_true=skv_true)

    @jax.custom_vjp
    def fa(q, k, v):
        out, _ = _flash_forward(q, k, v, **kw)
        return out

    def fwd(q, k, v):
        out, lse = _flash_forward(q, k, v, **kw)
        return out, (q, k, v, out, lse)

    def bwd(res, dout):
        q, k, v, out, lse = res
        return _flash_backward(q, k, v, out, lse, dout, **kw)

    fa.defvjp(fwd, bwd)
    return fa


def flash_attention(
    q: jax.Array,            # (B, Sq, Hq, Dh)
    k: jax.Array,            # (B, Skv, Hkv, Dh)
    v: jax.Array,            # (B, Skv, Hkv, Dv)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    q_offset: int = 0,
) -> jax.Array:
    """Online-softmax attention, O(S·chunk) memory, GQA via grouped einsum.

    The S×S score matrix never materialises, in forward OR backward: a
    custom VJP recomputes probability tiles from (q, k, lse) FlashAttention-
    style, so residuals are O(S·dh) instead of O(S²) — this is what lets the
    32k-prefill and 4k-train cells fit HBM.  (No double-backward support.)
    Values may be narrower than queries and keys (latent attention: q/k
    192 wide, v 128); the softmax scale is 1/sqrt(Dh).
    """
    b, sq, hq, dh = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]
    g = hq // hkv

    cq = min(q_chunk, sq)
    ck = min(kv_chunk, skv)
    nq = -(-sq // cq)
    nk = -(-skv // ck)
    sq_pad, skv_pad = nq * cq, nk * ck
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_pad - sq), (0, 0), (0, 0)))
    if skv_pad != skv:
        k = jnp.pad(k, ((0, 0), (0, skv_pad - skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skv_pad - skv), (0, 0), (0, 0)))

    # keep compute dtype (bf16 in models); f32 only in accumulators/lse
    qg = q.reshape(b, nq, cq, hkv, g, dh)
    kc = k.reshape(b, nk, ck, hkv, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nk, ck, hkv, dv).transpose(1, 0, 2, 3, 4)

    fa = _build_flash(causal, window, cq, ck, q_offset, skv)
    out = fa(qg, kc, vc)                                     # (b,nq,cq,hkv,g,dv)
    out = out.reshape(b, sq_pad, hq, dv)
    return out[:, :sq].astype(q.dtype)


def decode_attention(
    q: jax.Array,            # (B, 1, Hq, Dh_k)
    k_cache: jax.Array,      # (B, S, Hkv, Dh_k)
    v_cache: jax.Array,      # (B, S, Hkv, Dh_v)
    cache_len: jax.Array,    # (B,) or scalar int32 — valid prefix length
    *,
    window: Optional[int] = None,
    scale_dh: Optional[int] = None,  # softmax scale dim (original dh when
                                     # q/k are RP-projected to a smaller Dh_k)
) -> jax.Array:
    """Single-token attention over a (ring-buffered) KV cache."""
    b, s, hkv, dh = k_cache.shape
    hq = q.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(scale_dh or dh)
    qg = q.reshape(b, hkv, g, dh).astype(jnp.float32)
    pos = jnp.arange(s)
    valid = pos[None, :] < jnp.broadcast_to(jnp.asarray(cache_len), (b,))[:, None]
    if window is not None:
        lo = jnp.broadcast_to(jnp.asarray(cache_len), (b,))[:, None] - window
        valid &= pos[None, :] >= lo
    s_ = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache.astype(jnp.float32)) * scale
    s_ = jnp.where(valid[:, None, None], s_, NEG_INF)
    p = jax.nn.softmax(s_, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, hq, v_cache.shape[-1]).astype(q.dtype)


def mla_decode_attention(
    q_nope: jax.Array,       # (B, H, Dn)
    q_pe: jax.Array,         # (B, H, Dr) rotated
    c_kv: jax.Array,         # (B, S, C) normed latents of the cache
    k_pe: jax.Array,         # (B, S, Dr) rotated, shared by every head
    w_uk: jax.Array,         # (C, H, Dn) latent -> key (no rotary part)
    w_uv: jax.Array,         # (C, H, Dv) latent -> value
    cache_len: jax.Array,    # scalar int32 — valid prefix length
    *,
    scale: float,
) -> jax.Array:
    """One query per head over a latent cache, in the absorbed form
    (DeepSeek-V2 §2.1.2): q_nope·W_UK is scored against the cached latents
    and W_UV is applied after the weighted sum, so no per-head key or value
    is ever built.  The cache is read in its own dtype (f32 accumulation);
    no f32 copy of it is made.  Returns (B, H, Dv)."""
    cdt = c_kv.dtype
    f32 = jnp.float32
    q_abs = jnp.einsum("bhd,chd->bhc", q_nope.astype(cdt), w_uk.astype(cdt),
                       preferred_element_type=f32)
    s = jnp.einsum("bhc,bsc->bhs", q_abs.astype(cdt), c_kv,
                   preferred_element_type=f32)
    s = s + jnp.einsum("bhr,bsr->bhs", q_pe.astype(cdt), k_pe,
                       preferred_element_type=f32)
    valid = jnp.arange(c_kv.shape[1]) < cache_len
    s = jnp.where(valid[None, None], s * scale, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhs,bsc->bhc", p.astype(cdt), c_kv,
                     preferred_element_type=f32)
    out = jnp.einsum("bhc,chd->bhd", ctx.astype(cdt), w_uv.astype(cdt),
                     preferred_element_type=f32)
    return out.astype(cdt)


# ---------------------------------------------------------------------------
# chunked cross-entropy — the (B, S, V) logits tensor never materialises
# ---------------------------------------------------------------------------

def chunked_softmax_xent(
    x: jax.Array,           # (B, T, d) final hidden states (already normed)
    head: jax.Array,        # (d, V)
    targets: jax.Array,     # (B, T) int32; -1 = ignore
    *,
    chunk: int = 512,
) -> jax.Array:
    """Mean token NLL, computed per sequence-chunk under jax.checkpoint so
    that only one (B, chunk, V) logits tile is ever alive (fwd AND bwd).
    At 4k × 50k-vocab this replaces a ~13 GB f32 residual with ~100 MB."""
    b, t, d = x.shape
    c = min(chunk, t)
    nc = -(-t // c)
    t_pad = nc * c
    if t_pad != t:
        x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, t_pad - t)), constant_values=-1)
    xs = x.reshape(b, nc, c, d).transpose(1, 0, 2, 3)
    ts = targets.reshape(b, nc, c).transpose(1, 0, 2)

    @jax.checkpoint
    def body(acc, inp):
        xc, tc = inp
        logits = (xc @ head.astype(xc.dtype)).astype(jnp.float32)
        # nll = lse - gold: one logits tile, reductions only (no logp tile)
        lse = jax.nn.logsumexp(logits, axis=-1)
        safe_t = jnp.maximum(tc, 0)
        gold = jnp.take_along_axis(logits, safe_t[..., None], axis=-1)[..., 0]
        mask = (tc >= 0).astype(jnp.float32)
        return (acc[0] + jnp.sum((lse - gold) * mask), acc[1] + jnp.sum(mask)), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (xs, ts))
    return total / jnp.maximum(count, 1.0)


# ---------------------------------------------------------------------------
# dense MLP (SwiGLU-style)
# ---------------------------------------------------------------------------

def mlp(params: dict, x: jax.Array, act: str) -> jax.Array:
    if "w_gate" in params:
        h = act_fn(act)(x @ params["w_gate"]) * (x @ params["w_in"])
    else:  # plain 2-matrix MLP (starcoder2-style)
        h = act_fn(act)(x @ params["w_in"])
    return h @ params["w_out"]


# ---------------------------------------------------------------------------
# MoE (token-choice top-k, sort-based capacity dispatch — MegaBlocks-style
# grouped GEMM without the custom kernel; experts shard over `model` for EP)
# ---------------------------------------------------------------------------

def moe_capacity(n_tokens: int, spec: MoESpec) -> int:
    c = int(math.ceil(n_tokens * spec.top_k * spec.capacity_factor / spec.n_experts))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly shapes


def _route(x, router, spec: MoESpec):
    """Shared routing: returns (sorted dispatch metadata, aux losses)."""
    t = x.shape[0]
    e, k = spec.n_experts, spec.top_k
    logits = (x.astype(jnp.float32) @ router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                   # (T, E)
    top_w, top_e = jax.lax.top_k(probs, k)                    # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1)                                # (T*k,)
    flat_w = top_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e)                               # stable in jax
    se, stok, sw = flat_e[order], flat_tok[order], flat_w[order]
    starts = jnp.searchsorted(se, jnp.arange(e))              # (E,)
    pos = jnp.arange(t * k) - starts[se]

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0)
    lb = e * jnp.sum(me * ce)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return se, stok, sw, pos, {"moe_lb": lb, "moe_z": z * spec.router_z_coef}


def _moe_compute(params, x, spec, act, *, e_local, e_offset, c):
    """Dispatch/compute/combine for experts [e_offset, e_offset+e_local).

    params' expert weights hold only the local slice.  Returns the PARTIAL
    output (only local experts' contributions) — caller sums over shards.
    """
    t, d = x.shape
    se, stok, sw, pos, aux = _route(x, params["router"], spec)
    keep = (pos < c) & (se >= e_offset) & (se < e_offset + e_local)
    dest = jnp.where(keep, (se - e_offset) * c + pos, e_local * c)  # drop -> OOB

    x_sorted = jnp.take(x, stok, axis=0)
    xe = jnp.zeros((e_local * c, d), x.dtype).at[dest].set(x_sorted, mode="drop")
    xe = xe.reshape(e_local, c, d)

    h = act_fn(act)(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", xe, params["w_in"])
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_out"]).reshape(e_local * c, d)

    gathered = jnp.take(ye, jnp.where(keep, dest, 0), axis=0) * keep[:, None]
    y = jax.ops.segment_sum(gathered * sw[:, None].astype(x.dtype), stok, num_segments=t)
    return y, aux


def _moe_a2a_block(params, x_my, spec, act, *, n_model, dax):
    """Token-split + all-to-all expert parallelism (inside shard_map).

    Receives this shard's DISJOINT token slice (the residual stream is
    sequence-parallel: T shards over data×model), routes it over all E
    experts, builds a (n_model, E_loc, c, d) send buffer, all-to-alls it so
    each shard receives exactly its experts' tokens from every peer, computes
    the expert FFN, all-to-alls back, and combines locally — tokens never
    leave their shard except inside the two all-to-alls.
    """
    e, k = spec.n_experts, spec.top_k
    e_loc = e // n_model
    t_my = x_my.shape[0]
    d = x_my.shape[1]

    se, stok, sw, pos, aux = _route(x_my, params["router"], spec)
    c = moe_capacity(t_my, spec)
    keep = pos < c
    dest = jnp.where(keep, se * c + pos, e * c)              # drop -> OOB

    send = jnp.zeros((e * c, d), x_my.dtype).at[dest].set(
        jnp.take(x_my, stok, axis=0), mode="drop")
    send = send.reshape(n_model, e_loc, c, d)
    # a2a: dim0 (expert-owner shard) scatters, source shards concatenate
    recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                              tiled=False)                   # (n_model, e_loc, c, d)
    recv = recv.transpose(1, 0, 2, 3).reshape(e_loc, n_model * c, d)

    h = act_fn(act)(jnp.einsum("ecd,edf->ecf", recv, params["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", recv, params["w_in"])
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_out"])      # (e_loc, n_model*c, d)

    back = ye.reshape(e_loc, n_model, c, d).transpose(1, 0, 2, 3)
    ye_my = jax.lax.all_to_all(back, "model", split_axis=0, concat_axis=0,
                               tiled=False)                  # (n_model, e_loc, c, d)
    ye_my = ye_my.reshape(e * c, d)

    gathered = jnp.take(ye_my, jnp.where(keep, dest, 0), axis=0) * keep[:, None]
    y_my = jax.ops.segment_sum(gathered * sw[:, None].astype(x_my.dtype),
                               stok, num_segments=t_my)      # (t_my, d)
    aux = {k_: jax.lax.pmean(v, ("model",) + tuple(dax if isinstance(dax, tuple) else (dax,)))
           for k_, v in aux.items()}
    return y_my, aux


def moe_layer(params: dict, x: jax.Array, spec: MoESpec, act: str):
    """x (B, S, d) -> (y (B, S, d), aux dict). Dropped-on-overflow capacity.

    EP structure: the residual stream is sequence-parallel (B over data,
    S over model), so every (data, model) shard already owns a disjoint
    token slice.  shard_map runs over the 3-D view (a flat (B·S, d) view
    CANNOT express that product sharding — contiguous-T chunks ≠ B×S-shard
    blocks, and XLA would reshard every layer); each shard flattens locally,
    routes its tokens over all experts, and exchanges hidden states with the
    expert owners via two all-to-alls (_moe_a2a_block).  Identical plain-JAX
    math on a single device (smoke tests).
    """
    from repro.dist.sharding import _ambient_mesh, axis_size, batch_axes

    b, s, d = x.shape
    mesh = _ambient_mesh()
    e = spec.n_experts
    n_model = axis_size(mesh, "model") if mesh is not None else 1
    dax = batch_axes(mesh) if mesh is not None else ()
    n_data = axis_size(mesh, dax) if mesh is not None else 1
    use_shard_map = (
        mesh is not None and n_model > 1 and e % n_model == 0
        and b % n_data == 0 and s % n_model == 0)

    if not use_shard_map:
        y, aux = _moe_compute(params, x.reshape(b * s, d), spec, act,
                              e_local=e, e_offset=0, c=moe_capacity(b * s, spec))
        return y.reshape(b, s, d), aux

    from jax.sharding import PartitionSpec as P

    def block(router, w_gate, w_in, w_out, x_blk):
        bl, sl, _ = x_blk.shape
        p = {"router": router, "w_gate": w_gate, "w_in": w_in, "w_out": w_out}
        y, aux = _moe_a2a_block(p, x_blk.reshape(bl * sl, d), spec, act,
                                n_model=n_model, dax=dax)
        return y.reshape(bl, sl, d), aux

    stream_spec = P(dax, "model", None)
    y, aux = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(), P("model"), P("model"), P("model"), stream_spec),
        out_specs=(stream_spec, P()),
        check_vma=False,
    )(params["router"], params["w_gate"], params["w_in"], params["w_out"], x)
    return y, aux


# ---------------------------------------------------------------------------
# Dropless MoE over a held share of the experts (DeepSeek-V3 routing)
# ---------------------------------------------------------------------------

def route_sigmoid(x: jax.Array, router: jax.Array, bias: jax.Array,
                  spec: MoESpec) -> Tuple[jax.Array, jax.Array]:
    """Aux-loss-free routing over all `n_experts`: s = sigmoid(x·W_r) in
    f32, the top k picked by s + bias, weights s over their top-k sum
    times `routed_scale`; the bias moves the choice only.
    x (T, d) -> (experts (T, k) int32, weights (T, k) f32)."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, top_e = jax.lax.top_k(s + bias.astype(jnp.float32), spec.top_k)
    top_w = jnp.take_along_axis(s, top_e, axis=-1)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    return top_e.astype(jnp.int32), top_w * spec.routed_scale


def _gmm_tiling(m: int, k: int, n: int, itemsize: int) -> Tuple[int, int, int]:
    """(tm, tk, tn) of the grouped matmul: 128-row tiles (16 rows for a
    decode step's few pairs), 512-deep contraction tiles, and output tiles
    as wide as fit 1.5 MB of weights (all of an expert's 1,408 columns
    behind a 512-deep tile)."""
    tm = 128 if m >= 128 else -(-m // 16) * 16
    tk = min(k, 512)
    tn = n if n * tk * itemsize <= 3 << 19 else 512
    return tm, tk, tn


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   group_offset: int, out_dtype) -> jax.Array:
    """Rows of `lhs` sorted by group, times their group's matrix, for the
    groups [group_offset, group_offset + rhs.shape[0]) only: the megablox
    grouped matmul (`group_sizes` holds every group's row count; rows of
    other groups come out zero).  lhs (m, k), rhs (G_held, k, n) -> (m, n);
    m is padded to the row tile here."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from repro.core.execution import resolve_interpret

    m, k = lhs.shape
    tiling = _gmm_tiling(m, k, rhs.shape[2], jnp.dtype(rhs.dtype).itemsize)
    m_pad = -(-m // tiling[0]) * tiling[0]
    if m_pad != m:
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    out = megablox.gmm(lhs, rhs.astype(lhs.dtype), group_sizes,
                       jnp.dtype(out_dtype), tiling,
                       jnp.asarray(group_offset, jnp.int32), None, False,
                       resolve_interpret())
    return out[:m]


def moe_routed_held(params: dict, x: jax.Array, spec: MoESpec, act: str):
    """The held experts' part of a dropless expert layer.

    Every token is routed over all `n_experts`; its (token, expert) pairs
    are sorted by expert and the held experts' rows go through three
    grouped matmuls (gate, up, down) with per-expert row counts — no
    capacity, nothing dropped.  Pairs of experts held elsewhere add
    nothing here.  x (T, d) -> (y (T, d) f32, pairs per held expert
    (E_held,) int32)."""
    t, d = x.shape
    k, e0, eh = spec.top_k, spec.held_offset, spec.e_held
    top_e, top_w = route_sigmoid(x, params["router"], params["select_bias"],
                                 spec)
    flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)                 # (T*k,)
    sorted_e = flat_e[order]
    group_sizes = jnp.bincount(flat_e, length=spec.n_experts).astype(jnp.int32)
    xs = jnp.take(x, order // k, axis=0)
    gate = grouped_matmul(xs, params["w_gate"], group_sizes, e0, x.dtype)
    up = grouped_matmul(xs, params["w_in"], group_sizes, e0, x.dtype)
    ys = grouped_matmul(act_fn(act)(gate) * up, params["w_out"], group_sizes,
                        e0, x.dtype)
    held = (sorted_e >= e0) & (sorted_e < e0 + eh)
    ys = jnp.where(held[:, None], ys, 0)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    y = jnp.einsum("tkd,tk->td", jnp.take(ys, inv, axis=0).reshape(t, k, d),
                   top_w, preferred_element_type=jnp.float32)
    return y, group_sizes[e0:e0 + eh]


def moe_dropless(params: dict, x: jax.Array, spec: MoESpec, act: str):
    """x (B, S, d) -> (y, aux): the held experts' part plus the shared
    experts (which every chip computes alike).  aux carries `pairs`, the
    (token, expert) pairs computed per held expert."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    y, pairs = moe_routed_held(params, xt, spec, act)
    if spec.n_shared:
        y = y + mlp({"w_in": params["shared_in"],
                     "w_gate": params["shared_gate"],
                     "w_out": params["shared_out"]}, xt, act)
    zero = jnp.zeros((), jnp.float32)
    return y.astype(x.dtype).reshape(b, s, d), {
        "moe_lb": zero, "moe_z": zero, "pairs": pairs}


# ---------------------------------------------------------------------------
# param init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in, d_out, dtype, scale: Optional[float] = None):
    s = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * s).astype(dtype)


def stacked(keys_fn, n: int):
    """Stack per-layer inits along a leading `layers` axis."""
    outs = [keys_fn(i) for i in range(n)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
