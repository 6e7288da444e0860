"""Plain reference of a DeepSeek-V3-layout decoder LM (Moonlight-16B-A3B's
architecture) over one chip's share of the experts.

From the published description (DeepSeek-V2 arXiv:2405.04434 §2.1,
DeepSeek-V3 arXiv:2412.19437 §2.1, and the Hugging Face `deepseek_v3`
modeling code named by Moonlight's config.json): token embedding; per
layer RMSNorm, multi-head latent attention, a residual add, RMSNorm, an
MLP and a residual add; a final RMSNorm; logits against an untied head.

  attention  q = h W_q per head, split [q_nope (128) | q_pe (64)];
             [c_kv (512) | k_pe (64)] = h W_kva, c_kv <- RMSNorm(c_kv);
             k_nope_h = c_kv W_UK,h, v_h = c_kv W_UV,h; RoPE on q_pe and
             on k_pe (one vector shared by every head); scores
             (q_nope_h.k_nope_h + q_pe_h.k_pe) / sqrt(192), causal
             softmax; out = concat_h(p_h v_h) W_o
  layer 0    a gated SiLU MLP of width intermediate_size
  layers 1+  s = sigmoid(h W_r) in f32; the top k experts by s + b (the
             selection bias); w_i = scale * s_i / sum_top s_j;
             y = sum_top w_i E_i(h) + S(h), E_i and S gated SiLU MLPs (S of
             width n_shared * moe_intermediate_size)

Departures from the published model, each also in the configuration's
`assumed`/`deployment`:

  * the expert share: the file's `n_routed_experts` experts are held, at
    `deployment.held_offset` of the published `deployment.experts_published`;
    routing is over all of those, and the experts held elsewhere add
    nothing here, as in the program;
  * RoPE pairs: the Hugging Face code permutes each rotary vector from
    interleaved pairs to rotate-half order before rotating; here the
    rotate-half form is applied to the stored layout directly (with random
    weights the permutation is a relabelling of weight columns);
  * no rope scaling (the catalog's config has no `rope_scaling` key).

No cache, no kernels, no batching: one causal forward pass over one
sequence, in float32 under "highest" precision, with every matmul operand
rounded to `operand_dtype` if given (the lower-precision control).
Attention runs in query blocks so that an 8,192-token sequence fits; the
expert layer computes every held expert densely on every token and keeps
each token's own.

`init_params` makes the random weights the benchmark serves, in the
parameter layout the program takes (a dict of stacked per-layer arrays,
stored in the configuration's parameter dtype); the names are the
interface, the values are made here.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    dep = cfg["deployment"]
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        c=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        f_dense=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
        e=dep["experts_published"], eh=cfg["n_routed_experts"],
        e0=dep["held_offset"], k=cfg["num_experts_per_tok"],
        shared=cfg["n_shared_experts"], v=cfg["vocab_size"],
        L=cfg["num_hidden_layers"], L0=cfg["first_k_dense_replace"])


def _key(sz: Dict[str, Any]) -> tuple:
    return tuple(sorted(sz.items()))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init(key: jax.Array, shape: tuple, dtype: str) -> Dict[str, Any]:
    sz = dict(shape)
    d, h, c, dn, dr, dv = (sz[n] for n in ("d", "h", "c", "dn", "dr", "dv"))
    f, fd, e, eh, e0 = sz["f"], sz["f_dense"], sz["e"], sz["eh"], sz["e0"]
    v, L, L0 = sz["v"], sz["L"], sz["L0"]
    fs = sz["shared"] * f
    keys = iter(jax.random.split(key, 32))

    def normal(shp, std, dt=dtype):
        return (jax.random.normal(next(keys), shp, jnp.float32) * std).astype(dt)

    def attention(n):
        return {
            "ln1": jnp.ones((n, d), dtype), "ln2": jnp.ones((n, d), dtype),
            "wq": normal((n, d, h * (dn + dr)), 1 / math.sqrt(d)),
            "w_kva": normal((n, d, c + dr), 1 / math.sqrt(d)),
            "kv_norm": jnp.ones((n, c), dtype),
            "w_uk": normal((n, c, h * dn), 1 / math.sqrt(c)),
            "w_uv": normal((n, c, h * dv), 1 / math.sqrt(c)),
            "wo": normal((n, h * dv, d), 2 / math.sqrt(h * dv)),
        }

    n = L - L0
    # experts of one layer as the published layer's experts e0..e0+eh:
    # the weights of expert j come from its own key, so a share of the
    # experts is a slice of the whole layer's
    expert_keys = jax.random.split(next(keys), 3 * e).reshape(3, e, 2)

    def experts(which, d_in, d_out, std):
        ks = expert_keys[which, e0:e0 + eh]
        w = jax.vmap(lambda kk: jax.random.normal(
            kk, (n, d_in, d_out), jnp.float32))(ks)
        return (w.transpose(1, 0, 2, 3) * std).astype(dtype)

    moe = dict(attention(n))
    moe.update({
        "router": normal((n, d, e), 1 / math.sqrt(d), jnp.float32),
        "select_bias": normal((n, e), 0.05, jnp.float32),
        "w_in": experts(0, d, f, 1 / math.sqrt(d)),
        "w_gate": experts(1, d, f, 1 / math.sqrt(d)),
        "w_out": experts(2, f, d, 2 / math.sqrt(f)),
    })
    if fs:
        moe.update({"shared_in": normal((n, d, fs), 1 / math.sqrt(d)),
                    "shared_gate": normal((n, d, fs), 1 / math.sqrt(d)),
                    "shared_out": normal((n, fs, d), 2 / math.sqrt(fs))})
    params = {"embed": normal((v, d), 1.0), "layers": moe,
              "final_norm": jnp.ones((d,), dtype),
              "lm_head": normal((d, v), 1 / math.sqrt(d))}
    if L0:
        dense = dict(attention(L0))
        dense.update({"w_in": normal((L0, d, fd), 1 / math.sqrt(d)),
                      "w_gate": normal((L0, d, fd), 1 / math.sqrt(d)),
                      "w_out": normal((L0, fd, d), 2 / math.sqrt(fd))})
        params["dense_layers"] = dense
    return params


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Weights on the device from `key`, in the configuration's parameter
    dtype (the router and its selection bias in float32): embeddings
    N(0, 1), projections N(0, 1/fan_in), the projections that write into
    the residual stream (attention output, every MLP's down projection)
    N(0, 4/fan_in) as in `reference/lm.py`, the head N(0, 1/d), the
    selection bias N(0, 0.05^2), norms at 1.  An expert's weights come
    from its own key, so that a share of the experts is a slice of the
    whole layer's."""
    return _init(key, _key(sizes(cfg)), cfg["run"]["param_dtype"])


def _mm(a, b, operand_dtype):
    if operand_dtype is not None:
        a = a.astype(operand_dtype).astype(jnp.float32)
        b = b.astype(operand_dtype).astype(jnp.float32)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, H, dr): rotate-half RoPE at positions 0..S-1."""
    s, _, dr = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(lp, x, sz, eps, theta, od):
    s = x.shape[0]
    h, c, dn, dr, dv = sz["h"], sz["c"], sz["dn"], sz["dr"], sz["dv"]
    hx = _rms(x, lp["ln1"], eps)
    q = _mm(hx, lp["wq"], od).reshape(s, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    kva = _mm(hx, lp["w_kva"], od)
    c_kv = _rms(kva[:, :c], lp["kv_norm"], eps)
    k_pe = _rope(kva[:, None, c:], theta)                       # (S, 1, dr)
    k = jnp.concatenate([_mm(c_kv, lp["w_uk"], od).reshape(s, h, dn),
                         jnp.broadcast_to(k_pe, (s, h, dr))], -1)
    val = _mm(c_kv, lp["w_uv"], od).reshape(s, h, dv)
    kt = k.transpose(1, 2, 0)                                   # (H, 192, S)
    vt = val.transpose(1, 0, 2)                                 # (H, S, dv)
    nb = -(-s // Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * Q_BLOCK - s), (0, 0), (0, 0)))
    qb = qb.reshape(nb, Q_BLOCK, h, dn + dr).transpose(0, 2, 1, 3)

    def block(args):
        q_blk, i = args                                         # (H, Bq, 192)
        sc = _mm(q_blk, kt, od) / math.sqrt(dn + dr)            # (H, Bq, S)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        mask = rows[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return _mm(p, vt, od)                                   # (H, Bq, dv)

    att = jax.lax.map(block, (qb, jnp.arange(nb)))              # (nb, H, Bq, dv)
    att = att.transpose(0, 2, 1, 3).reshape(nb * Q_BLOCK, h * dv)[:s]
    return x + _mm(att, lp["wo"], od)


def _mlp(x, w_in, w_gate, w_out, od):
    return _mm(jax.nn.silu(_mm(x, w_gate, od)) * _mm(x, w_in, od), w_out, od)


def route(hx, router, bias, sz, scale, od):
    """Experts (S, k) and their weights (S, k), routed over all experts
    (norm_topk_prob: the weights are normalised over the top k)."""
    s = jax.nn.sigmoid(_mm(hx, router, od))
    _, top_e = jax.lax.top_k(s + bias, sz["k"])
    w = jnp.take_along_axis(s, top_e, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return top_e, w * scale


def experts_held(hx, lp, top_e, w, sz, od):
    """The held experts' part of the layer: every held expert on every
    token, each token keeping the experts it was routed to."""
    e0, eh = sz["e0"], sz["eh"]
    onehot = (top_e[..., None] == (e0 + jnp.arange(eh))).astype(jnp.float32)
    gate = jnp.einsum("sk,ske->se", w, onehot)                  # (S, eh)

    def one(acc, xs):
        w_in, w_gate, w_out, g = xs
        return acc + g[:, None] * _mlp(hx, w_in, w_gate, w_out, od), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(hx),
                        (lp["w_in"], lp["w_gate"], lp["w_out"], gate.T))
    return y


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 8))
def _forward(params, tokens, shape, eps, theta, scale, operand_dtype,
             first, count):
    sz = dict(shape)
    od = operand_dtype
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    x = params["embed"][tokens].astype(jnp.float32)             # (S, d)

    def dense_layer(x, lp):
        lp = f32(lp)
        x = _attention(lp, x, sz, eps, theta, od)
        hx = _rms(x, lp["ln2"], eps)
        return x + _mlp(hx, lp["w_in"], lp["w_gate"], lp["w_out"], od), None

    def moe_layer(x, lp):
        lp = f32(lp)
        x = _attention(lp, x, sz, eps, theta, od)
        hx = _rms(x, lp["ln2"], eps)
        top_e, w = route(hx, lp["router"], lp["select_bias"], sz, scale, od)
        y = experts_held(hx, lp, top_e, w, sz, od)
        if "shared_in" in lp:
            y = y + _mlp(hx, lp["shared_in"], lp["shared_gate"],
                         lp["shared_out"], od)
        return x + y, top_e

    if "dense_layers" in params:
        x, _ = jax.lax.scan(dense_layer, x, params["dense_layers"])
    x, routes = jax.lax.scan(moe_layer, x, params["layers"])
    if count is not None:
        x = jax.lax.dynamic_slice_in_dim(x, first, count)
    x = _rms(x, params["final_norm"].astype(jnp.float32), eps)
    return _mm(x, params["lm_head"].astype(jnp.float32), od), routes


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: Dict[str, Any],
            operand_dtype: Optional[Any] = None, with_routes: bool = False,
            first: int = 0, count: Optional[int] = None):
    """Logits (S, vocab) of one sequence `tokens` (S,) — with `count`, of
    its positions first..first+count-1 only; with `with_routes` also the
    experts each MoE layer picked for each token (L_moe, S, k)."""
    logits, routes = _forward(
        params, tokens, _key(sizes(cfg)), float(cfg["rms_norm_eps"]),
        float(cfg["rope_theta"]), float(cfg["routed_scaling_factor"]),
        operand_dtype,
        jnp.asarray(first, jnp.int32), count)
    return (logits, routes) if with_routes else logits
