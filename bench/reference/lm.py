"""Plain reference of a Llama-layout decoder LM (SmolLM-135M's architecture).

From the published description of the architecture (Hugging Face
`LlamaForCausalLM`, the `model_type` of SmolLM-135M's config.json):
token embedding; per layer RMSNorm, grouped-query attention with rotary
position embedding (rotate-half form, base `rope_theta`), a residual add,
RMSNorm, a gated SiLU MLP and a residual add; a final RMSNorm; logits
against the tied embedding.  No cache, no chunking, no batching tricks:
one causal forward pass over the whole sequence, in float32 under
"highest" precision, or with every matmul operand rounded to
`operand_dtype` (the lower-precision control).

`init_params` makes the random weights the benchmark serves, in the
parameter layout the program takes (a dict of stacked per-layer arrays);
the names are the interface, the values are made here.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def sizes(cfg: Dict[str, Any]):
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return dict(d=d, hq=hq, hkv=cfg["num_key_value_heads"], dh=d // hq,
                f=cfg["intermediate_size"], v=cfg["vocab_size"],
                L=cfg["num_hidden_layers"])


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key: jax.Array, shape: tuple) -> Dict[str, Any]:
    d, hq, hkv, dh, f, v, L = shape
    k_embed, k_layers = jax.random.split(key)

    def normal(k, shp, std):
        return jax.random.normal(k, shp, jnp.float32) * std

    ks = jax.random.split(k_layers, 7)
    return {
        "embed": normal(k_embed, (v, d), 1.0),
        "layers": {
            "ln1": jnp.ones((L, d), jnp.float32),
            "ln2": jnp.ones((L, d), jnp.float32),
            "wq": normal(ks[0], (L, d, hq * dh), 1.0 / math.sqrt(d)),
            "wk": normal(ks[1], (L, d, hkv * dh), 1.0 / math.sqrt(d)),
            "wv": normal(ks[2], (L, d, hkv * dh), 1.0 / math.sqrt(d)),
            "wo": normal(ks[3], (L, hq * dh, d), 2.0 / math.sqrt(hq * dh)),
            "w_in": normal(ks[4], (L, d, f), 1.0 / math.sqrt(d)),
            "w_gate": normal(ks[5], (L, d, f), 1.0 / math.sqrt(d)),
            "w_out": normal(ks[6], (L, f, d), 2.0 / math.sqrt(f)),
        },
        "final_norm": jnp.ones((d,), jnp.float32),
    }


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """float32 weights on the device from `key`, in one call: embeddings
    with unit variance, projections with variance 1/fan-in, the two
    projections that write into the residual stream (attention output,
    MLP output) with variance 4/fan-in, norms at 1.  The larger residual
    writes keep the stream from being dominated by the token's own
    embedding: with variance 1/(2 L fan-in) the input token was the
    argmax at every position and no rounding could move a served token."""
    s = sizes(cfg)
    return _init(key, (s["d"], s["hq"], s["hkv"], s["dh"], s["f"], s["v"],
                       s["L"]))


def _mm(a, b, operand_dtype):
    if operand_dtype is not None:
        a = a.astype(operand_dtype).astype(jnp.float32)
        b = b.astype(operand_dtype).astype(jnp.float32)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, H, dh): rotate-half RoPE at positions 0..S-1."""
    s, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (S, dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _forward(params, tokens, shape, eps, theta, operand_dtype):
    d, hq, hkv, dh, f, v, L = shape
    s = tokens.shape[0]
    x = params["embed"][tokens]                                # (S, d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    g = hq // hkv

    def layer(x, lp):
        h = _rms(x, lp["ln1"], eps)
        q = _rope(_mm(h, lp["wq"], operand_dtype).reshape(s, hq, dh), theta)
        k = _rope(_mm(h, lp["wk"], operand_dtype).reshape(s, hkv, dh), theta)
        val = _mm(h, lp["wv"], operand_dtype).reshape(s, hkv, dh)
        k = jnp.repeat(k, g, axis=1)                           # (S, hq, dh)
        val = jnp.repeat(val, g, axis=1)
        sc = _mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0),
                 operand_dtype) / math.sqrt(dh)                # (hq, S, S)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        att = _mm(p, val.transpose(1, 0, 2), operand_dtype)   # (hq, S, dh)
        x = x + _mm(att.transpose(1, 0, 2).reshape(s, hq * dh), lp["wo"],
                    operand_dtype)
        h = _rms(x, lp["ln2"], eps)
        mlp = jax.nn.silu(_mm(h, lp["w_gate"], operand_dtype)) * \
            _mm(h, lp["w_in"], operand_dtype)
        return x + _mm(mlp, lp["w_out"], operand_dtype), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"], eps)
    return _mm(x, params["embed"].T, operand_dtype)            # (S, V)


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: Dict[str, Any],
            operand_dtype: Optional[Any] = None) -> jax.Array:
    """Logits (S, vocab) of one sequence `tokens` (S,)."""
    sz = sizes(cfg)
    shape = (sz["d"], sz["hq"], sz["hkv"], sz["dh"], sz["f"], sz["v"],
             sz["L"])
    return _forward(params, tokens, shape, float(cfg["rms_norm_eps"]),
                    float(cfg["rope_theta"]), operand_dtype)
