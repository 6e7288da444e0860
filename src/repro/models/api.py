"""Family-dispatched model API: one entry point for every assigned arch.

    init_params(key, cfg)                 -> param pytree
    loss_fn(params, batch, cfg)           -> (loss, aux)       [train]
    prefill(params, batch, cfg, size)     -> (logits, cache)   [serving]
    decode_step(params, token, cache, cfg)-> (logits, cache')  [serving]
    init_cache(cfg, batch, size)          -> structural cache  [dry-run]
    input_specs(cfg, shape_name)          -> ShapeDtypeStructs [dry-run]
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import rwkv6, ssm, transformer
from repro.models.config import ArchConfig

PyTree = Any


def _mod(cfg: ArchConfig):
    return {"transformer": transformer, "rwkv6": rwkv6, "zamba": ssm}[cfg.family]


def init_params(key: jax.Array, cfg: ArchConfig) -> PyTree:
    return _mod(cfg).init_params(key, cfg)


def loss_fn(params, batch, cfg: ArchConfig, *, remat: bool = True):
    return _mod(cfg).loss_fn(params, batch, cfg, remat=remat)


def prefill(params, batch, cfg: ArchConfig, cache_size: int):
    return _mod(cfg).prefill(params, batch, cfg, cache_size)


def decode_step(params, token, cache, cfg: ArchConfig):
    return _mod(cfg).decode_step(params, token, cache, cfg)


def init_cache(cfg: ArchConfig, batch: int, cache_size: int) -> PyTree:
    """Concrete zero cache (smoke tests) — structural twin of prefill output."""
    cdt = jnp.dtype(cfg.compute_dtype)
    if cfg.family == "rwkv6":
        return rwkv6.init_state(cfg, batch, cdt)
    if cfg.family == "zamba":
        return ssm.init_cache(cfg, batch, cache_size, cdt)
    if cfg.mla is not None:
        m = cfg.mla
        cache = {
            "c_kv": jnp.zeros((cfg.n_layers, batch, cache_size, m.kv_lora_rank), cdt),
            "k_pe": jnp.zeros((cfg.n_layers, batch, cache_size, m.qk_rope_head_dim), cdt),
            "len": jnp.zeros((), jnp.int32),
            "pos": jnp.zeros((), jnp.int32),
        }
        if cfg.moe is not None and cfg.moe.dropless:
            cache["pairs_prefill"] = jnp.zeros((cfg.moe.e_held,), jnp.int32)
            cache["pairs_decode"] = jnp.zeros((cfg.moe.e_held,), jnp.int32)
        return cache
    win = cfg.sliding_window
    keep = min(cache_size, win) if win else cache_size
    dh_k = cfg.dh // cfg.kv_rp if cfg.kv_rp else cfg.dh  # RP-sketched keys
    return {
        "k": jnp.zeros((cfg.n_layers, batch, keep, cfg.n_kv_heads, dh_k), cdt),
        "v": jnp.zeros((cfg.n_layers, batch, keep, cfg.n_kv_heads, cfg.dh), cdt),
        "len": jnp.zeros((), jnp.int32),
        "pos": jnp.zeros((), jnp.int32),
    }


def exact_param_counts(cfg: ArchConfig) -> Tuple[int, int]:
    """(total, active) param counts from the REAL param tree (eval_shape).

    `active` discounts routed expert weights by top_k/E — the 6·N_active·D
    convention for MoE model-FLOPs.  For a chip holding E_held of the E
    experts the tree holds E_held of them, and top_k/E of those is the
    expected top_k·E_held/E experts a token runs here.  Shared experts and
    leading dense layers count whole.
    """
    import re

    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    total = sum(l.size for _, l in flat)
    active = float(total)
    if cfg.moe is not None:
        for kp, l in flat:
            p = jax.tree_util.keystr(kp)
            if l.ndim == 4 and re.search(r"w_(in|gate|out)", p):
                active -= l.size * (1.0 - cfg.moe.top_k / cfg.moe.n_experts)
    return int(total), int(active)


# ---------------------------------------------------------------------------
# assigned shape cells
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeCell("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
}


def cell_supported(cfg: ArchConfig, shape_name: str) -> Tuple[bool, str]:
    """Assignment rules: encoder archs skip decode; long_500k needs
    sub-quadratic attention (SSM/hybrid/SWA)."""
    cell = SHAPES[shape_name]
    if not cfg.causal and cell.kind == "decode":
        return False, "encoder-only arch has no decode step"
    if shape_name == "long_500k":
        subquad = cfg.family in ("rwkv6", "zamba") or cfg.sliding_window is not None
        if not subquad:
            return False, "pure full-attention arch; 500k cache excluded by assignment rule"
    return True, ""


def input_specs(cfg: ArchConfig, shape_name: str, *, batch_override: int = None) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    No device allocation — exactly what jit(...).lower(**specs) needs.
    """
    cell = SHAPES[shape_name]
    b = batch_override or cell.global_batch
    s = cell.seq_len
    f32 = jnp.float32
    i32 = jnp.int32

    def tok_batch(seq):
        d: Dict[str, Any] = {}
        if cfg.frontend == "audio":
            d["frames"] = jax.ShapeDtypeStruct((b, seq, cfg.frontend_dim), f32)
            d["tokens"] = jax.ShapeDtypeStruct((b, seq), i32)  # targets
        elif cfg.frontend == "vision":
            d["patches"] = jax.ShapeDtypeStruct((b, cfg.frontend_seq, cfg.frontend_dim), f32)
            d["tokens"] = jax.ShapeDtypeStruct((b, seq), i32)
        else:
            d["tokens"] = jax.ShapeDtypeStruct((b, seq), i32)
        return d

    if cell.kind in ("train", "prefill"):
        return {"batch": tok_batch(s)}
    # decode: one new token against a seq_len-deep cache
    cache = jax.eval_shape(lambda: init_cache(cfg, b, s))
    return {"token": jax.ShapeDtypeStruct((b,), i32), "cache": cache}
