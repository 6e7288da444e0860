"""Operations and bytes of a DeepSeek-V3-layout LM (latent attention,
routed and shared experts, leading dense layers) on one chip holding a
share of the routed experts, from the configuration file's shapes.

Model counts, not the kernels' padded buffers: attention counts the
causal (query, key) pairs, the routed experts count the (token, expert)
pairs the held experts computed (the program's counters), and a decode
step's bytes are the least it must read — every weight outside the routed
experts, the routed experts it ran, and the latent cache up to its
context — so a share of the HBM peak computed from them is a lower bound.
"""

from __future__ import annotations

from typing import Any, Dict


def _s(cfg: Dict[str, Any]) -> Dict[str, int]:
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        c=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        f=cfg["moe_intermediate_size"], fd=cfg["intermediate_size"],
        e=cfg["deployment"]["experts_published"],
        shared=cfg["n_shared_experts"], v=cfg["vocab_size"],
        L=cfg["num_hidden_layers"], L0=cfg["first_k_dense_replace"])


def _wbytes(cfg: Dict[str, Any]) -> int:
    return 2 if cfg["run"]["param_dtype"] == "bfloat16" else 4


def attention_params(cfg: Dict[str, Any]) -> int:
    """Weights one token multiplies in one layer's latent attention
    (q, the latent and rotary key, W_UK, W_UV, the output)."""
    s = _s(cfg)
    d, h, c = s["d"], s["h"], s["c"]
    return (d * h * (s["dn"] + s["dr"]) + d * (c + s["dr"])
            + c * h * s["dn"] + c * h * s["dv"] + h * s["dv"] * d)


def token_params(cfg: Dict[str, Any]) -> int:
    """Weights one token multiplies outside the routed experts and the
    head: every layer's attention, the dense layers' MLPs, the MoE layers'
    routers and shared experts."""
    s = _s(cfg)
    d, n_moe = s["d"], s["L"] - s["L0"]
    return (s["L"] * attention_params(cfg) + s["L0"] * 3 * d * s["fd"]
            + n_moe * (d * s["e"] + s["shared"] * 3 * d * s["f"]))


def expert_pair_flops(cfg: Dict[str, Any]) -> float:
    """One (token, expert) pair: gate, up and down, 6 d f."""
    s = _s(cfg)
    return 6.0 * s["d"] * s["f"]


def prefill_flops(cfg: Dict[str, Any], prefills: float, tokens: float,
                  causal_pairs: float, expert_pairs: float) -> float:
    """Prefills of `tokens` prompt tokens in all, `causal_pairs` (query,
    key) pairs of causal attention, `expert_pairs` held-expert pairs:
    matmuls, attention (scores over q_nope+q_pe, the weighted sum over v)
    and the head on each prompt's last position."""
    s = _s(cfg)
    attn = 2.0 * s["L"] * s["h"] * (s["dn"] + s["dr"] + s["dv"]) * causal_pairs
    return (2.0 * token_params(cfg) * tokens + attn
            + expert_pairs * expert_pair_flops(cfg)
            + 2.0 * s["d"] * s["v"] * prefills)


def decode_flops(cfg: Dict[str, Any], context: float,
                 expert_pairs: float) -> float:
    """One decode step of one sequence at `context` cached positions with
    `expert_pairs` held-expert pairs: matmuls, the absorbed attention
    (scores against the latent and the rotary key, the weighted sum of the
    latents) and the head."""
    s = _s(cfg)
    attn = 2.0 * s["L"] * s["h"] * (2 * s["c"] + s["dr"]) * context
    return (2.0 * token_params(cfg) + attn
            + expert_pairs * expert_pair_flops(cfg) + 2.0 * s["d"] * s["v"])


def decode_bytes(cfg: Dict[str, Any], context: float,
                 expert_pairs: float) -> float:
    """The least one decode step reads: every weight outside the routed
    experts (routers and selection biases in float32, one embedding row),
    the `expert_pairs` routed experts it ran, and the latent cache (c_kv
    and k_pe of every layer) at `context` positions."""
    s = _s(cfg)
    w = _wbytes(cfg)
    d, n_moe = s["d"], s["L"] - s["L0"]
    router = n_moe * (d * s["e"] + s["e"]) * 4
    other = token_params(cfg) - n_moe * d * s["e"]
    norms = s["L"] * (2 * d + s["c"]) + d
    weights = (other + norms + d * s["v"] + d) * w + router
    experts = expert_pairs * 3 * d * s["f"] * w
    cache = s["L"] * context * (s["c"] + s["dr"]) * w
    return float(weights + experts + cache)


def gmm_flops(cfg: Dict[str, Any], expert_pairs: float) -> float:
    """The grouped matmuls of `expert_pairs` pairs (gate, up, down)."""
    return expert_pairs * expert_pair_flops(cfg)


def gmm_bytes(cfg: Dict[str, Any], expert_pairs: float,
              decode_pairs: float) -> float:
    """The least the grouped matmuls read and write: each pair's rows in
    and out of the three products, and the weights of the expert of each
    decode pair (a decode step's pairs are distinct experts, each read
    once).  Prefill weight reads are left out: a lower bound."""
    s = _s(cfg)
    w = _wbytes(cfg)
    d, f = s["d"], s["f"]
    rows = expert_pairs * (3 * d + 3 * f) * w
    return float(rows + decode_pairs * 3 * d * f * w)
