"""Operations and bytes the algorithms need, from the unpadded model shapes.

These are the algorithm's counts, not the kernels' padded buffers: the
Pallas kernels pad every narrow axis to 128 lanes, and a change that stops
moving that padding through HBM then reads as a gain.  A roofline share is
max(flops / peak FLOP/s, bytes / peak bytes/s) over the measured time.
"""

from __future__ import annotations

from typing import Any, Dict


# ---------------------------------------------------------------------------
# DR: RP (m -> p, ternary int8) then EASI (p -> n)
# ---------------------------------------------------------------------------

def dr_transform_flops(rows: int, m: int, p: int, n: int) -> float:
    """Served rows through (x Rᵀ) Bᵀ: 2mp + 2pn per row (1,280 at the
    paper's m=32, p=16, n=8)."""
    return 2.0 * rows * (m * p + p * n)


def dr_transform_bytes(rows: int, calls: int, m: int, p: int, n: int,
                       in_bytes: int = 4, out_bytes: int = 4,
                       b_bytes: int = 4) -> float:
    """Rows in and out, plus R (int8) and B read once per kernel call."""
    return float(rows * (m * in_bytes + n * out_bytes)
                 + calls * (p * m + n * p * b_bytes))


def easi_update_flops(rows: int, n: int, p: int, second_order: bool,
                      higher_order: bool) -> float:
    """The fused EASI update kernel on a block of `rows` outputs y (rows, n)
    and B (n, p): C = yᵀy/b and/or H = g(y)ᵀy/b with cubic g, G, then
    B - mu G B.  y itself is computed outside the kernel."""
    f = 0.0
    if second_order:
        f += 2.0 * rows * n * n + n * n
    if higher_order:
        f += 2.0 * rows * n + 2.0 * rows * n * n + n * n
    return f + 2.0 * n * n * p + 2.0 * n * p


def easi_update_bytes(rows: int, n: int, p: int, y_bytes: int = 4,
                      b_bytes: int = 4) -> float:
    """y read once, B read and written once."""
    return float(rows * n * y_bytes + 2 * n * p * b_bytes)


def dr_update_row_flops(m: int, p: int, n: int) -> float:
    """Model FLOPs one folded row adds to an update: its own projection
    and y = h Bᵀ (computed again for the update), and its share of C/H
    (rotation: H = g(y)ᵀy and the cube)."""
    return 2.0 * (m * p + p * n) + 2.0 * n * n + 2.0 * n


# ---------------------------------------------------------------------------
# decoder LM (llama layout: GQA attention, gated MLP, tied head)
# ---------------------------------------------------------------------------

def _lm_sizes(cfg: Dict[str, Any]):
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    dh = d // hq
    return (d, hq, hkv, dh, cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"])


def lm_layer_matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights one token multiplies in one layer (q, k, v, o, 3 MLP)."""
    d, hq, hkv, dh, f, _, _ = _lm_sizes(cfg)
    return d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * f


def lm_param_count(cfg: Dict[str, Any]) -> int:
    """Parameters of the model as configured (tied head counted once)."""
    d, _, _, _, _, v, L = _lm_sizes(cfg)
    n = L * (lm_layer_matmul_params(cfg) + 2 * d) + v * d + d
    if not cfg.get("tie_word_embeddings", False):
        n += v * d
    return n


def lm_decode_flops(cfg: Dict[str, Any], context: float,
                    batch: int = 1) -> float:
    """One decode step: every layer's matmuls, attention of one query over
    `context` cached positions (scores and weighted sum), and the head."""
    d, hq, _, dh, _, v, L = _lm_sizes(cfg)
    per_token = (L * (2.0 * lm_layer_matmul_params(cfg)
                      + 4.0 * hq * dh * context)
                 + 2.0 * d * v)
    return batch * per_token
