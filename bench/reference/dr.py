"""Plain reference of the paper's DR datapath: ternary RP, then EASI.

Nazemi, Eshratifar & Pedram, arXiv:1801.04014: §III-B (sparse ternary
random projection, entries +1/0/-1 with probabilities 1/(2s), 1-1/s,
1/(2s)) and §III-D, Eq. 6 (EASI, B <- B - mu [yyᵀ - I + g(y)yᵀ - y g(y)ᵀ] B
with g cubic; the paper's proposed datapath after RP keeps only the
higher-order, rotation term).  On a block of b rows the bracket is the
block mean, as the program's TPU form of the same estimator.

Written from those equations, not from the program.  Every product runs
in float32 under "highest" precision unless `operand_dtype` names a lower
type, which is the lower-precision control: operands are rounded to it
and products still accumulate in float32.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def rp_scale(cfg: Dict[str, Any]) -> float:
    """Output scale of the projection: "per_dim" sqrt(s/m) keeps each
    projected feature at the mean per-feature variance of the input."""
    mode = cfg["rp"]["normalize"]
    if mode != "per_dim":
        raise ValueError(f"normalize {mode!r}: the reference knows "
                         f"'per_dim' only")
    return math.sqrt(cfg["rp"]["sparsity"] / cfg["m"])


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init(key: jax.Array, m: int, p: int, n_s: tuple) -> tuple:
    n, s = n_s
    ku, kc, ks, kb = jax.random.split(key, 4)
    u = jax.random.uniform(ku, (p, m))
    half = 1.0 / (2.0 * s)
    r = jnp.where(u < half, 1, jnp.where(u < 2 * half, -1, 0)).astype(jnp.int8)
    # a row with no entry is a dead output: plant one +-1 in it
    dead = jnp.all(r == 0, axis=1)
    cols = jax.random.randint(kc, (p,), 0, m)
    signs = jnp.where(jax.random.bernoulli(ks, 0.5, (p,)), 1, -1)
    plant = (jax.nn.one_hot(cols, m, dtype=jnp.int32) * signs[:, None])
    r = jnp.where(dead[:, None], plant.astype(jnp.int8), r)
    # B: n orthonormal rows spanning a uniformly random subspace of R^p
    q, _ = jnp.linalg.qr(jax.random.normal(kb, (p, n), jnp.float32))
    return r, q.T


def init_state(cfg: Dict[str, Any], key: jax.Array) -> tuple:
    """(R int8 (p, m), B float32 (n, p)) on the device, in one call."""
    return _init(key, cfg["m"], cfg["p"], (cfg["n"], cfg["rp"]["sparsity"]))


def _mm(a: jax.Array, b: jax.Array, operand_dtype: Optional[Any]) -> jax.Array:
    if operand_dtype is not None:
        a = a.astype(operand_dtype).astype(jnp.float32)
        b = b.astype(operand_dtype).astype(jnp.float32)
    return jnp.matmul(a, b, precision=HIGHEST)


def project(r: jax.Array, x: jax.Array, scale: float,
            operand_dtype: Optional[Any] = None) -> jax.Array:
    """h = scale * x Rᵀ (R is ternary, exact in every type)."""
    return _mm(x, r.astype(jnp.float32).T, operand_dtype) * scale


def transform(r: jax.Array, b: jax.Array, x: jax.Array, scale: float,
              operand_dtype: Optional[Any] = None) -> jax.Array:
    """y = (scale * x Rᵀ) Bᵀ for rows x (rows, m)."""
    return _mm(project(r, x, scale, operand_dtype), b.T, operand_dtype)


def update(r: jax.Array, b: jax.Array, x: jax.Array, scale: float,
           mu: float, second_order: bool, higher_order: bool,
           operand_dtype: Optional[Any] = None) -> jax.Array:
    """One EASI step on a block x (rows, m): y = h Bᵀ, the block-mean
    bracket G, then B - mu G B."""
    y = transform(r, b, x, scale, operand_dtype)
    rows, n = y.shape
    g = jnp.zeros((n, n), jnp.float32)
    if second_order:
        g = g + _mm(y.T, y, operand_dtype) / rows - jnp.eye(n)
    if higher_order:
        h = _mm((y * y * y).T, y, operand_dtype) / rows
        g = g + h - h.T
    return b - mu * _mm(g, b, operand_dtype)
