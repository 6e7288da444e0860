"""Pallas TPU kernel: ternary random projection  y = scale · x Rᵀ.

R is the paper's ternary {−1,0,+1} matrix stored as **int8** (p × m).  On the
FPGA the ternary alphabet deletes multipliers; the MXU cannot skip zeros, so
the TPU-native win is HBM traffic: int8 weights move 4× fewer bytes than f32
(2× vs bf16) and are widened to the compute dtype *inside VMEM*, after the
DMA.  The matmul itself runs on the MXU at full rate.

Tiling: grid (M/bm, P/bp, K/bk), K innermost so the f32 accumulator tile in
VMEM is revisited across the contraction;  BlockSpecs keep one (bm × bk) x
tile, one (bp × bk) R tile and one (bm × bp) out tile resident per step.
Block shapes are MXU/VPU aligned: multiples of (8, 128) for f32 outputs and
(32, 128) for the int8 operand's native layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, r_ref, o_ref, *, scale: float, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                                  # (bm, bk) compute dtype
    r = r_ref[...].astype(x.dtype)                  # (bp, bk) int8 -> widen in VMEM
    acc = jax.lax.dot_general(
        x, r,
        dimension_numbers=(((1,), (1,)), ((), ())),  # contract k: x @ r.T
        preferred_element_type=jnp.float32,
    )
    o_ref[...] += (acc * scale).astype(o_ref.dtype)


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


@functools.partial(jax.jit, static_argnames=("scale", "block_m", "block_p", "block_k", "interpret"))
def ternary_matmul(
    x: jax.Array,            # (b, m) float
    r_int8: jax.Array,       # (p, m) int8 ternary
    *,
    scale: float = 1.0,
    block_m: int = 128,
    block_p: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """y (b, p) = scale * x @ r_int8ᵀ, f32 accumulation."""
    b, m = x.shape
    p, m2 = r_int8.shape
    assert m == m2, (x.shape, r_int8.shape)

    bm = min(block_m, _round_up(b, 8))
    bp = min(block_p, _round_up(p, 128))
    bk = min(block_k, _round_up(m, 128))

    # Pad to tile multiples (zero columns/rows contribute 0 to the dot).
    bp_pad, mp_pad, kp_pad = _round_up(b, bm), _round_up(p, bp), _round_up(m, bk)
    x_p = jnp.pad(x, ((0, bp_pad - b), (0, kp_pad - m)))
    r_p = jnp.pad(r_int8, ((0, mp_pad - p), (0, kp_pad - m)))

    grid = (bp_pad // bm, mp_pad // bp, kp_pad // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bp, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bp), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp_pad, mp_pad), x.dtype),
        interpret=interpret,
        name="ternary_matmul",
    )(x_p, r_p)
    return out[:b, :p]
