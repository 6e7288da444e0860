"""Pallas TPU kernel: fused serve transform  out = (scale · x Rᵀ) Bᵀ.

The paper's deployment datapath is project-then-whiten: a static ternary
RP (R int8, p × m) followed by the adaptive stage's linear map (B, n × p).
Served through XLA that is three HLOs — pad, ternary matmul, dense matmul —
with the (b × p) intermediate round-tripping HBM between them.  Here the
whole bucketed micro-batch runs in ONE Pallas call: the projected tile
y₁ = scale·xRᵀ lives in a VMEM scratch accumulator and is contracted
against B the moment its k-loop finishes, so the intermediate never leaves
VMEM and R still moves int8 bytes over HBM (4× less than f32).

Tiling: grid (M/bm, P/bp, K/bk), k innermost.  For a fixed (i, j) the
scratch y₁ (bm × bp) accumulates x·Rᵀ across k; at the last k step it is
folded into the output tile o (bm × n_pad) — o is revisited across both j
and k (TPU grids execute sequentially, so the revisited tile persists).
All three tile sizes are meaningful autotuner knobs: bm trades VMEM
residency against grid parallelism, bp sizes the scratch, bk the DMA depth
of the contraction.  n is padded to one lane tile (n_pad = 128) — the
final dim is small by construction (it is the REDUCED dimensionality).

Zero-padding keeps everything exact: padded m-columns contribute 0 to y₁,
padded p-rows of R produce zero y₁ columns which meet zero B columns, and
padded batch rows / n rows are sliced off on return.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, r_ref, b_ref, o_ref, y_ref, *, scale: float, n_k: int):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init_y():
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[...]                                   # (bm, bk) compute dtype
    r = r_ref[...].astype(x.dtype)                   # (bp, bk) int8 -> widen in VMEM
    y_ref[...] += jax.lax.dot_general(
        x, r,
        dimension_numbers=(((1,), (1,)), ((), ())),  # contract k: x @ r.T
        preferred_element_type=jnp.float32,
    ) * scale

    @pl.when(k == n_k - 1)                           # y₁ tile complete: fold into out
    def _project():
        @pl.when(j == 0)
        def _init_o():
            o_ref[...] = jnp.zeros_like(o_ref)

        b = b_ref[...].astype(jnp.float32)           # (n_pad, bp)
        o_ref[...] += jax.lax.dot_general(
            y_ref[...], b,
            dimension_numbers=(((1,), (1,)), ((), ())),  # contract p: y @ b.T
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


@functools.partial(jax.jit, static_argnames=("scale", "block_m", "block_p",
                                             "block_k", "interpret"))
def fused_transform(
    x: jax.Array,            # (b, m) float
    r_int8: jax.Array,       # (p, m) int8 ternary
    b_mat: jax.Array,        # (n, p) float
    *,
    scale: float = 1.0,
    block_m: int = 128,
    block_p: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """out (b, n) = (scale * x @ r_int8ᵀ) @ b_matᵀ, f32 accumulation
    throughout; the (b, p) intermediate never leaves VMEM."""
    rows, m = x.shape
    p, m2 = r_int8.shape
    n, p2 = b_mat.shape
    assert m == m2, (x.shape, r_int8.shape)
    assert p == p2, (r_int8.shape, b_mat.shape)

    bm = min(block_m, _round_up(rows, 8))
    bp = min(block_p, _round_up(p, 128))
    bk = min(block_k, _round_up(m, 128))
    n_pad = _round_up(n, 128)

    rows_pad, p_pad, m_pad = (_round_up(rows, bm), _round_up(p, bp),
                              _round_up(m, bk))
    x_p = jnp.pad(x, ((0, rows_pad - rows), (0, m_pad - m)))
    r_p = jnp.pad(r_int8, ((0, p_pad - p), (0, m_pad - m)))
    b_p = jnp.pad(b_mat, ((0, n_pad - n), (0, p_pad - p)))

    grid = (rows_pad // bm, p_pad // bp, m_pad // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bp, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((n_pad, bp), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, n_pad), lambda i, j, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, n_pad), b_mat.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bp), jnp.float32)],
        interpret=interpret,
        name="fused_transform",
    )(x_p, r_p, b_p)
    return out[:rows, :n]
