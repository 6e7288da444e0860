"""Pallas TPU kernel: fused EASI relative-gradient + weight update.

Given a block of outputs Y (b × n) and the separation matrix B (n × m),
computes in one VMEM-resident pass (paper Alg. 1 lines 3–6):

    C = YᵀY / b                       (second-order, optional)
    H = g(Y)ᵀY / b,  g = cubic        (higher-order, optional)
    G = [C − I]·so + [H − Hᵀ]·ho
    B ← B − μ G B

The FPGA datapath streams one sample through a MAC array per cycle; the TPU
equivalent batches a block and fuses all five stages so that g(Y) (b×n),
C, H and G (n×n) never exist in HBM — only B is re-read/re-written, tiled
over its m (column) dimension.  G is computed once in a VMEM scratch on the
first grid step and reused for every column tile (TPU grid steps execute
sequentially on a core, so scratch persists across the grid).

The paper's reconfigurability mux (EASI / whitening / rotation-only) maps to
the `second_order` / `higher_order` static flags — same kernel, three
algorithms, zero recompilation of the surrounding graph beyond flag value.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(y_ref, b_ref, o_ref, g_scratch, *, mu, inv_b, second_order, higher_order, g_name):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _compute_g():
        y = y_ref[...].astype(jnp.float32)           # (b, n)
        n = y.shape[1]
        g_acc = jnp.zeros((n, n), jnp.float32)
        if second_order:
            c = jax.lax.dot_general(
                y, y, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * inv_b
            g_acc += c - jnp.eye(n, dtype=jnp.float32)
        if higher_order:
            if g_name == "cubic":
                gy = y * y * y
            elif g_name == "tanh":
                gy = jnp.tanh(y)
            else:  # sign_cubic
                gy = jnp.sign(y) * y * y
            h = jax.lax.dot_general(
                gy, y, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * inv_b
            g_acc += h - h.T
        g_scratch[...] = g_acc

    b_blk = b_ref[...].astype(jnp.float32)           # (n, bm)
    gb = jnp.dot(g_scratch[...], b_blk, preferred_element_type=jnp.float32)
    o_ref[...] = (b_blk - mu * gb).astype(o_ref.dtype)


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


@functools.partial(
    jax.jit,
    static_argnames=("mu", "second_order", "higher_order", "g_name", "block_m", "interpret"),
)
def easi_apply(
    b_mat: jax.Array,        # (n, m) f32
    y: jax.Array,            # (b, n) float — outputs for this block
    *,
    mu: float,
    second_order: bool = True,
    higher_order: bool = True,
    g_name: str = "cubic",
    block_m: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns updated B. Fused G computation + tiled column update."""
    n, m = b_mat.shape
    b, n2 = y.shape
    assert n == n2, (b_mat.shape, y.shape)

    n_pad = _round_up(n, 128)
    b_pad = _round_up(b, 8)
    bm = min(block_m, _round_up(m, 128))
    m_pad = _round_up(m, bm)

    # Zero-padding is exact here: padded Y rows add 0 to C/H; padded B rows
    # are 0 and stay 0 (their −I diagonal multiplies a zero row of B).
    y_p = jnp.pad(y, ((0, b_pad - b), (0, n_pad - n)))
    b_p = jnp.pad(b_mat, ((0, n_pad - n), (0, m_pad - m)))

    out = pl.pallas_call(
        functools.partial(
            _kernel, mu=mu, inv_b=1.0 / b,
            second_order=second_order, higher_order=higher_order, g_name=g_name,
        ),
        grid=(m_pad // bm,),
        in_specs=[
            pl.BlockSpec((b_pad, n_pad), lambda k: (0, 0)),   # Y resident
            pl.BlockSpec((n_pad, bm), lambda k: (0, k)),      # B column tile
        ],
        out_specs=pl.BlockSpec((n_pad, bm), lambda k: (0, k)),
        out_shape=jax.ShapeDtypeStruct((n_pad, m_pad), b_mat.dtype),
        scratch_shapes=[pltpu.VMEM((n_pad, n_pad), jnp.float32)],
        interpret=interpret,
        name="easi_apply",
    )(y_p, b_p)
    return out[:n, :m]
