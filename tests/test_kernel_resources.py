"""The static VMEM resource model and its checker.

Two halves.  The pure-math half (no jax import) exercises the physical
tile rounding, the per-kernel estimators, and the paper-scale report the
CI gate rides on.  The interpret-mode half pins the model against
reality: a spy on `pl.pallas_call` captures the BlockSpecs, grid, and
scratch of a REAL `fused_transform` trace and asserts the model's block
arithmetic and byte count match the actual allocation — the model
cannot silently drift from the wrapper it prices.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import scan
from repro.kernels.resource_model import (
    VMEM_BUDGET_BYTES,
    Buffer,
    KernelEstimate,
    MODELED_KERNELS,
    easi_apply_estimate,
    flash_attention_estimate,
    fused_transform_estimate,
    paper_scale_report,
    ternary_matmul_estimate,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# physical tile rounding
# ---------------------------------------------------------------------------

def test_buffer_rounds_to_physical_tiles():
    # a (cq, 1) f32 running-max column really occupies (cq, 128) lanes
    assert Buffer("m", (512, 1), 4, "scratch").bytes == 512 * 128 * 4
    # sublane granularity depends on dtype width: 8 rows for f32...
    assert Buffer("x", (3, 128), 4, "in").bytes == 8 * 128 * 4
    # ...32 rows for int8
    assert Buffer("r", (3, 128), 1, "in").bytes == 32 * 128 * 1
    # aligned shapes price exactly
    assert Buffer("x", (128, 512), 4, "in").bytes == 128 * 512 * 4
    # leading dims multiply through untouched
    assert Buffer("q", (1, 512, 128), 4, "in").bytes == 512 * 128 * 4


def test_pipelined_counts_streamed_tiles_twice_scratch_once():
    est = KernelEstimate(
        kernel="k", grid=(2, 3),
        buffers=[Buffer("a", (8, 128), 4, "in"),
                 Buffer("o", (8, 128), 4, "out"),
                 Buffer("s", (8, 128), 4, "scratch")])
    tile = 8 * 128 * 4
    assert est.grid_steps == 6
    assert est.vmem_bytes == 3 * tile
    assert est.vmem_pipelined_bytes == 3 * tile + 2 * tile


def test_validate_flags_misaligned_and_overbudget():
    bad = KernelEstimate(
        kernel="k", grid=(1,),
        buffers=[Buffer("x", (8, 100), 4, "in")])
    assert any("lane dim 100" in p for p in bad.validate())
    huge = KernelEstimate(
        kernel="k", grid=(1,),
        buffers=[Buffer("x", (8192, 8192), 4, "in")])
    assert any("exceeds budget" in p for p in huge.validate())


# ---------------------------------------------------------------------------
# estimators mirror the wrappers' clamp math
# ---------------------------------------------------------------------------

def test_fused_transform_estimate_paper_scale():
    est = fused_transform_estimate(rows=1024, m=32, p=16, n=8)
    # every dim clamps to one 128-lane tile at this scale except rows
    assert est.blocks == {"bm": 128, "bp": 128, "bk": 128, "n_pad": 128}
    assert est.grid == (8, 1, 1)
    tile = 128 * 128
    assert est.vmem_bytes == tile * (4 + 1 + 4 + 4 + 4)
    assert est.vmem_pipelined_bytes == est.vmem_bytes + tile * (4 + 1 + 4 + 4)
    assert est.validate() == []


def test_estimates_clamp_small_shapes():
    est = ternary_matmul_estimate(rows=4, m=20, p=12)
    assert est.blocks == {"bm": 8, "bp": 128, "bk": 128}
    assert est.grid == (1, 1, 1)
    est = easi_apply_estimate(n=8, m=16, batch=100)
    assert est.blocks == {"bm": 128, "n_pad": 128, "b_pad": 104}
    assert est.grid == (1,)
    est = flash_attention_estimate(batch=2, sq=100, skv=300, hq=4, hkv=4,
                                   dh=64)
    assert est.blocks == {"cq": 104, "ck": 384, "dh_p": 128}
    assert est.grid == (8, 1, 1)


def test_paper_scale_report_covers_every_modeled_kernel_under_budget():
    report = paper_scale_report()
    assert {est.kernel for est in report} == set(MODELED_KERNELS)
    for est in report:
        assert est.validate() == [], est.kernel
        assert est.vmem_pipelined_bytes <= VMEM_BUDGET_BYTES


def test_report_rows_are_gated_in_committed_baseline():
    """Every paper-scale row must have a ceiling in baseline.json — a
    kernel the gate silently skips is not budgeted at all."""
    with open(os.path.join(REPO, "benchmarks", "baseline.json")) as f:
        baseline = json.load(f)
    for est in paper_scale_report():
        row = est.to_row()
        assert row["name"] in baseline, row["name"]
        gate = baseline[row["name"]]["vmem_pipelined_bytes"]
        # committed ceiling is the current estimate (factor-2 headroom
        # lives in check_regression, not here)
        assert row["vmem_pipelined_bytes"] <= gate


def test_cli_writes_regression_compatible_rows(tmp_path):
    out = tmp_path / "rows.json"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.kernels.resource_model",
         "--json", str(out)],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
             "JAX_PLATFORMS": "cpu"},
        timeout=120)
    assert proc.returncode == 0, proc.stderr
    rows = json.loads(out.read_text())
    assert {r["name"] for r in rows} == {
        f"analysis/kernel_resources/{k}" for k in MODELED_KERNELS}
    for r in rows:
        assert r["vmem_pipelined_bytes"] > r["vmem_bytes"] > 0


# ---------------------------------------------------------------------------
# kernel-resources checker (fixture files)
# ---------------------------------------------------------------------------

def _kernel_file(tmp_path, code):
    d = tmp_path / "repro" / "kernels"
    d.mkdir(parents=True, exist_ok=True)
    p = d / "fixture.py"
    p.write_text(textwrap.dedent(code))
    return str(p)


def _findings(path, checker="kernel-resources"):
    return [f for f in scan([path]).findings if f.checker == checker]


HEADER = """
    import functools
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def _round_up(v, mult):
        return ((v + mult - 1) // mult) * mult
"""


def test_checker_flags_unmodeled_pallas_call(tmp_path):
    path = _kernel_file(tmp_path, HEADER + """
    def _k(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def brand_new_kernel(x):
        return pl.pallas_call(
            _k, grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), x.dtype),
        )(x)
    """)
    assert any("no entry in" in f.message for f in _findings(path))


def test_checker_flags_stale_model_entry(tmp_path):
    # imports pallas, defines a modeled name, but no pallas_call inside
    path = _kernel_file(tmp_path, HEADER + """
    def ternary_matmul(x, r):
        return x @ r.T
    """)
    assert any("stale model" in f.message for f in _findings(path))


def test_checker_ignores_dispatch_layers_without_pallas_import(tmp_path):
    # kernels/ops.py shape: re-exports modeled names, no pallas import
    d = tmp_path / "repro" / "kernels"
    d.mkdir(parents=True, exist_ok=True)
    p = d / "fixture.py"
    p.write_text(textwrap.dedent("""
        def ternary_matmul(x, r, backend="xla"):
            return x @ r.T
    """))
    assert _findings(str(p)) == []


def test_checker_flags_unclamped_tile_dim(tmp_path):
    path = _kernel_file(tmp_path, HEADER + """
    def _k(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def ternary_matmul(x):
        bm = x.shape[0]
        return pl.pallas_call(
            _k, grid=(1,),
            in_specs=[pl.BlockSpec((bm, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), x.dtype),
        )(x)
    """)
    assert any("not clamped" in f.message and "bm" in f.message
               for f in _findings(path))


def test_checker_accepts_clamp_idiom(tmp_path):
    path = _kernel_file(tmp_path, HEADER + """
    def _k(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def ternary_matmul(x):
        rows, m = x.shape
        bm = min(128, _round_up(rows, 8))
        bk = _round_up(m, 128)
        return pl.pallas_call(
            _k, grid=(1,),
            in_specs=[pl.BlockSpec((bm, bk), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((bm, bk), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), x.dtype),
        )(x)
    """)
    assert _findings(path) == []


def test_checker_flags_non_f32_scratch(tmp_path):
    path = _kernel_file(tmp_path, HEADER + """
    def _k(x_ref, o_ref, acc_ref):
        o_ref[...] = x_ref[...]

    def ternary_matmul(x):
        return pl.pallas_call(
            _k, grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), x.dtype),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.bfloat16)],
        )(x)
    """)
    assert any("not jnp.float32" in f.message for f in _findings(path))


def test_checker_flags_dot_without_f32_accumulator(tmp_path):
    path = _kernel_file(tmp_path, HEADER + """
    def _k(x_ref, r_ref, o_ref):
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], r_ref[...],
            dimension_numbers=(((1,), (1,)), ((), ())))

    def ternary_matmul(x, r):
        return pl.pallas_call(
            functools.partial(_k), grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0)),
                      pl.BlockSpec((8, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 8), x.dtype),
        )(x, r)
    """)
    assert any("preferred_element_type" in f.message
               for f in _findings(path))


def test_checker_flags_index_map_arity_mismatch(tmp_path):
    path = _kernel_file(tmp_path, HEADER + """
    def _k(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def ternary_matmul(x):
        return pl.pallas_call(
            _k, grid=(2, 2),
            in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 256), x.dtype),
        )(x)
    """)
    assert any("arity" in f.message for f in _findings(path))


def test_repo_kernels_are_clean():
    assert _findings(os.path.join(REPO, "src", "repro", "kernels")) == []


# ---------------------------------------------------------------------------
# interpret mode: the model pinned against a live fused_transform trace
# ---------------------------------------------------------------------------

@pytest.mark.kernels
def test_model_matches_live_fused_transform_allocation(monkeypatch):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import fused_transform as ft_mod

    rows, m, p, n = 48, 20, 12, 5          # deliberately unaligned
    est = fused_transform_estimate(rows=rows, m=m, p=p, n=n)

    captured = {}
    real = ft_mod.pl.pallas_call

    def spy(kernel, **kwargs):
        captured.update(kwargs)
        return real(kernel, **kwargs)

    monkeypatch.setattr(ft_mod.pl, "pallas_call", spy)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(rows, m)), jnp.float32)
    r = jnp.asarray(rng.integers(-1, 2, size=(p, m)), jnp.int8)
    b = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    got = ft_mod.fused_transform(x, r, b, scale=0.37, interpret=True)

    # numerics stay right with the spy in place
    want = (0.37 * (np.asarray(x) @ np.asarray(r, np.float32).T)
            ) @ np.asarray(b).T
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)

    assert captured, "pallas_call was never intercepted (stale jit cache?)"
    assert tuple(captured["grid"]) == est.grid

    bm, bp, bk = est.blocks["bm"], est.blocks["bp"], est.blocks["bk"]
    n_pad = est.blocks["n_pad"]
    in_shapes = [tuple(s.block_shape) for s in captured["in_specs"]]
    assert in_shapes == [(bm, bk), (bp, bk), (n_pad, bp)]
    assert tuple(captured["out_specs"].block_shape) == (bm, n_pad)

    (scratch,) = captured["scratch_shapes"]
    assert tuple(scratch.shape) == (bm, bp)
    assert jnp.dtype(scratch.dtype) == jnp.float32

    # rebuild the byte count from the CAPTURED allocation and compare
    # with the model's estimate: the model cannot drift from the wrapper
    live = [
        Buffer("x", in_shapes[0], x.dtype.itemsize, "in"),
        Buffer("r_int8", in_shapes[1], r.dtype.itemsize, "in"),
        Buffer("b_mat", in_shapes[2], b.dtype.itemsize, "in"),
        Buffer("out", tuple(captured["out_specs"].block_shape),
               jnp.dtype(jnp.float32).itemsize, "out"),
        Buffer("y_scratch", tuple(scratch.shape),
               jnp.dtype(scratch.dtype).itemsize, "scratch"),
    ]
    assert sum(bf.bytes for bf in live) == est.vmem_bytes
    assert est.validate() == []
