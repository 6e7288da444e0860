"""The main-path Pallas kernels compile for a TPU v5e chip.

Nothing runs: each kernel is lowered and compiled ahead of time for one
chip of a described (not attached) `v5e:2x2` topology, which the TPU
compiler installed with JAX can do from a CPU-only host.  This catches
what interpret mode cannot: tiles not aligned to the chip's layout, more
VMEM than a kernel may use, and shapes Mosaic refuses.  Each compiled
program must hold the Mosaic kernel (`tpu_custom_call`).

Shapes: the paper widths (m=32 -> p=16 -> n=8, the `dr_paper` phase of
`chip_smoke.py`) and the wide DR shape (m=1024 -> p=256 -> n=64 in
1024-row buckets, its `dr_wide` phase), at the autotuner's smallest and
largest tiles.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.easi_update import easi_apply
from repro.kernels.fused_transform import fused_transform
from repro.kernels.ternary_matmul import ternary_matmul

F32, BF16, I8 = jnp.float32, jnp.bfloat16, jnp.int8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


# (rows, m, p, n, dtype, (block_m, block_p, block_k))
FUSED_SHAPES = {
    "paper_bucket1024": (1024, 32, 16, 8, F32, (128, 128, 512)),
    "paper_rows8_bf16": (8, 32, 16, 8, BF16, (128, 128, 512)),
    "wide_small_tiles": (1024, 1024, 256, 64, F32, (64, 128, 128)),
    "wide_default_tiles": (1024, 1024, 256, 64, F32, (128, 128, 512)),
    "wide_large_tiles": (1024, 1024, 256, 64, F32, (512, 256, 512)),
}


@pytest.mark.parametrize("name", sorted(FUSED_SHAPES))
def test_fused_transform_compiles(one_chip, name):
    rows, m, p, n, dtype, (bm, bp, bk) = FUSED_SHAPES[name]
    lowered = fused_transform.lower(
        _spec((rows, m), dtype, one_chip), _spec((p, m), I8, one_chip),
        _spec((n, p), dtype, one_chip), scale=0.5, block_m=bm, block_p=bp,
        block_k=bk, interpret=False)
    _assert_mosaic(lowered)


# (block rows b, n, m): y (b, n) folds into B (n, m)
EASI_SHAPES = {
    "paper": (256, 8, 16),
    "wide": (1024, 64, 256),
    "wide_full_easi": (4096, 256, 4096),
}


@pytest.mark.parametrize("name", sorted(EASI_SHAPES))
def test_easi_apply_compiles(one_chip, name):
    b, n, m = EASI_SHAPES[name]
    lowered = easi_apply.lower(
        _spec((n, m), F32, one_chip), _spec((b, n), F32, one_chip),
        mu=5e-4, second_order=False, higher_order=True, interpret=False)
    _assert_mosaic(lowered)


def test_ternary_matmul_compiles_at_paper_widths(one_chip):
    lowered = ternary_matmul.lower(
        _spec((1024, 32), F32, one_chip), _spec((16, 32), I8, one_chip),
        scale=0.5, interpret=False)
    _assert_mosaic(lowered)


# the held experts' grouped matmul at Moonlight-16B-A3B's widths: rows of
# (token, expert) pairs, 8 of 64 experts held, gate/up 2048 -> 1408 and
# down 1408 -> 2048; an 8,128-token prefill and a one-token decode step
GMM_SHAPES = {
    "prefill_up": (8128 * 6, 2048, 1408),
    "prefill_down": (8128 * 6, 1408, 2048),
    "decode_up": (6, 2048, 1408),
    "decode_down": (6, 1408, 2048),
}


@pytest.mark.parametrize("name", sorted(GMM_SHAPES))
def test_held_expert_grouped_matmul_compiles(one_chip, name, monkeypatch):
    from repro.core import execution
    from repro.models.blocks import grouped_matmul

    monkeypatch.setattr(execution, "_probe_interpret", lambda: False)
    rows, k, n = GMM_SHAPES[name]
    lowered = jax.jit(lambda x, w, g: grouped_matmul(x, w, g, 0, BF16)).lower(
        _spec((rows, k), BF16, one_chip), _spec((8, k, n), BF16, one_chip),
        _spec((64,), jnp.int32, one_chip))
    _assert_mosaic(lowered)
