"""Operations and bytes from shapes, against counts made by hand, and the
roofline share and peaks built on them."""

from __future__ import annotations

import json
import pathlib

import pytest

from bench import costs, peaks
from bench.metrics import readers

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    with open(CONFIGS / f"{name}.json") as f:
        return json.load(f)


def test_a_served_row_costs_1280_flops_at_paper_widths():
    c = _cfg("dr_paper")
    # x (32) . R^T (32x16) = 2*32*16 = 1024;  h (16) . B^T (16x8) = 256
    assert costs.dr_transform_flops(1, c["m"], c["p"], c["n"]) == 1280
    assert costs.dr_transform_flops(1024, 32, 16, 8) == 1024 * 1280


def test_transform_bytes_move_rows_once_and_weights_once_per_call():
    # 10 rows: 10*(32*4 in + 8*4 out) = 1600; 2 calls: 2*(16*32 + 8*16*4)
    # = 2*(512 + 512)
    assert costs.dr_transform_bytes(10, 2, 32, 16, 8) == 1600 + 2 * 1024


def test_easi_rotation_update_flops_by_hand():
    # rotation only, b=256, n=8, p=16:
    #   cube 2*256*8 = 4096; H = g(y)^T y 2*256*64 = 32768; H - H^T 64
    #   G B 2*64*16 = 2048; B - mu G B 2*8*16 = 256
    assert costs.easi_update_flops(256, 8, 16, False, True) == \
        4096 + 32768 + 64 + 2048 + 256
    assert costs.easi_update_bytes(256, 8, 16) == 256 * 8 * 4 + 2 * 8 * 16 * 4


def test_smollm_parameter_count_is_the_published_135m():
    # 30 layers x (q 576*576 + k,v 2*576*192 + o 576*576 + MLP 3*576*1536
    # + 2 norms of 576) + tied embedding 49152*576 + final norm 576
    assert costs.lm_param_count(_cfg("smollm_135m")) == 134_515_008


def test_smollm_decode_step_flops_by_hand():
    c = _cfg("smollm_135m")
    per_layer = 576 * 576 + 2 * 576 * 192 + 576 * 576 + 3 * 576 * 1536
    assert per_layer == 3_538_944
    ctx = 1024
    want = (30 * (2 * per_layer + 4 * 9 * 64 * ctx) + 2 * 576 * 49152)
    assert want == 339_738_624
    assert costs.lm_decode_flops(c, ctx) == want


def test_roofline_share_takes_the_larger_of_the_two_bounds():
    ctx = {"device_kind": "TPU v5 lite"}
    # 197e12 FLOP in 2 s: compute-bound at 1 s, so 50%
    assert readers.roofline_share(ctx, 197e12, 1.0, 2.0) == pytest.approx(50)
    # 819e9 bytes in 4 s: memory-bound at 1 s, so 25%
    assert readers.roofline_share(ctx, 1.0, 819e9, 4.0) == pytest.approx(25)
    assert readers.roofline_share(ctx, 0.0, 0.0, 1.0) is None


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_raise():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
