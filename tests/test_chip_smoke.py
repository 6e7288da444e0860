"""`chip_smoke.py` at smoke sizes on the CPU: its phases run the same code
as on the chip (Pallas in interpret mode here), and `main()` refuses to
run without a TPU."""

import dataclasses
import os
import pathlib
import subprocess
import sys

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from repro.configs import smollm_135m, waveform_paper  # noqa: E402
from repro.serve import BucketPolicy  # noqa: E402

DR_SMALL = {
    "dr_paper": dataclasses.replace(
        chip_smoke.DR_PAPER, buckets=BucketPolicy(min_bucket=8, max_bucket=64),
        n_requests=6, max_rows=40, update_rows=32, n_updates=2),
    "dr_wide": dataclasses.replace(
        chip_smoke.DR_WIDE, model=waveform_paper.rp_easi_model(256, 128, 16),
        buckets=BucketPolicy(min_bucket=64, max_bucket=64),
        n_requests=4, max_rows=40, update_rows=64, n_updates=2),
}
LM_SMALL = dataclasses.replace(chip_smoke.LM_SMOLLM, cfg=smollm_135m.SMOKE,
                               batch=2, prompt_len=16, cache_size=24,
                               decode_steps=3)


@pytest.mark.parametrize("name", sorted(DR_SMALL))
def test_dr_phase_matches_reference(name):
    case = DR_SMALL[name]
    report, svc = chip_smoke.dr_phase(case)
    for key in ("err_serve", "err_serve_and_update",
                "err_serve_after_promote"):
        assert 0.0 <= report[key] <= chip_smoke.TOL_DR_OUT, (key, report)
    for key in ("err_state_delta", "err_serve_promote_delta"):
        assert 0.0 <= report[key] <= chip_smoke.TOL_DR_DELTA, (key, report)
    assert report["autotunes"] >= 1
    assert svc.registry.get("dr").version == 1


def test_lm_phase_matches_reference():
    report = chip_smoke.lm_phase(LM_SMALL)
    assert report["err_prefill"] <= chip_smoke.TOL_LM_LOGITS
    assert report["err_decode"] <= chip_smoke.TOL_LM_LOGITS
    assert len(report["tokens"]) == LM_SMALL.batch
    assert all(len(t) == LM_SMALL.decode_steps for t in report["tokens"])


def test_check_raises_past_tolerance():
    with pytest.raises(AssertionError, match="relative error"):
        chip_smoke.check("x", [jax.numpy.ones(3) * 1.1], [jax.numpy.ones(3)],
                         1e-2)


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


SHARDED_SCRIPT = r"""
import dataclasses, sys
sys.path.insert(0, ".")
import jax
import chip_smoke
from repro.configs import smollm_135m
from repro.serve import BucketPolicy

devices = jax.devices()
assert len(devices) == 4, devices
dr = dataclasses.replace(chip_smoke.DR_PAPER,
                         buckets=BucketPolicy(min_bucket=8, max_bucket=64),
                         n_requests=6, max_rows=40)
print(chip_smoke.dr_sharded_phase(dr, devices))
lm = dataclasses.replace(chip_smoke.LM_SMOLLM, cfg=smollm_135m.SMOKE,
                         batch=4, prompt_len=16, cache_size=24, decode_steps=2)
print(chip_smoke.lm_sharded_phase(lm, devices))
print("SHARDED_OK")
"""


def test_sharded_phases_on_four_cpu_devices():
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, cwd=str(REPO),
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_OK" in out.stdout
