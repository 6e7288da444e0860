"""`repro.launch` plumbing: Auto-axis meshes, the compile-cache directory,
and the per-device-kind peak table."""

import os
import pathlib
import subprocess
import sys

import jax
import pytest
from jax.sharding import AxisType

from repro.launch import compile_cache, roofline
from repro.launch.mesh import make_mesh, make_smoke_mesh, require_auto_axes

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_meshes_have_auto_axes():
    for mesh in (make_mesh((1, 1), ("data", "model")), make_smoke_mesh(1)):
        assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
        require_auto_axes(mesh)
    with pytest.raises(ValueError, match="AxisType.Auto"):
        require_auto_axes(jax.make_mesh((1, 1), ("data", "model")))


CACHE_SCRIPT = r"""
import os, sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
d = use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((32, 32))))
print(d)
print(jax.config.jax_compilation_cache_dir)
print(len(os.listdir(d)))
"""


@pytest.mark.parametrize("env_set", [True, False], ids=["env_set", "env_unset"])
def test_compile_cache_directory(tmp_path, env_set):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env.update(PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    want = str(compile_cache.CHECKOUT_CACHE_DIR)
    if env_set:
        want = str(tmp_path / "jax_cache")
        env[compile_cache.ENV_VAR] = want
    out = subprocess.run([sys.executable, "-c", CACHE_SCRIPT], env=env,
                         capture_output=True, text=True, cwd=str(tmp_path),
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    returned, configured, entries = out.stdout.split()[-3:]
    assert returned == configured == want
    assert int(entries) > 0
    if not env_set:
        assert pathlib.Path(want).parent == REPO


def test_device_peak_flops_known_and_unknown_kinds():
    peak, source = roofline.device_peak_flops("TPU v5 lite")
    assert peak == 197e12 and "v5e" in source
    assert roofline.DEVICE_PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peak"):
        roofline.device_peak_flops("cpu")
