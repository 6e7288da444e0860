"""Distribution-layer tests: sharding rules, RP gradient compression,
shard_map MoE parity, roofline HLO analyzer."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import compress, sharding
from repro.launch import roofline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestShardingRules:
    def _mesh(self):
        return jax.make_mesh((1, 1), ("data", "model"))

    def test_param_spec_degrades_on_indivisible(self):
        mesh = self._mesh()  # sizes 1 -> everything divisible but size-1 axes
        spec = sharding.param_spec("['layers']['wq']", (30, 577, 9 * 64), mesh)
        assert len(spec) == 3

    def test_expert_weights_pin_model(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        spec = sharding.param_spec("['layers']['w_in']", (32, 16, 4096, 6400), mesh)
        assert spec[0] is None  # stacked layer dim never sharded

    def test_constrain_noop_without_mesh(self):
        x = jnp.ones((8, 8))
        y = sharding.constrain(x, "data", None)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestCompressionMath:
    def test_sketch_unbiased_single_shard(self):
        """E[backproject(sketch(g))] = g: check the mean over many R draws."""
        cfg = compress.CompressConfig(ratio=4, chunk=256, min_size=0)
        g = jax.random.normal(jax.random.PRNGKey(0), (256,), jnp.float32)
        est = jnp.zeros_like(g)
        n = 200
        for i in range(n):
            r = compress._rp_matrix(jax.random.PRNGKey(i + 1), 64, 256, 64)
            y = g @ r.T
            est = est + (y @ r) * (64 / 64) * (64 / 64)
        # unbiased back-projection: scale s/p with s=p=64 -> 1; average ≈ g
        est = est / n
        corr = float(jnp.dot(est, g) / (jnp.linalg.norm(est) * jnp.linalg.norm(g)))
        assert corr > 0.9, corr

    def test_bytes_accounting(self):
        cfg = compress.CompressConfig(ratio=4, chunk=4096, min_size=1024)
        grads = {"w": jnp.zeros((1024, 1024)), "b": jnp.zeros((8,))}
        acc = compress.collective_bytes_saved(grads, cfg)
        assert 3.5 < acc["ratio"] < 4.5


COMPRESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.dist import compress

mesh = jax.make_mesh((8,), ("data",))
cfg = compress.CompressConfig(ratio=4, chunk=1024, min_size=0)

g_local = jax.random.normal(jax.random.PRNGKey(0), (8, 4096), jnp.float32)

def sync(g, ef):
    out, ef2 = compress.compress_sync({"g": g}, {"g": ef}, cfg, ("data",))
    return out["g"], ef2["g"]

f = jax.jit(jax.shard_map(sync, mesh=mesh, in_specs=(P("data"), P("data")),
                          out_specs=(P("data"), P("data")), check_vma=False))
g_in = g_local.reshape(8, 1, 4096)  # one row per shard
ef0 = jnp.zeros_like(g_in)
out, ef = f(g_in, ef0)
out = np.asarray(out)
# every shard must hold the SAME synced gradient (approximately the mean)
for i in range(1, 8):
    np.testing.assert_allclose(out[0], out[i], rtol=1e-5, atol=1e-6)
true_mean = np.asarray(g_local).mean(axis=0)
est = out[0, 0]
corr = float(np.dot(est, true_mean) / (np.linalg.norm(est) * np.linalg.norm(true_mean) + 1e-9))
assert corr > 0.3, corr  # ratio-4 sketch of white noise: corr ~ sqrt(p/c) ~ 0.5, noisy
# error feedback holds the residual
resid = np.asarray(ef)[0, 0]
np.testing.assert_allclose(resid, np.asarray(g_local)[0] - est, rtol=1e-4, atol=1e-5)
print("COMPRESS_OK corr=%.3f" % corr)
"""


@pytest.mark.slow
def test_compressed_allreduce_8dev():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", COMPRESS_SCRIPT], env=env,
                         capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "COMPRESS_OK" in out.stdout


MOE_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models import blocks
from repro.models.config import MoESpec

d, e, f, t, k = 16, 4, 32, 128, 2
spec = MoESpec(n_experts=e, top_k=k, d_ff_expert=f, capacity_factor=float(e))
ks = jax.random.split(jax.random.PRNGKey(0), 5)
params = {
    "router": jax.random.normal(ks[0], (d, e), jnp.float32) * 0.1,
    "w_in": jax.random.normal(ks[1], (e, d, f), jnp.float32) / np.sqrt(d),
    "w_gate": jax.random.normal(ks[2], (e, d, f), jnp.float32) / np.sqrt(d),
    "w_out": jax.random.normal(ks[3], (e, f, d), jnp.float32) / np.sqrt(f),
}
x = jax.random.normal(ks[4], (2, t // 2, d), jnp.float32)  # (B, S, d)

# single-device reference (plain path)
y_ref, aux_ref = blocks.moe_layer(params, x, spec, "silu")

# sharded path: mesh (2 data x 4 model) -> a2a block over the 3-D stream
mesh = jax.make_mesh((2, 4), ("data", "model"))
xs = jax.device_put(x, NamedSharding(mesh, P("data", "model", None)))
ps = jax.tree.map(lambda a: jax.device_put(a, NamedSharding(mesh, P())), params)
with mesh:
    y_sh, aux_sh = jax.jit(lambda p, xx: blocks.moe_layer(p, xx, spec, "silu"))(ps, xs)
np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_sh), rtol=2e-4, atol=2e-5)
print("MOE_PARITY_OK lb=%.3f" % float(aux_sh["moe_lb"]))
"""


@pytest.mark.slow
def test_moe_shard_map_parity_8dev():
    """a2a expert-parallel MoE == single-device math (capacity high enough
    that neither path drops tokens)."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", MOE_PARITY_SCRIPT], env=env,
                         capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MOE_PARITY_OK" in out.stdout


class TestHloAnalyzer:
    def test_trip_count_scaling(self):
        """Analyzer flops must scale with scan length; result checked against
        the exact dot count of the loop body."""
        def f(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), None
            out, _ = jax.lax.scan(body, x, None, length=7)
            return out.sum()

        x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        txt = jax.jit(f).lower(x, w).compile().as_text()
        r = roofline.analyze_hlo(txt, 1)
        expected = 7 * 2 * 64 * 128 * 128
        assert abs(r["flops"] - expected) / expected < 0.05, (r["flops"], expected)

    def test_collectives_inside_loop_counted_per_trip(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = jax.make_mesh((1,), ("data",))

        def f(x):
            def body(c, _):
                s = jax.lax.with_sharding_constraint(c, P(None))
                return s * 1.00001, None
            out, _ = jax.lax.scan(body, x, None, length=5)
            return out

        # single-device: no collectives expected — just exercise the parser
        x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
        with mesh:
            txt = jax.jit(f).lower(x).compile().as_text()
        r = roofline.analyze_hlo(txt, 1)
        assert r["flops"] >= 0.0
        assert r["bytes"] > 0.0
