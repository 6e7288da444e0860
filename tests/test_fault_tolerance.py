"""Fault tolerance: checkpoint atomicity, auto-resume determinism, corruption
quarantine, straggler watchdog, elastic restore (different device count)."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, leaf_hash
from repro.configs import registry
from repro.train import optimizer as opt_mod
from repro.train import train_step as ts_mod
from repro.train import trainer as trainer_mod


def _trainer_cfg(tmpdir, total_steps=6, ckpt_every=3, arch_id="smollm_135m"):
    arch = registry.get_smoke(arch_id)
    tcfg = ts_mod.TrainConfig(arch=arch, opt=opt_mod.AdamWConfig(lr=1e-3), seed=0)
    return trainer_mod.TrainerConfig(
        train=tcfg, total_steps=total_steps, ckpt_dir=str(tmpdir),
        ckpt_every=ckpt_every, log_every=100)


class TestCheckpointManager:
    def test_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        state = {"a": jnp.arange(10, dtype=jnp.float32), "b": {"c": jnp.ones((3, 4))}}
        mgr.save(5, state)
        step, restored = mgr.restore(jax.tree.map(jnp.zeros_like, state))
        assert step == 5
        for x, y in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_keep_n_gc(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_n=2, async_save=False)
        state = {"x": jnp.zeros((4,))}
        for s in (1, 2, 3, 4):
            mgr.save(s, state)
        assert mgr.steps() == [3, 4]

    def test_corruption_quarantine(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        state = {"x": jnp.arange(4, dtype=jnp.float32)}
        mgr.save(1, state)
        mgr.save(2, state)
        # corrupt the newest checkpoint
        with open(os.path.join(str(tmp_path), "step_00000002", "manifest.json"), "w") as f:
            f.write("{broken")
        step, restored = mgr.restore(jax.tree.map(jnp.zeros_like, state))
        assert step == 1  # fell back
        assert any(n.endswith(".corrupt") for n in os.listdir(str(tmp_path)))

    def test_partial_tmp_cleaned(self, tmp_path):
        os.makedirs(os.path.join(str(tmp_path), "tmp_step_00000009"))
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        assert not any(n.startswith("tmp_") for n in os.listdir(str(tmp_path)))

    def test_async_save_blocks_on_wait(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_save=True)
        state = {"x": jnp.arange(1000, dtype=jnp.float32)}
        mgr.save(7, state)
        mgr.wait()
        assert mgr.latest_step() == 7

    def test_manifest_records_leaf_hashes(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        state = {"x": jnp.arange(64, dtype=jnp.float32)}
        mgr.save(1, state)
        with open(os.path.join(str(tmp_path), "step_00000001",
                               "manifest.json")) as f:
            manifest = json.load(f)
        entry = manifest["leaves"][0]
        arr = np.load(os.path.join(str(tmp_path), "step_00000001",
                                   entry["file"]))
        assert entry["sha256"] == leaf_hash(arr)

    def test_flipped_leaf_byte_quarantines_and_falls_back(self, tmp_path):
        """SILENT corruption: one flipped bit in a leaf's data still
        np.loads fine and has the right shape — only the per-leaf sha256
        catches it.  Restore must quarantine and fall back, never serve
        the corrupt bytes."""
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        good = {"x": jnp.arange(64, dtype=jnp.float32)}
        bad_src = {"x": jnp.arange(64, dtype=jnp.float32) * 2.0}
        mgr.save(1, good)
        mgr.save(2, bad_src)
        leaf = os.path.join(str(tmp_path), "step_00000002", "leaf_00000.npy")
        with open(leaf, "r+b") as f:
            f.seek(-1, os.SEEK_END)                 # last data byte
            b = f.read(1)
            f.seek(-1, os.SEEK_END)
            f.write(bytes([b[0] ^ 0x01]))
        step, restored = mgr.restore(jax.tree.map(jnp.zeros_like, good))
        assert step == 1                            # fell back past step 2
        assert any(n == "step_00000002.corrupt"
                   for n in os.listdir(str(tmp_path)))
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(good["x"]))

    def test_pre_hash_manifest_still_restores(self, tmp_path):
        """Manifests written before the sha256 field existed restore
        without verification instead of failing."""
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        state = {"x": jnp.arange(8, dtype=jnp.float32)}
        mgr.save(3, state)
        mpath = os.path.join(str(tmp_path), "step_00000003", "manifest.json")
        with open(mpath) as f:
            manifest = json.load(f)
        for entry in manifest["leaves"]:
            del entry["sha256"]
        with open(mpath, "w") as f:
            json.dump(manifest, f)
        step, restored = mgr.restore(jax.tree.map(jnp.zeros_like, state))
        assert step == 3
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(state["x"]))


class TestResume:
    def test_interrupted_run_matches_uninterrupted(self, tmp_path):
        """Crash-after-3-steps + resume == straight 6-step run (CPU bitwise)."""
        d1, d2 = tmp_path / "a", tmp_path / "b"
        # uninterrupted
        res_full = trainer_mod.train(_trainer_cfg(d1, total_steps=6), log=lambda s: None)
        # interrupted: run 3, then "restart" and run to 6
        cfg_short = dataclasses.replace(_trainer_cfg(d2, total_steps=6), total_steps=3)
        trainer_mod.train(cfg_short, log=lambda s: None)
        res_resumed = trainer_mod.train(_trainer_cfg(d2, total_steps=6), log=lambda s: None)

        for x, y in zip(jax.tree.leaves(res_full["state"].params),
                        jax.tree.leaves(res_resumed["state"].params)):
            np.testing.assert_allclose(np.asarray(x, np.float32), np.asarray(y, np.float32),
                                       rtol=0, atol=0)

    def test_loss_decreases(self, tmp_path):
        res = trainer_mod.train(_trainer_cfg(tmp_path, total_steps=12, ckpt_every=20),
                                log=lambda s: None)
        assert np.mean(res["losses"][-3:]) < np.mean(res["losses"][:3])


class TestWatchdog:
    def test_flags_outlier(self):
        wd = trainer_mod.StragglerWatchdog(factor=3.0, min_steps=3)
        for i in range(6):
            assert not wd.observe(i, 0.1)
        assert wd.observe(6, 1.0)  # 10x EMA
        assert wd.events and wd.events[0][0] == 6

    def test_no_flag_on_gradual_drift(self):
        wd = trainer_mod.StragglerWatchdog(factor=3.0, min_steps=3)
        t = 0.1
        for i in range(20):
            t *= 1.1
            assert not wd.observe(i, t)


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
import jax, jax.numpy as jnp, numpy as np, sys
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.checkpoint import CheckpointManager

mgr = CheckpointManager(r"{d}", async_save=False)
state = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
if "{mode}" == "save":
    mesh = jax.make_mesh(({n},), ("data",))
    sh = NamedSharding(mesh, P("data", None))
    state = {{"w": jax.device_put(state["w"], sh)}}
    mgr.save(1, state)
else:
    mesh = jax.make_mesh(({n},), ("data",))
    sh = {{"w": NamedSharding(mesh, P(None, "data"))}}
    step, restored = mgr.restore(jax.tree.map(jnp.zeros_like, state), shardings=sh)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(64, dtype=np.float32).reshape(8, 8))
    print("ELASTIC_OK")
"""


@pytest.mark.slow
def test_elastic_restore_across_device_counts(tmp_path):
    """Save sharded over 4 devices, restore sharded (differently) over 8."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    for mode, n in (("save", 4), ("load", 8)):
        script = ELASTIC_SCRIPT.format(n=n, d=str(tmp_path / "ck"), mode=mode)
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr[-2000:]
    assert "ELASTIC_OK" in out.stdout
