"""The paper's technique as an LM front-end: HuBERT-style audio encoder whose
input frames pass through an RP→EASI unit, co-trained (streaming,
unsupervised) inside the supervised train loop — the two-stage pipeline of
the paper fused into one pass.

Trains a reduced config for a few hundred steps on CPU and prints the loss
curve with/without the DR front-end plus the DR unit's whitening progress.

Run: PYTHONPATH=src python examples/lm_dr_frontend.py [--steps 120]
"""

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.core import easi
from repro.data import synthetic
from repro.launch.compile_cache import use_compile_cache
from repro.models.config import DRFrontendSpec
from repro.train import optimizer as opt_mod
from repro.train import train_step as ts_mod


def run(arch_cfg, steps, seed=0, tag=""):
    tcfg = ts_mod.TrainConfig(arch=arch_cfg, opt=opt_mod.AdamWConfig(lr=3e-4), seed=seed)
    state = ts_mod.init_state(jax.random.PRNGKey(seed), tcfg)
    data = synthetic.TokenStreamConfig(vocab_size=arch_cfg.vocab_size, seq_len=64,
                                       global_batch=8, seed=seed)

    def make_batch(step):
        b = synthetic.token_batch(data, step)
        frames = synthetic.feature_batch(
            arch_cfg.frontend_dim, data.global_batch * data.seq_len, step, seed=seed)
        b["frames"] = frames.reshape(data.global_batch, data.seq_len, arch_cfg.frontend_dim)
        b["tokens"] = b["tokens"] % arch_cfg.vocab_size
        return b

    from repro.launch.mesh import make_smoke_mesh
    mesh = make_smoke_mesh()
    with mesh:
        step_fn = ts_mod.make_train_step(tcfg, mesh, state, make_batch(0))
        losses = []
        for i in range(steps):
            state, metrics = step_fn(state, make_batch(i))
            losses.append(float(metrics["loss"]))
            if i % 20 == 0:
                extra = ""
                if state.dr is not None:
                    feats = make_batch(i)["frames"].reshape(-1, arch_cfg.frontend_dim)
                    from repro.core import dr_unit as dru
                    red = dru.transform(state.dr, ts_mod._dr_cfg(arch_cfg), feats[:2048])
                    extra = f"  DR whiteness KL={float(easi.whiteness_kl(red)):.3f}"
                print(f"[{tag}] step {i:4d} loss {losses[-1]:.4f}{extra}")
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    args = ap.parse_args()
    use_compile_cache()

    base = registry.get_smoke("hubert_xlarge")
    print(f"== baseline (frontend_dim={base.frontend_dim} -> d_model direct) ==")
    l0 = run(base, args.steps, tag="base")

    with_dr = dataclasses.replace(
        base, dr_frontend=DRFrontendSpec(kind="rp_easi", p=16, n=8, mu=2e-4))
    print(f"\n== with RP→EASI front-end ({base.frontend_dim} -> 16 -> 8) ==")
    l1 = run(with_dr, args.steps, tag="rp_easi")

    import numpy as np
    print(f"\nfinal-20-step mean loss: baseline {np.mean(l0[-20:]):.4f} "
          f"vs DR front-end {np.mean(l1[-20:]):.4f} "
          f"(frontend params {base.frontend_dim}×d vs {8}×d — {base.frontend_dim/8:.0f}× smaller)")


if __name__ == "__main__":
    main()
