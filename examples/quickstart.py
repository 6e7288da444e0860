"""Quickstart: the paper's technique in 30 lines.

Composes the reconfigurable DR datapath from first-class stages (random
projection -> rotation-only EASI), trains it unsupervised on a synthetic
16-dim mixture of 4 independent sources, and shows that the learned 4-dim
representation separates sources (Amari distance) at half the
adaptive-stage cost of full-width EASI.

Run: PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro.core import easi
from repro.data import mixtures
from repro.dr import DRModel, EASIStage, RPStage
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()

# 1. data: x = A s, 16 observed dims, 4 independent non-Gaussian sources
x, a_true, _ = mixtures.mixture(n_samples=30000, m=16, n_src=4, seed=0,
                                kinds=["uniform", "bimodal", "sine"])
x = jnp.asarray(x)

# 2. compose the datapath: RP 16->8 (static ternary), EASI 8->4.
#    EASIStage.full keeps Eq. 6's second-order term — the adaptive stage
#    still runs at HALF the width (p=8 not m=16), which is where the
#    paper's resource saving lives.  (EASIStage.rotation would be the
#    paper's bypassed variant; any deeper cascade chains the same way.)
model = DRModel(stages=(RPStage(16, 8), EASIStage.full(8, 4, mu=1e-3)),
                block_size=32)
state = model.init(jax.random.PRNGKey(0))
print(f"RP matrix: int8 {state.r.shape}, {float((state.r != 0).mean()):.3f} dense")
full_width = DRModel(stages=(EASIStage.full(16, 4),))
print(f"EASI stage: {state.b.shape} (vs {(4, 16)} for full-width EASI -> "
      f"{model.mac_counts()['easi_macs']:.0f} MACs/sample vs "
      f"{full_width.mac_counts()['easi_macs']:.0f})")

# 3. unsupervised streaming fit (the paper's training phase)
state = model.fit(state, x, epochs=10)

# 4. deploy: transform new data (the paper's inference phase)
y = model.transform(state, x)
print(f"reduced features: {y.shape}, whiteness KL = {float(easi.whiteness_kl(y)):.3f}")

# 5. quality: the effective separator B·(scale·R) should invert the mixing
rp_cfg = model.stages[0].rp_cfg(model.execution)
r_eff = state.r.astype(jnp.float32) * rp_cfg.scale
w_eff = state.b @ r_eff
print(f"Amari distance to true mixing: {float(easi.amari_distance(w_eff, jnp.asarray(a_true))):.4f} "
      f"(0 = perfect, random ≈ 0.4)")

# 6. scale-out teaser: train 4 independent models in ONE vmapped pass
ens = model.ensemble(4)
est = ens.fit(ens.init(jax.random.PRNGKey(1)), x, epochs=10)
dists = [float(easi.amari_distance(est.stages[1][i] @ (est.stages[0][i].astype(jnp.float32) * rp_cfg.scale),
                                   jnp.asarray(a_true))) for i in range(4)]
print(f"ensemble(4) Amari distances: {['%.3f' % d for d in dists]}")
