"""Reproduce the paper's Table I (Waveform-V2 accuracy) + references.

Run:  PYTHONPATH=src python examples/waveform_repro.py \
          [--seeds 3] [--fast] [--backend xla|pallas]

Table rows are `repro.dr.DRModel` stage compositions (configs/waveform_paper);
`--backend pallas` reruns the whole protocol through the fused kernels via
the Execution policy — same numbers, different datapath.  Prints our
measured accuracy next to the paper's reported number for each row, plus
init-sensitivity ablations, a 3-stage cascade the old kind enum could not
express, and the ideal-PCA reference the paper doesn't report.  See
EXPERIMENTS.md §Paper-parity for the archived results and analysis.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import waveform_paper as wp
from repro.core import pipeline
from repro.core.execution import Execution
from repro.data import waveform
from repro.launch.compile_cache import use_compile_cache


def run_row(name: str, cfg, seeds, xtr, ytr, xte, yte, fast=False, execution=None):
    accs = []
    for seed in seeds:
        c = dataclasses.replace(cfg, seed=seed)
        if fast:
            c = dataclasses.replace(
                c, dr_epochs=max(1, c.dr_epochs // 4), head_epochs=15)
        model = pipeline.fit_two_stage(c, xtr, ytr, execution=execution)
        accs.append(pipeline.evaluate(model, xte, yte, execution=execution))
    return float(np.mean(accs)) * 100, float(np.std(accs)) * 100


def ideal_pca_reference(xtr, ytr, xte, yte, n, seed=0):
    """Closed-form PCA whitening to n dims — the information ceiling."""
    from repro.models import mlp

    x_dr, st = pipeline.center_global_scale(xtr)
    xte_dr, _ = pipeline.center_global_scale(xte, st)
    cov = np.asarray(x_dr.T @ x_dr / x_dr.shape[0])
    evals, evecs = np.linalg.eigh(cov)
    order = np.argsort(evals)[::-1][:n]
    w = jnp.asarray((evecs[:, order] / np.sqrt(evals[order])).T, jnp.float32)
    f_tr, f_te = x_dr @ w.T, xte_dr @ w.T
    f_tr_s, stats = pipeline.standardize(f_tr)
    f_te_s, _ = pipeline.standardize(f_te, stats)
    params = mlp.init(jax.random.PRNGKey(seed), n, (64, 64), 3)
    params = mlp.fit(params, f_tr_s, ytr, key=jax.random.PRNGKey(seed + 1))
    return mlp.accuracy(params, f_te_s, yte) * 100


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--fast", action="store_true", help="reduced epochs (CI smoke)")
    ap.add_argument("--skip-ablations", action="store_true")
    ap.add_argument("--backend", choices=("xla", "pallas"), default="xla",
                    help="execution backend for every DR stage")
    args = ap.parse_args()
    use_compile_cache()
    execution = Execution(backend=args.backend)

    (xtr, ytr), (xte, yte) = waveform.paper_split(seed=0)
    xtr, ytr, xte, yte = map(jnp.asarray, (xtr, ytr, xte, yte))
    seeds = list(range(args.seeds))

    print(f"Waveform-V2: train {xtr.shape} test {xte.shape} (paper protocol, "
          f"backend={args.backend})")
    print(f"{'row':26s} {'ours (mean±std %)':>20s} {'paper %':>8s}")
    rows = {}
    for name, cfg in wp.TABLE1_ROWS.items():
        mean, std = run_row(name, cfg, seeds, xtr, ytr, xte, yte, fast=args.fast,
                            execution=execution)
        rows[name] = mean
        print(f"{name:26s} {mean:13.1f} ± {std:4.1f} {wp.PAPER_TABLE1[name]:8.1f}")

    # The paper's core claim, init-matched: RP+EASI ≈ EASI at equal n.
    d16 = rows["rp24_easi_n16"] - rows["easi_n16"]
    d8 = rows["rp16_easi_n8"] - rows["easi_n8"]
    print(f"\nclaim check (init-matched): Δ(n=16) = {d16:+.1f}  Δ(n=8) = {d8:+.1f}  "
          f"(paper: −0.1 / −0.1)")

    if not args.skip_ablations:
        print("\nablations / references:")
        for name, cfg in {**wp.ABLATION_ROWS, **wp.CASCADE_ROWS}.items():
            mean, std = run_row(name, cfg, seeds[:1], xtr, ytr, xte, yte, fast=args.fast,
                                execution=execution)
            print(f"{name:26s} {mean:13.1f} ± {std:4.1f}      n/a")
        for n in (16, 8, 4):
            print(f"{'ideal_pca_n%d' % n:26s} {ideal_pca_reference(xtr, ytr, xte, yte, n):13.1f}          n/a")


if __name__ == "__main__":
    main()
