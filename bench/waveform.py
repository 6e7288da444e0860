"""Waveform-V2 rows, the paper's data (Nazemi et al., arXiv:1801.04014, §V).

Copied from `repro.data.waveform.generate` (Breiman et al. 1984, UCI
"Waveform Database Generator (Version 2)"), with the preprocessing of the
paper pipeline (`repro.core.pipeline.center_global_scale`: centre, then one
global scale so the mean per-feature variance is 1), so that a change to
the program's copies cannot change the benchmark's traffic.

  * 3 triangular base waves on t = 1..21 (peaks at 7, 15, 11; height 6)
  * class c mixes two of them with u ~ U(0, 1)
  * N(0, 1) noise on all 21 attributes, then 19 pure-noise attributes
  * the paper keeps the first 32 of the 40 features
"""

from __future__ import annotations

import numpy as np

N_WAVE = 21
N_NOISE = 19
N_TOTAL = N_WAVE + N_NOISE
_MIX = np.array([(0, 1), (0, 2), (1, 2)])


def _base_waves() -> np.ndarray:
    t = np.arange(1, N_WAVE + 1, dtype=np.float64)
    return np.stack([np.maximum(6.0 - np.abs(t - c), 0.0)
                     for c in (7.0, 15.0, 11.0)])


def generate(rng: np.random.Generator, n_rows: int,
             n_features: int) -> np.ndarray:
    """(n_rows, n_features) float64 Waveform-V2 rows."""
    waves = _base_waves()
    cls = rng.integers(0, 3, size=n_rows)
    u = rng.uniform(0.0, 1.0, size=(n_rows, 1))
    a, b = _MIX[cls, 0], _MIX[cls, 1]
    clean = u * waves[a] + (1.0 - u) * waves[b]
    noise = rng.standard_normal((n_rows, N_TOTAL))
    x = np.concatenate([clean, np.zeros((n_rows, N_NOISE))], axis=1) + noise
    return x[:, :n_features]


def centre_global_scale(x: np.ndarray) -> np.ndarray:
    """Centre each feature, then divide by one scalar: the square root of
    the mean per-feature variance."""
    xc = x - x.mean(axis=0)
    return xc / (np.sqrt(np.mean(xc.var(axis=0))) + 1e-8)


def pool(rng: np.random.Generator, n_rows: int,
         n_features: int) -> np.ndarray:
    """Preprocessed float32 rows, the payloads requests are cut from."""
    return centre_global_scale(generate(rng, n_rows, n_features)).astype(
        np.float32)
