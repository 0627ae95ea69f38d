"""Seeded synthetic jets: the pool every request of a run is cut from.

A copy of ``make_jets`` in ``src/repro/data/jets.py`` of this
repository (same classes, per-class subjet multiplicity, angular spread
and pT falloff, the same fixed 3 -> 16 embedding and global
standardization), with the per-jet Python loop replaced by array draws:
the same distribution, not the same numbers, made in bulk so that a
pool of tens of thousands of jets costs well under a second of set-up.
"""

from __future__ import annotations

import numpy as np

N_CLASSES = 5


def make_jets(rng: np.random.RandomState, n: int, n_particles: int,
              n_features: int = 16, noise: float = 0.25) -> np.ndarray:
    """(n, n_particles, n_features) float32 jets."""
    y = rng.randint(0, N_CLASSES, size=n)
    n_subjets = 1 + (y % 3)                       # 1..3 clusters
    spread = 0.1 + 0.15 * (y % 2)                 # angular spread
    softness = 0.5 + 0.25 * (y // 2)              # pT falloff

    centers = rng.normal(0, 1.0, size=(n, 3, 2))
    assign = (rng.random_sample((n, n_particles))
              * n_subjets[:, None]).astype(np.int64)
    ang = (centers[np.arange(n)[:, None], assign]
           + rng.normal(0, 1.0, (n, n_particles, 2)) * spread[:, None, None])
    pt = rng.exponential(1.0, (n, n_particles)) * softness[:, None]
    pt = -np.sort(-pt, axis=1)                    # leading particles first
    x3 = np.concatenate([np.log1p(pt)[..., None], ang], axis=-1)
    x3 = x3.astype(np.float32)

    emb_rng = np.random.RandomState(1234)         # fixed across calls
    w1 = emb_rng.normal(0, 1.0, (3, n_features)).astype(np.float32)
    w2 = emb_rng.normal(0, 0.5, (3, n_features)).astype(np.float32)
    x = np.tanh(x3 @ w1) + x3 @ w2
    x += rng.normal(0, noise, x.shape).astype(np.float32)
    x = (x - x.mean(axis=(0, 1), keepdims=True)) / (
        x.std(axis=(0, 1), keepdims=True) + 1e-6)
    return np.ascontiguousarray(x, dtype=np.float32)
