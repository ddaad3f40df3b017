"""The paper's Section 7.1 synthetic design, and responses over it.

The design comes from the system's own generator
(``repro.data.synthetic.make_synthetic``) at the configuration's sizes.
Each response draws a fresh ground truth and noise by that generator's
law: ``gamma1`` active groups, ``gamma2`` coordinates in each set to
sign(xi) U with U ~ Unif[0.5, 10] and xi ~ Unif[-1, 1], and
y = X beta + noise * N(0, I).
"""
from __future__ import annotations

import numpy as np


def make_design(gen: dict, seed: int):
    """Returns ``(X (n, p) float64, group size)``."""
    from repro.data.synthetic import make_synthetic

    X, _y, _beta, sizes = make_synthetic(**gen, seed=seed)
    return X, sizes[0]


def make_response(gen: dict, X: np.ndarray, ng: int, rng) -> np.ndarray:
    n, p = X.shape
    beta = np.zeros(p)
    for g in rng.choice(p // ng, size=gen["gamma1"], replace=False):
        coords = rng.choice(ng, size=min(gen["gamma2"], ng), replace=False)
        u = rng.uniform(0.5, 10.0, size=len(coords))
        s = np.sign(rng.uniform(-1.0, 1.0, size=len(coords)))
        beta[g * ng + coords] = s * u
    return X @ beta + gen["noise"] * rng.standard_normal(n)
