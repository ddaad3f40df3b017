"""Structural stand-in for the NCEP/NCAR Reanalysis 1 design of the
paper's Section 7.2, and responses over it.

The design comes from the system's own generator
(``repro.data.climate.make_climate_like``) at the configuration's grid.
Each response draws a fresh ground truth and noise by that generator's
law: ``n_active_regions`` groups, 3 of the ``n_vars`` variables in each
set to Unif[0.5, 2] times a random sign, y = X beta + noise * N(0, I),
then centred.
"""
from __future__ import annotations

import numpy as np


def make_design(gen: dict, seed: int):
    """Returns ``(X (n, p) float64, group size)``."""
    from repro.data.climate import make_climate_like

    X, _y, _beta, sizes = make_climate_like(**gen, seed=seed)
    return X, sizes[0]


def make_response(gen: dict, X: np.ndarray, ng: int, rng) -> np.ndarray:
    n, p = X.shape
    beta = np.zeros(p)
    for g in rng.choice(p // ng, size=gen["n_active_regions"], replace=False):
        vs = rng.choice(ng, size=3, replace=False)
        beta[g * ng + vs] = rng.uniform(0.5, 2.0, size=3) * np.sign(
            rng.uniform(-1, 1, size=3))
    y = X @ beta + gen["noise"] * rng.standard_normal(n)
    return y - y.mean()
