"""Sparse-Group Lasso + Elastic Net (paper Appendix D).

    min_beta 1/2 ||y - X beta||^2 + lam1 * Omega_{tau,w}(beta)
             + lam2/2 ||beta||^2

is exactly the plain SGL problem on the augmented design

    X~ = [X; sqrt(lam2) I_p],  y~ = [y; 0],

so the whole GAP-safe machinery (screening, epsilon-norm dual evaluation,
ISTA-BC) applies unchanged — including the safety certificates, which now
hold for the elastic-net objective.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from .sgl import SGLProblem, make_problem
from .precision import one_minus

__all__ = ["make_elastic_problem", "elastic_objective"]


def make_elastic_problem(
    X_flat,
    y,
    group_sizes,
    tau: float,
    lam2: float,
    w=None,
) -> SGLProblem:
    """SGL+ridge as an augmented plain-SGL problem (Appendix D, Eq. 38)."""
    X_flat = np.asarray(X_flat)
    y = np.asarray(y)
    n, p = X_flat.shape
    X_aug = np.concatenate(
        [X_flat, np.sqrt(lam2) * np.eye(p, dtype=X_flat.dtype)], axis=0
    )
    y_aug = np.concatenate([y, np.zeros(p, y.dtype)])
    return make_problem(X_aug, y_aug, group_sizes, tau=tau, w=w)


def elastic_objective(X_flat, y, beta_flat, tau, w, lam1, lam2, group_sizes):
    """Direct evaluation of the Appendix-D objective (for tests)."""
    X_flat = jnp.asarray(X_flat)
    beta_flat = jnp.asarray(beta_flat)
    resid = jnp.asarray(y) - X_flat @ beta_flat
    fit = 0.5 * jnp.sum(resid * resid)
    l1 = jnp.sum(jnp.abs(beta_flat))
    l2g = 0.0
    off = 0
    for g, s in enumerate(group_sizes):
        l2g = l2g + w[g] * jnp.linalg.norm(beta_flat[off:off + s])
        off += s
    ridge = 0.5 * lam2 * jnp.sum(beta_flat * beta_flat)
    return fit + lam1 * (tau * l1 + one_minus(tau) * l2g) + ridge
