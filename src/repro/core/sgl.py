"""Sparse-Group Lasso problem definition (paper Sections 3 and 5).

Primal (Eq. 5):   P(beta) = 1/2 ||y - X beta||^2 + lambda Omega_{tau,w}(beta)
Norm  (Eq. 10):   Omega_{tau,w}(beta) = tau ||beta||_1
                                        + (1 - tau) sum_g w_g ||beta_g||
Dual  (Eq. 6):    D(theta) = 1/2 ||y||^2 - lambda^2/2 ||theta - y/lambda||^2
                  over  Delta = {theta : Omega^D(X^T theta) <= 1}.

Group representation
--------------------
Groups are a partition of [p].  The in-memory layout is *grouped*: the design
matrix is carried as ``X`` of shape ``(n, G, ng)`` (groups zero-padded to the
max group size) and coefficients as ``beta`` of shape ``(G, ng)``.  A boolean
``feat_mask`` of shape (G, ng) marks real features.  This makes every
group-level quantity a reduction over the trailing axis — the layout XLA/TPU
wants — and exactly matches the paper's experiments (equal-size groups of 10
and 7).  ``flatten``/``unflatten`` convert to the flat (p,) view.

Everything here but :func:`make_problem`, which is set-up and waits for its
outputs, is pure and jit-compatible.
"""
from __future__ import annotations

import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from .epsilon_norm import lam
from .precision import one_minus

__all__ = [
    "SGLProblem",
    "make_problem",
    "problem_from_grouped",
    "flatten",
    "unflatten",
    "sgl_norm",
    "sgl_dual_norm",
    "sgl_dual_norm_terms",
    "primal",
    "dual",
    "duality_gap",
    "dual_scale",
    "lambda_max",
    "primal_loss",
    "dual_loss",
    "duality_gap_loss",
    "dual_scale_loss",
    "lambda_max_loss",
    "multitask_norm",
    "multitask_dual_norm_terms",
    "multitask_dual_norm",
    "multitask_primal",
    "multitask_dual",
    "multitask_duality_gap",
    "multitask_dual_scale",
    "multitask_lambda_max",
    "multitask_group_screen",
    "soft_threshold",
    "group_soft_threshold",
    "sgl_prox",
    "epsilons",
    "group_weight_total",
]


class SGLProblem(NamedTuple):
    """Static data of one SGL instance, in grouped layout."""

    X: jax.Array          # (n, G, ng) zero-padded design matrix
    y: jax.Array          # (n,)
    w: jax.Array          # (G,) group weights (paper: w_g = sqrt(n_g))
    tau: jax.Array        # scalar in [0, 1]
    feat_mask: jax.Array  # (G, ng) bool, True for real features
    Lg: jax.Array         # (G,) block Lipschitz constants ||X_g||_2^2
    Xnorm_col: jax.Array  # (G, ng) column norms ||X_j||
    Xnorm_grp: jax.Array  # (G,) spectral norms ||X_g||_2

    @property
    def n(self) -> int:
        return self.X.shape[0]

    @property
    def G(self) -> int:
        return self.X.shape[1]

    @property
    def ng(self) -> int:
        return self.X.shape[2]


_M_MAKE_PROBLEM_S = obs_metrics.REGISTRY.gauge(
    "sgl.make_problem_s",
    help="Seconds the last make_problem took, from entry until its outputs "
         "were ready on the device")


def _group_spectral_norms(Xg: jax.Array, n_iter: int = 50) -> jax.Array:
    """||X_g||_2 for each group via power iteration on X_g^T X_g.

    Xg: (n, G, ng) -> (G,).  Deterministic start vector (ones) is fine for
    PSD Gram matrices (converges to top eigenpair unless orthogonal start,
    which the added tiny perturbation avoids).
    """
    G, ng = Xg.shape[1], Xg.shape[2]
    gram = jnp.einsum("nga,ngb->gab", Xg, Xg)  # (G, ng, ng)

    v0 = jnp.ones((G, ng), gram.dtype)
    v0 = v0 + 1e-3 * jnp.arange(ng, dtype=gram.dtype)[None, :]
    v0 = v0 / jnp.linalg.norm(v0, axis=-1, keepdims=True)

    def body(_, v):
        u = jnp.einsum("gab,gb->ga", gram, v)
        nrm = jnp.linalg.norm(u, axis=-1, keepdims=True)
        return u / jnp.maximum(nrm, 1e-30)

    v = jax.lax.fori_loop(0, n_iter, body, v0)
    ev = jnp.einsum("ga,gab,gb->g", v, gram, v)
    return jnp.maximum(ev, 0.0)  # == ||X_g||_2^2 estimate's eigenvalue


def make_problem(
    X_flat: jax.Array,
    y: jax.Array,
    group_sizes,
    tau: float,
    w=None,
) -> SGLProblem:
    """Build an :class:`SGLProblem` from a flat (n, p) design matrix.

    ``group_sizes``: python sequence of ints summing to p (contiguous groups).
    ``w``: group weights; defaults to sqrt(n_g) (paper Section 7.1).

    The grouped design is one device computation: one gather of a (G, ng)
    column table, with the padding slots of smaller groups zeroed.  Returns
    once every output is ready, inside a ``make_problem`` span, and sets
    the ``sgl.make_problem_s`` gauge to the seconds that took.
    """
    t0 = time.perf_counter()
    X_flat = jnp.asarray(X_flat)
    y = jnp.asarray(y, X_flat.dtype)
    sizes = np.asarray([int(s) for s in group_sizes], np.int64)
    n, p = X_flat.shape
    if sizes.sum() != p:
        raise ValueError(f"group sizes sum to {sizes.sum()}, not p={p}")
    G = sizes.size
    ng = int(sizes.max())
    with obs_trace.span("make_problem") as sp:
        sp.set("n", n).set("p", p).set("G", G).set("ng", ng)
        sp.set("bytes", n * G * ng * X_flat.dtype.itemsize)
        slot = np.arange(ng)
        mask_np = slot[None, :] < sizes[:, None]
        cols = np.where(mask_np, (np.cumsum(sizes) - sizes)[:, None] + slot, 0)
        mask = jnp.asarray(mask_np)
        Xg = jnp.where(mask, X_flat[:, cols], jnp.zeros((), X_flat.dtype))

        if w is None:
            w = jnp.sqrt(jnp.asarray(sizes, X_flat.dtype))
        else:
            w = jnp.asarray(w, X_flat.dtype)

        Lg = _group_spectral_norms(Xg)
        # Padded groups/columns: keep Lg > 0 guard at use sites.
        col = jnp.linalg.norm(Xg, axis=0)  # (G, ng)
        problem = jax.block_until_ready(SGLProblem(
            X=Xg,
            y=y,
            w=w,
            tau=jnp.asarray(tau, X_flat.dtype),
            feat_mask=mask,
            Lg=Lg,
            Xnorm_col=col,
            Xnorm_grp=jnp.sqrt(Lg),
        ))
    _M_MAKE_PROBLEM_S.set(time.perf_counter() - t0)
    return problem


def problem_from_grouped(
    X: jax.Array,
    y: jax.Array,
    tau: float,
    w=None,
    feat_mask=None,
) -> SGLProblem:
    """Build an :class:`SGLProblem` directly from a grouped (n, G, ng) design.

    Cheap constructor: column norms are exact, but the per-group spectral
    norm ``Xnorm_grp`` (and hence ``Lg``) uses the Frobenius upper bound
    ``||X_g||_F >= ||X_g||_2`` instead of a power iteration.  An upper bound
    keeps both consumers valid — Theorem-1 tests stay *safe* (larger radius
    term means fewer, never wrong, screens) and block-Lipschitz BCD steps
    stay convergent (smaller steps).  This is the constructor behind the
    raw-array ``solve_distributed`` wrapper, where the mesh kernels
    recompute their own sharded norms anyway.

    ``feat_mask`` defaults to the all-zero-column test (matching the
    zero-padding convention of :func:`make_problem`).
    """
    X = jnp.asarray(X)
    y = jnp.asarray(y, X.dtype)
    if feat_mask is None:
        feat_mask = jnp.any(X != 0, axis=0)           # (G, ng)
    else:
        feat_mask = jnp.asarray(feat_mask, bool)
    if w is None:
        w = jnp.sqrt(jnp.sum(feat_mask, axis=-1).astype(X.dtype))
    else:
        w = jnp.asarray(w, X.dtype)
    col = jnp.linalg.norm(X, axis=0)                  # (G, ng)
    fro2 = jnp.sum(X * X, axis=(0, 2))                # ||X_g||_F^2  (G,)
    return SGLProblem(
        X=X,
        y=y,
        w=w,
        tau=jnp.asarray(tau, X.dtype),
        feat_mask=feat_mask,
        Lg=fro2,
        Xnorm_col=col,
        Xnorm_grp=jnp.sqrt(fro2),
    )


def flatten(problem: SGLProblem, beta_g: jax.Array) -> jax.Array:
    """Grouped (G, ng) -> flat (p,) coefficient view."""
    return beta_g[problem.feat_mask]


def unflatten(problem: SGLProblem, beta_flat: jax.Array) -> jax.Array:
    """Flat (p,) -> grouped (G, ng) coefficient view (inverse of
    :func:`flatten`; padded slots come back zero).

    jit-compatible: the scatter is expressed as a cumulative-count gather
    over the static ``feat_mask`` rather than boolean indexing.
    """
    mask = jnp.ravel(problem.feat_mask)
    beta_flat = jnp.asarray(beta_flat)
    pos = jnp.cumsum(mask) - 1                         # flat slot -> (p,) index
    vals = jnp.take(beta_flat, jnp.clip(pos, 0, beta_flat.shape[0] - 1))
    vals = jnp.where(mask, vals, 0)
    return vals.reshape(problem.feat_mask.shape).astype(beta_flat.dtype)


# ----------------------------------------------------------------------------
# Norm, dual norm, objectives
# ----------------------------------------------------------------------------

def epsilons(tau: jax.Array, w: jax.Array) -> jax.Array:
    """eps_g = (1-tau) w_g / (tau + (1-tau) w_g)   (paper Eq. 18)."""
    denom = tau + one_minus(tau) * w
    return jnp.where(denom > 0, one_minus(tau) * w / jnp.where(denom > 0, denom, 1.0), 0.0)


def group_weight_total(tau: jax.Array, w: jax.Array) -> jax.Array:
    """tau + (1-tau) w_g — the per-group scaling of the eps-norm duality."""
    return tau + one_minus(tau) * w


def sgl_norm(beta: jax.Array, tau, w) -> jax.Array:
    """Omega_{tau,w}(beta) for grouped beta (G, ng) (padding must be zero)."""
    l1 = jnp.sum(jnp.abs(beta))
    l2 = jnp.sum(w * jnp.linalg.norm(beta, axis=-1))
    return tau * l1 + one_minus(tau) * l2


def sgl_dual_norm_terms(xi: jax.Array, tau, w) -> jax.Array:
    """Per-group terms of Omega^D: ||xi_g||_{eps_g} / (tau + (1-tau) w_g).

    The dual norm (Eq. 20) is the max of these; the compacted certified
    round (:mod:`repro.core.solver`) needs them individually — each screened
    group's term at a reference residual is cached so later rounds can bound
    it without re-touching that group's columns.  xi: grouped (G, ng) or any
    (..., ng) batch with w broadcastable to the leading shape.
    """
    xi = jnp.asarray(xi)
    eps = epsilons(tau, xi.dtype.type(1) * jnp.asarray(w, xi.dtype))
    scale = group_weight_total(tau, jnp.asarray(w, xi.dtype))
    return lam(xi, one_minus(eps), eps) / scale


def sgl_dual_norm(xi: jax.Array, tau, w) -> jax.Array:
    """Omega^D(xi) = max_g ||xi_g||_{eps_g} / (tau + (1-tau) w_g)  (Eq. 20).

    xi: grouped (G, ng) (padded entries must be 0 — they are then inert:
    S_threshold of 0 contributes nothing).
    """
    return jnp.max(sgl_dual_norm_terms(xi, tau, w))


def primal(problem: SGLProblem, beta: jax.Array, lam_: jax.Array) -> jax.Array:
    resid = problem.y - jnp.einsum("ngk,gk->n", problem.X, beta)
    return 0.5 * jnp.sum(resid * resid) + lam_ * sgl_norm(
        beta, problem.tau, problem.w
    )


def dual(problem: SGLProblem, theta: jax.Array, lam_: jax.Array) -> jax.Array:
    d = theta - problem.y / lam_
    return 0.5 * jnp.sum(problem.y * problem.y) - 0.5 * lam_ * lam_ * jnp.sum(d * d)


def duality_gap(
    problem: SGLProblem, beta: jax.Array, theta: jax.Array, lam_: jax.Array
) -> jax.Array:
    return primal(problem, beta, lam_) - dual(problem, theta, lam_)


def dual_scale(problem: SGLProblem, resid: jax.Array, lam_: jax.Array) -> jax.Array:
    """Dual feasible point from a residual (paper Eq. 15):

        theta = resid / max(lambda, Omega^D(X^T resid)).
    """
    corr = jnp.einsum("ngk,n->gk", problem.X, resid)
    scale = jnp.maximum(lam_, sgl_dual_norm(corr, problem.tau, problem.w))
    return resid / scale


def lambda_max(problem: SGLProblem) -> jax.Array:
    """lambda_max = Omega^D(X^T y)   (paper Eq. 22)."""
    corr = jnp.einsum("ngk,n->gk", problem.X, problem.y)
    return sgl_dual_norm(corr, problem.tau, problem.w)


# ----------------------------------------------------------------------------
# Loss-generalized objectives (journal follow-up arXiv 1611.05780)
# ----------------------------------------------------------------------------
#
# The quartet below generalizes primal/dual/gap/lambda_max to any
# registered :class:`repro.losses.Loss`:
#
#     P(beta)  = F(X beta) + lam * Omega_{tau,w}(beta)
#     D(theta) = -F*(-lam * theta)
#     rho      = -grad F(X beta)        (the generalized residual)
#     theta    = rho / max(lam, Omega^D(X^T rho))      (Eq. 15, verbatim)
#     lam_max  = Omega^D(X^T rho_0),  rho_0 = -grad F(0)
#
# The ``loss.name == "lsq"`` branches delegate to the original functions
# above *verbatim* — the default loss must produce bit-identical jitted
# programs to the pre-loss solver (asserted by tests/test_losses.py).

def primal_loss(problem: SGLProblem, loss, beta: jax.Array,
                lam_: jax.Array) -> jax.Array:
    """``F(X beta) + lam * Omega`` for any registered loss."""
    if loss.name == "lsq":
        return primal(problem, beta, lam_)
    z = jnp.einsum("ngk,gk->n", problem.X, beta)
    return loss.value(problem.y, z) + lam_ * sgl_norm(
        beta, problem.tau, problem.w
    )


def dual_loss(problem: SGLProblem, loss, theta: jax.Array,
              lam_: jax.Array) -> jax.Array:
    """``D(theta) = -F*(-lam theta)`` for any registered loss."""
    if loss.name == "lsq":
        return dual(problem, theta, lam_)
    return loss.dual_obj(problem.y, theta, lam_)


def duality_gap_loss(problem: SGLProblem, loss, beta: jax.Array,
                     theta: jax.Array, lam_: jax.Array) -> jax.Array:
    if loss.name == "lsq":
        return duality_gap(problem, beta, theta, lam_)
    return primal_loss(problem, loss, beta, lam_) - dual_loss(
        problem, loss, theta, lam_
    )


def dual_scale_loss(problem: SGLProblem, loss, beta: jax.Array,
                    lam_: jax.Array) -> jax.Array:
    """Dual feasible point from the loss gradient (Eq. 15 generalized):
    ``theta = rho / max(lam, Omega^D(X^T rho))``, ``rho = -grad F(X beta)``.

    The ``>= lam`` floor keeps ``-lam theta`` inside the conjugate's
    domain for bounded-domain losses (logistic), so the gap is finite.
    """
    if loss.name == "lsq":
        resid = problem.y - jnp.einsum("ngk,gk->n", problem.X, beta)
        return dual_scale(problem, resid, lam_)
    z = jnp.einsum("ngk,gk->n", problem.X, beta)
    rho = loss.neg_grad(problem.y, z)
    corr = jnp.einsum("ngk,n->gk", problem.X, rho)
    scale = jnp.maximum(lam_, sgl_dual_norm(corr, problem.tau, problem.w))
    return rho / scale


def lambda_max_loss(problem: SGLProblem, loss) -> jax.Array:
    """``lam_max = Omega^D(X^T rho_0)`` with ``rho_0 = -grad F(0)``
    (lsq: Eq. 22 verbatim; logistic: ``rho_0 = y - 1/2``)."""
    if loss.name == "lsq":
        return lambda_max(problem)
    rho0 = loss.lam_max_rho(problem.y)
    corr = jnp.einsum("ngk,n->gk", problem.X, rho0)
    return sgl_dual_norm(corr, problem.tau, problem.w)


# ----------------------------------------------------------------------------
# Multi-task SGL math (arXiv 1506.03736): matrix-valued beta (G, ng, K)
# ----------------------------------------------------------------------------
#
# The penalty becomes row-group norms:
#
#     Omega(B) = tau * sum_{g,j} ||B[g, j, :]||_2
#                + (1 - tau) * sum_g w_g ||B_g||_F
#
# i.e. the vector SGL norm applied to the matrix of row norms
# R[g, j] = ||B[g, j, :]||_2 — which means the dual norm REDUCES to the
# vector machinery: for a dual variable xi (G, ng, K), the sup over
# {B : Omega(B) <= 1} of <xi, B> factors through rows (each row of B
# only enters via its own l2 norm, and <xi_row, b_row> <= ||xi_row||_2
# * ||b_row||_2 with equality for aligned rows), so
#
#     Omega^D(xi) = vector-SGL-dual-norm of the row-norm matrix
#                   R'[g, j] = ||xi[g, j, :]||_2.
#
# The epsilon-norm only sees |x_j|, so feeding it row norms is exact.
# These helpers take raw arrays (Y is (n, K), beta (G, ng, K)) because
# :class:`SGLProblem` carries a (n,) response; the session-level solver
# threading is future work (SGLSession rejects multi_output losses).

def multitask_norm(beta: jax.Array, tau, w) -> jax.Array:
    """Row-group SGL norm of matrix-valued beta (G, ng, K)."""
    rows = jnp.linalg.norm(beta, axis=-1)           # (G, ng)
    l1 = jnp.sum(rows)
    l2 = jnp.sum(w * jnp.linalg.norm(rows, axis=-1))
    return tau * l1 + one_minus(tau) * l2


def multitask_dual_norm_terms(xi: jax.Array, tau, w) -> jax.Array:
    """Per-group dual-norm terms of the row-group norm: the vector terms
    (Eq. 20) evaluated on the row-norm matrix (see the reduction above)."""
    rows = jnp.linalg.norm(xi, axis=-1)             # (G, ng)
    return sgl_dual_norm_terms(rows, tau, w)


def multitask_dual_norm(xi: jax.Array, tau, w) -> jax.Array:
    return jnp.max(multitask_dual_norm_terms(xi, tau, w))


def multitask_primal(X: jax.Array, Y: jax.Array, beta: jax.Array,
                     tau, w, lam_) -> jax.Array:
    """``0.5 ||Y - X beta||_F^2 + lam * Omega`` (X (n,G,ng), Y (n,K))."""
    R = Y - jnp.einsum("ngk,gkt->nt", X, beta)
    return 0.5 * jnp.sum(R * R) + lam_ * multitask_norm(beta, tau, w)


def multitask_dual(Y: jax.Array, theta: jax.Array, lam_) -> jax.Array:
    """Quadratic dual at matrix-valued theta (n, K)."""
    d = theta - Y / lam_
    return 0.5 * jnp.sum(Y * Y) - 0.5 * lam_ * lam_ * jnp.sum(d * d)


def multitask_duality_gap(X: jax.Array, Y: jax.Array, beta: jax.Array,
                          theta: jax.Array, tau, w, lam_) -> jax.Array:
    return multitask_primal(X, Y, beta, tau, w, lam_) - multitask_dual(
        Y, theta, lam_
    )


def multitask_dual_scale(X: jax.Array, Y: jax.Array, beta: jax.Array,
                         tau, w, lam_) -> jax.Array:
    """Eq. 15 on the matrix residual: theta = R / max(lam, Omega^D(X^T R))."""
    R = Y - jnp.einsum("ngk,gkt->nt", X, beta)
    corr = jnp.einsum("ngk,nt->gkt", X, R)
    scale = jnp.maximum(lam_, multitask_dual_norm(corr, tau, w))
    return R / scale


def multitask_lambda_max(X: jax.Array, Y: jax.Array, tau, w) -> jax.Array:
    corr = jnp.einsum("ngk,nt->gkt", X, Y)
    return multitask_dual_norm(corr, tau, w)


def multitask_group_screen(corr: jax.Array, radius, Xnorm_grp: jax.Array,
                           tau, w) -> jax.Array:
    """Conservative safe group test for the multi-task GAP sphere.

    For the GAP sphere B(theta, r), group g can be discarded when
    ``sup_{||Z||_F <= r} Omega^D_g(X_g^T (theta + Z)) < 1``.  We bound
    the sup by ``Omega^D_g(X_g^T theta) + r ||X_g||_2 / (tau +
    (1-tau) w_g)`` — the second factor because ``Omega_g(B_g) >= (tau +
    (1-tau) w_g) ||B_g||_F`` (every row contributes at least its own
    norm to both the l1-of-rows and the Frobenius term), hence
    ``Omega^D_g(V) <= ||V||_F / (tau + (1-tau) w_g)``.  Conservative
    (never screens a group the exact test would keep), hence safe.

    ``corr``: X^T theta in grouped layout (G, ng, K).  Returns (G,) bool,
    True = group survives (may be active).
    """
    terms = multitask_dual_norm_terms(corr, tau, w)   # (G,)
    slack = radius * Xnorm_grp / group_weight_total(tau, jnp.asarray(w))
    return terms + slack >= 1.0


# ----------------------------------------------------------------------------
# Proximal operators
# ----------------------------------------------------------------------------

def soft_threshold(x: jax.Array, thr) -> jax.Array:
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - thr, 0.0)


def group_soft_threshold(x: jax.Array, thr) -> jax.Array:
    """S^gp_thr(x) = (1 - thr/||x||)_+ x over the trailing axis."""
    nrm = jnp.linalg.norm(x, axis=-1, keepdims=True)
    scale = jnp.maximum(one_minus(thr / jnp.maximum(nrm, 1e-30)), 0.0)
    return jnp.where(nrm > 0, scale * x, 0.0)


def sgl_prox(beta: jax.Array, step, tau, w, lam_) -> jax.Array:
    """prox of step * lambda * Omega_{tau,w} at grouped beta (G, ng):
    two-level soft-thresholding (paper Section 6).

    ``step`` may be a scalar or per-group (G,) array (1/L_g for BCD).
    """
    step = jnp.asarray(step)
    if step.ndim == 1:
        step = step[:, None]
    a = soft_threshold(beta, tau * lam_ * step)
    thr = (one_minus(tau) * lam_ * jnp.asarray(w))[:, None] * step
    return group_soft_threshold_keep(a, thr)


def group_soft_threshold_keep(x: jax.Array, thr: jax.Array) -> jax.Array:
    """Group soft-threshold with per-group threshold array (G, 1)."""
    nrm = jnp.linalg.norm(x, axis=-1, keepdims=True)
    scale = jnp.maximum(one_minus(thr / jnp.maximum(nrm, 1e-30)), 0.0)
    return jnp.where(nrm > 0, scale * x, 0.0)
