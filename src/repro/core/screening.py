"""Safe screening rules for the Sparse-Group Lasso (paper Section 4 + App. C).

A *safe sphere* B(theta_c, r) is any ball guaranteed to contain the dual
optimum theta_hat.  Given one, Theorem 1 gives the two-level tests:

group level:    T_g < (1 - tau) w_g             =>  beta_g = 0
   T_g = ||S_tau(X_g^T theta_c)|| + r ||X_g||_2     if ||X_g^T theta_c||_inf > tau
       = (||X_g^T theta_c||_inf + r ||X_g||_2 - tau)_+   otherwise
feature level:  |X_j^T theta_c| + r ||X_j|| < tau  =>  beta_j = 0

Spheres implemented (paper Section 7.1):
* GAP        — B(theta, sqrt(2 gap / lambda^2))        [this paper, Thm 2]
* static     — B(y/lambda, ||y/lambda_max - y/lambda||) [El Ghaoui et al. 12]
* dynamic    — B(y/lambda, ||theta_k - y/lambda||)      [Bonnefoy et al. 14]
* DST3       — sphere refined by the most-correlated-group hyperplane
               [Xiang 11 / Bonnefoy 14, extended to SGL in App. C]

All tests operate on the grouped layout of :mod:`repro.core.sgl` and return a
:class:`ScreenResult` with boolean *active* masks (True = keep).  Safety means
a screened-out (False) variable is *provably* zero at the optimum.

This module holds the sphere *constructions* and the Theorem-1 *tests*;
the strategy objects that plug them into the solver's shared round
skeleton (center/radius per rule + safety metadata) live in
:mod:`repro.rules`, and the solver consumes rules through that API.

Bounded dual-norm terms (compacted certified rounds)
----------------------------------------------------
Certificates are permanent, so a screened group's exact correlation
``X_g^T resid`` is never needed again for *screening* — it only re-enters
through the dual scaling ``Omega^D(X^T resid)`` (Eq. 15), which maxes the
per-group eps-norm terms over ALL groups.  :func:`screened_dual_bound`
bounds the screened groups' part of that max from a cached reference:

    ||X_g^T resid||_eps  <=  ||X_g^T resid_ref||_eps
                             + ||X_g||_2 * ||resid - resid_ref||_2

by the triangle inequality (the eps-norm is a norm) plus
``||v||_eps <= ||v||_2`` and Cauchy-Schwarz.  The l2-domination holds
because coordinatewise ``(|v_i| - c)_+ <= |v_i| (||v||_2 - c)_+ / ||v||_2``
for any c >= 0, so at nu = ||v||_2 the defining equation's left side
``sum S_{(1-eps)nu}(v)^2 <= (eps nu)^2`` already — the root is <= ||v||_2.
Whenever the bound stays below ``max(lambda, active-group max)``, the full
dual norm provably equals the active-group max and a round computed on the
compacted active buffer alone is *exact* (see
:mod:`repro.core.solver`).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import sgl
from .epsilon_norm import epsilon_norm, epsilon_norm_dual
from .sgl import SGLProblem, soft_threshold
from .precision import one_minus

__all__ = [
    "ScreenResult",
    "Sphere",
    "gap_sphere",
    "sequential_sphere",
    "static_sphere",
    "dynamic_sphere",
    "dst3_sphere",
    "screen",
    "screen_with_corr",
    "screened_dual_bound",
    "screened_group_rate",
    "theorem1_tests",
]


class Sphere(NamedTuple):
    center: jax.Array  # (n,)
    radius: jax.Array  # scalar


class ScreenResult(NamedTuple):
    group_active: jax.Array  # (G,) bool
    feat_active: jax.Array   # (G, ng) bool — False => provably zero
    sphere: Sphere


# ----------------------------------------------------------------------------
# Safe spheres
# ----------------------------------------------------------------------------

def gap_sphere(
    problem: SGLProblem, beta: jax.Array, theta: jax.Array, lam_
) -> Sphere:
    """GAP safe sphere (Theorem 2): r = sqrt(2 (P - D) / lambda^2)."""
    gap = jnp.maximum(sgl.duality_gap(problem, beta, theta, lam_), 0.0)
    return Sphere(theta, jnp.sqrt(2.0 * gap) / lam_)


def sequential_sphere(
    problem: SGLProblem, beta_prev: jax.Array, lam_new
) -> Sphere:
    """Sequential GAP safe sphere at a *new* lambda on a path (paper §7.1).

    Before any epoch at ``lam_new``, the previous lambda's primal point
    ``beta_prev`` yields a dual feasible point at the new lambda by residual
    rescaling (Eq. 15); Theorem 2 then gives a GAP sphere valid at
    ``lam_new``, so most groups are discarded with zero BCD work.  This is
    the paper's "sequential" rule; the path engine evaluates the same round
    through the jitted/Pallas-backed :func:`repro.core.solver.screen_round`
    and this helper is the reference/one-shot form of it.
    """
    resid = problem.y - jnp.einsum("ngk,gk->n", problem.X, beta_prev)
    theta = sgl.dual_scale(problem, resid, lam_new)
    return gap_sphere(problem, beta_prev, theta, lam_new)


def static_sphere(problem: SGLProblem, lam_, lam_max) -> Sphere:
    center = problem.y / lam_
    radius = jnp.linalg.norm(problem.y / lam_max - problem.y / lam_)
    return Sphere(center, radius)


def dynamic_sphere(problem: SGLProblem, theta_k: jax.Array, lam_) -> Sphere:
    center = problem.y / lam_
    radius = jnp.linalg.norm(theta_k - center)
    return Sphere(center, radius)


def dst3_sphere(
    problem: SGLProblem, theta_k: jax.Array, lam_, lam_max
) -> Sphere:
    """DST3 sphere (paper App. C, Prop. 11), extended to the SGL.

    Uses the hyperplane supporting the dual feasible set at y/lambda_max,
    normal to the gradient of the eps-norm of the most-correlated group.
    """
    y, tau, w = problem.y, problem.tau, problem.w
    corr = jnp.einsum("ngk,n->gk", problem.X, y)  # X^T y, grouped
    eps = sgl.epsilons(tau, w)
    scale = sgl.group_weight_total(tau, w)
    per_group = epsilon_norm(corr, eps) / scale
    g_star = jnp.argmax(per_group)

    xg = jnp.take(corr, g_star, axis=0) / lam_max       # X_{g*}^T y / lam_max
    eps_s = jnp.take(eps, g_star)
    nu = epsilon_norm(xg, eps_s)
    xi_star = soft_threshold(xg, one_minus(eps_s) * nu)  # eps-part of gradient
    denom = epsilon_norm_dual(xi_star, eps_s)
    Xg_star = jnp.take(problem.X, g_star, axis=1)       # (n, ng)
    eta = Xg_star @ xi_star / jnp.maximum(denom, 1e-30)

    c_level = jnp.take(scale, g_star)                    # tau + (1-tau) w_{g*}
    yl = y / lam_
    shift = (jnp.dot(eta, y) / lam_ - c_level) / jnp.maximum(
        jnp.dot(eta, eta), 1e-30
    )
    theta_c = yl - shift * eta
    r2 = jnp.sum((yl - theta_k) ** 2) - jnp.sum((yl - theta_c) ** 2)
    return Sphere(theta_c, jnp.sqrt(jnp.maximum(r2, 0.0)))


# ----------------------------------------------------------------------------
# Bounded dual-norm terms for compacted certified rounds
# ----------------------------------------------------------------------------

def screened_group_rate(problem: SGLProblem) -> jax.Array:
    """Per-group growth rate of the dual-norm term under a residual shift:
    ``||X_g||_2 / (tau + (1-tau) w_g)`` — the Lipschitz constant of
    ``resid -> ||X_g^T resid||_eps / scale_g`` (see the module docstring).
    Constants of the problem; (G,)."""
    return problem.Xnorm_grp / sgl.group_weight_total(problem.tau, problem.w)


def screened_dual_bound(
    ref_terms: jax.Array,
    rate: jax.Array,
    resid_shift: jax.Array,
    screened: jax.Array,
) -> jax.Array:
    """Upper bound on ``max_{g screened} ||X_g^T resid||_eps / scale_g``.

    ``ref_terms``: (G,) per-group dual-norm terms at a reference residual
    (:func:`repro.core.sgl.sgl_dual_norm_terms` of ``X^T resid_ref``);
    ``rate``: (G,) from :func:`screened_group_rate`;
    ``resid_shift``: scalar ``||resid - resid_ref||_2``;
    ``screened``: (G,) bool, True for the groups to bound.

    Safety: by the triangle inequality on the eps-norm and
    ``||X_g^T d||_eps <= ||X_g^T d||_2 <= ||X_g||_2 ||d||_2`` (module
    docstring), every screened group's true term at ``resid`` is <= its
    bound, so if the returned max is <= max(lambda, max over *exact* active
    terms), the full-problem dual norm equals the active-term max exactly.
    Returns 0 when nothing is screened (the bound then constrains nothing).
    """
    b = ref_terms + rate * resid_shift
    return jnp.max(jnp.where(screened, b, 0.0))


# ----------------------------------------------------------------------------
# Screening tests (Theorem 1)
# ----------------------------------------------------------------------------

def theorem1_tests(
    corr: jax.Array,       # (..., ng) X^T theta_c, grouped
    radius,                # sphere radius r
    Xnorm_grp: jax.Array,  # (...,) ||X_g||_2 (any safe upper bound)
    Xnorm_col: jax.Array,  # (..., ng) column norms
    w: jax.Array,          # (...,) group weights
    feat_mask: jax.Array,  # (..., ng) bool, real features
    tau,
    st_norm: Optional[jax.Array] = None,
):
    """Raw Theorem-1 keep-tests; the ONE implementation of the paper's
    group/feature test formulas.

    Shared by the full round (:func:`screen_with_corr`) and the compacted
    round (:func:`repro.core.solver._screen_round_compact`), whose safety
    contract is exact agreement with the full round on the gathered groups
    — keeping a single copy of the formulas is what guarantees they cannot
    drift apart.  Operates on any leading batch shape (full (G, ...) or a
    gathered (Gb, ...) buffer).  Returns ``(group_keep, feat_keep)``
    *before* the caller's extra masking (group wipe-out of features,
    feat_mask, already-screened groups).

    ``st_norm``: optional precomputed ||S_tau(corr)|| per group (e.g. from
    the fused Pallas kernel's S_tau(corr)^2 output).
    """
    if st_norm is None:
        ste = soft_threshold(corr, tau)
        st_norm = jnp.linalg.norm(ste, axis=-1)                 # ||S_tau(.)||
    inf_norm = jnp.max(jnp.abs(jnp.where(feat_mask, corr, 0.0)), axis=-1)

    Tg_out = st_norm + radius * Xnorm_grp
    Tg_in = jnp.maximum(inf_norm + radius * Xnorm_grp - tau, 0.0)
    Tg = jnp.where(inf_norm > tau, Tg_out, Tg_in)
    group_keep = Tg >= one_minus(tau) * w                        # keep if test fails

    feat_keep = jnp.abs(corr) + radius * Xnorm_col >= tau
    return group_keep, feat_keep


def screen_with_corr(
    problem: SGLProblem, sphere: Sphere, corr: jax.Array,
    st2: Optional[jax.Array] = None,
) -> ScreenResult:
    """Theorem 1 tests given precomputed correlations corr = X^T theta_c
    in grouped layout (G, ng).

    ``st2``: optional precomputed S_tau(corr)^2, e.g. the second output of
    the fused Pallas kernel (:func:`repro.kernels.ops.screening_scores`),
    which thresholds the correlation while the block is still resident in
    VMEM.  When given, the group test consumes it directly instead of
    re-thresholding ``corr`` — previously that half of every fused kernel
    call was discarded and recomputed here (ROADMAP item).
    """
    st_norm = None if st2 is None else jnp.sqrt(jnp.sum(st2, axis=-1))
    group_active, feat_active = theorem1_tests(
        corr, sphere.radius, problem.Xnorm_grp, problem.Xnorm_col,
        problem.w, problem.feat_mask, problem.tau, st_norm=st_norm,
    )
    # Feature-level screening only has bite for tau > 0; for tau == 0 the
    # test |.| < 0 never fires, which the >= comparison already encodes.
    # Screened groups wipe all their features; padding is always inactive.
    feat_active = feat_active & group_active[:, None] & problem.feat_mask
    group_active = group_active & jnp.any(problem.feat_mask, axis=-1)
    return ScreenResult(group_active, feat_active, sphere)


def screen(problem: SGLProblem, sphere: Sphere, backend: str = "xla",
           xt_pre: Optional[jax.Array] = None) -> ScreenResult:
    """Theorem-1 tests against ``sphere``.

    ``backend="pallas"`` routes the correlation through the *fused*
    screening-scores kernel — here the threshold ``tau`` applies to
    ``X^T center`` directly (no dual rescaling), so the kernel's fused
    S_tau(corr)^2 output is handed to :func:`screen_with_corr` and the
    group test never re-thresholds.  Requires a concrete (un-traced)
    problem because ``tau`` is a static kernel parameter.

    ``xt_pre``: persistent transposed design from
    :func:`repro.kernels.ops.prepare_transposed`; without it every
    Pallas-backed call materialises a fresh (p, n) transposed copy of X
    (the per-call copy the session API exists to eliminate) — built through
    the counted :func:`repro.kernels.ops.transposed_design` so the
    transpose audit sees this path too.
    """
    if backend == "pallas":
        from ..kernels import ops as kops

        n, G, ng = problem.X.shape
        p = G * ng
        Xt = kops.transposed_design(problem.X) if xt_pre is None else xt_pre
        corr_f, st2_f = kops.screening_scores(
            Xt, sphere.center, tau=float(problem.tau)
        )
        return screen_with_corr(
            problem, sphere, corr_f[:p].reshape(G, ng),
            st2=st2_f[:p].reshape(G, ng)
        )
    corr = jnp.einsum("ngk,n->gk", problem.X, sphere.center)
    return screen_with_corr(problem, sphere, corr)
