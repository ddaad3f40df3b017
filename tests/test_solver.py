"""Solver correctness: optimality conditions, reference agreement, warm starts.

Hypothesis-based property tests live in test_properties.py; path-engine
tests (sequential screening, cache carrying) in test_path.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    lambda_max,
    make_problem,
    primal,
    sgl_prox,
    solve,
    solve_path,
    lambda_grid,
)
from repro.data import make_climate_like, make_synthetic


def prox_grad_reference(X, y, sizes, tau, lam_, w=None, iters=30_000):
    """Plain full-gradient ISTA in numpy — an independent oracle."""
    n, p = X.shape
    ng = sizes[0]
    G = len(sizes)
    w = np.sqrt(ng) * np.ones(G) if w is None else w
    L = np.linalg.norm(X, 2) ** 2
    beta = np.zeros(p)
    for _ in range(iters):
        grad = X.T @ (X @ beta - y)
        z = beta - grad / L
        z = np.sign(z) * np.maximum(np.abs(z) - tau * lam_ / L, 0.0)
        zg = z.reshape(G, ng)
        nrm = np.linalg.norm(zg, axis=1, keepdims=True)
        scale = np.maximum(1 - ((1 - tau) * w[:, None] * lam_ / L) / np.maximum(nrm, 1e-30), 0)
        beta = (scale * zg).ravel()
    return beta


@pytest.fixture(scope="module")
def tiny():
    X, y, bt, sizes = make_synthetic(n=25, p=60, n_groups=12, gamma1=2,
                                     gamma2=2, seed=11)
    return X, y, sizes


def test_matches_independent_ista(tiny):
    X, y, sizes = tiny
    tau = 0.4
    prob = make_problem(X, y, sizes, tau=tau)
    lam_ = 0.2 * float(lambda_max(prob))
    ref = prox_grad_reference(X, y, sizes, tau, lam_, iters=20_000)
    res = solve(prob, lam_, tol=1e-12, rule="gap", max_epochs=50_000)
    ours = np.asarray(res.beta).reshape(-1)[: X.shape[1]]
    np.testing.assert_allclose(ours, ref, atol=5e-6)


def test_fixed_point_of_prox(tiny):
    """At the optimum, beta = prox(beta - grad/L) per group (Fermat)."""
    X, y, sizes = tiny
    prob = make_problem(X, y, sizes, tau=0.25)
    lam_ = 0.15 * float(lambda_max(prob))
    res = solve(prob, lam_, tol=1e-12, rule="gap", max_epochs=50_000)
    beta = res.beta
    resid = prob.y - jnp.einsum("ngk,gk->n", prob.X, beta)
    grad = -jnp.einsum("ngk,n->gk", prob.X, resid)
    step = 1.0 / prob.Lg
    z = beta - grad * step[:, None]
    fixed = sgl_prox(z, step, prob.tau, prob.w, lam_)
    np.testing.assert_allclose(
        np.asarray(fixed * prob.feat_mask), np.asarray(beta), atol=1e-6
    )


def test_screening_identical_solutions(tiny):
    X, y, sizes = tiny
    prob = make_problem(X, y, sizes, tau=0.5)
    lam_ = 0.1 * float(lambda_max(prob))
    sols = {}
    for rule in ("gap", "none", "dynamic"):
        res = solve(prob, lam_, tol=1e-10, rule=rule, max_epochs=40_000)
        sols[rule] = np.asarray(res.beta)
    np.testing.assert_allclose(sols["gap"], sols["none"], atol=1e-5)
    np.testing.assert_allclose(sols["dynamic"], sols["none"], atol=1e-5)


def test_gap_decreases_epochs_vs_no_screening(tiny):
    """Screening must never *increase* the number of epochs to tolerance."""
    X, y, sizes = tiny
    prob = make_problem(X, y, sizes, tau=0.3)
    lam_ = 0.3 * float(lambda_max(prob))
    e_gap = solve(prob, lam_, tol=1e-9, rule="gap", max_epochs=40_000).n_epochs
    e_none = solve(prob, lam_, tol=1e-9, rule="none", max_epochs=40_000).n_epochs
    assert e_gap <= e_none + 10  # same epoch grid, allow one f_ce round slack


def test_path_warm_start_active_fracs():
    X, y, _, sizes = make_synthetic(n=30, p=200, n_groups=20, gamma1=3,
                                    gamma2=3, seed=5)
    prob = make_problem(X, y, sizes, tau=0.2)
    path = solve_path(prob, T=10, delta=2.0, tol=1e-7)
    assert np.all(path.gaps <= 1e-7)
    # active fraction grows (weakly) as lambda decreases (index 0 is
    # lambda_max where beta=0 converges before any screening round runs)
    assert path.feat_active_frac[1] <= path.feat_active_frac[-1] + 1e-9
    # first lambda = lambda_max keeps beta = 0
    assert float(jnp.abs(path.betas[0]).max()) == 0.0


def test_unequal_group_sizes():
    rng = np.random.default_rng(2)
    n, sizes = 30, [3, 7, 5, 10, 2, 13]
    p = sum(sizes)
    X = rng.standard_normal((n, p))
    beta = np.zeros(p)
    beta[3:7] = 2.0
    y = X @ beta + 0.01 * rng.standard_normal(n)
    prob = make_problem(X, y, sizes, tau=0.35)
    lam_ = 0.2 * float(lambda_max(prob))
    ref_rule_none = solve(prob, lam_, tol=1e-10, rule="none", max_epochs=40_000)
    res = solve(prob, lam_, tol=1e-10, rule="gap", max_epochs=40_000)
    np.testing.assert_allclose(
        np.asarray(res.beta), np.asarray(ref_rule_none.beta), atol=1e-5
    )
    screened = ~np.asarray(res.feat_active) & np.asarray(prob.feat_mask)
    assert np.all(np.abs(np.asarray(ref_rule_none.beta)[screened]) < 1e-8)


def test_climate_like_generator_solves():
    X, y, _, sizes = make_climate_like(n=60, n_lon=6, n_lat=4, seed=1)
    prob = make_problem(X, y, sizes, tau=0.4)
    lam_ = 0.3 * float(lambda_max(prob))
    res = solve(prob, lam_, tol=1e-7, rule="gap", max_epochs=20_000)
    assert float(res.gap) <= 1e-7
    assert res.feat_active.sum() < np.asarray(prob.feat_mask).sum()


def test_lambda_grid_matches_paper():
    g = lambda_grid(100.0, T=100, delta=3.0)
    assert g[0] == 100.0
    np.testing.assert_allclose(g[-1], 100.0 * 10 ** -3.0)
    assert len(g) == 100


# The full certified round against the grouped-einsum formulas: groups of
# 7 (climate), of 10 (synth) and ragged, for the GAP rule on lsq and
# logistic and for a dynamic rule whose center needs a second correlation.
ROUND_SIZES = {"g7": [7] * 9, "g10": [10] * 8, "ragged": [3, 7, 5, 10, 2, 6]}
ROUND_RULES = [("gap", "lsq"), ("gap", "logistic"), ("dynamic", "lsq")]


def _round_problem(sizes, loss_name, seed=3):
    rng = np.random.default_rng(seed)
    n, p = 40, sum(sizes)
    X = rng.standard_normal((n, p))
    if loss_name == "logistic":
        y = (rng.standard_normal(n) > 0).astype(np.float64)
    else:
        y = X[:, :4] @ np.array([2.0, -1.5, 1.0, 0.5]) + 0.1 * rng.standard_normal(n)
    prob = make_problem(X, y, sizes, tau=0.3)
    beta = (0.01 * rng.standard_normal(prob.feat_mask.shape)
            * np.asarray(prob.feat_mask))
    beta[2:, :] = 0.0          # a sparse primal point: two live groups
    return prob, jnp.asarray(beta)


@pytest.mark.parametrize("rule_name,loss_name", ROUND_RULES)
@pytest.mark.parametrize("shape", sorted(ROUND_SIZES))
def test_screen_round_matches_grouped_einsum_formulas(shape, rule_name,
                                                      loss_name):
    from repro.core import screening as scr
    from repro.core import sgl
    from repro.core.solver import _screen_round
    from repro.losses import resolve_loss
    from repro.rules import RuleState, get_rule

    prob, beta = _round_problem(ROUND_SIZES[shape], loss_name)
    loss, rule = resolve_loss(loss_name), get_rule(rule_name)
    lam_max = sgl.lambda_max_loss(prob, loss)
    lam_ = 0.9 * lam_max
    rr, resid, terms = _screen_round(prob, beta, lam_, lam_max, rule=rule,
                                     backend="xla", loss=loss)

    theta = sgl.dual_scale_loss(prob, loss, beta, lam_)
    gap = sgl.duality_gap_loss(prob, loss, beta, theta, lam_)
    corr = jnp.einsum("ngk,n->gk", prob.X, resid)
    scale = jnp.maximum(lam_, sgl.sgl_dual_norm(corr, prob.tau, prob.w))
    state = RuleState(problem=prob, beta=beta, resid=resid, corr=corr,
                      scale=scale, theta=theta, gap=gap, lam=lam_,
                      lam_max=lam_max, nu=float(loss.nu))
    center, radius, _ = rule.center_and_radius(state)
    ref = scr.screen_with_corr(
        prob, scr.Sphere(center, radius),
        jnp.einsum("ngk,n->gk", prob.X, center))

    assert abs(float(rr.gap - gap)) <= 1e-12 * abs(float(gap))
    np.testing.assert_allclose(np.asarray(rr.theta), np.asarray(theta),
                               rtol=0, atol=1e-12 * float(jnp.max(
                                   jnp.abs(theta))))
    np.testing.assert_allclose(
        np.asarray(terms), np.asarray(sgl.sgl_dual_norm_terms(
            corr, prob.tau, prob.w)), rtol=1e-12)
    g_ref = np.asarray(ref.group_active)
    np.testing.assert_array_equal(np.asarray(rr.group_active), g_ref)
    np.testing.assert_array_equal(np.asarray(rr.feat_active),
                                  np.asarray(ref.feat_active))
    assert 0 < g_ref.sum() < g_ref.size   # the tests had something to cut


def test_screen_round_passes_over_design_twice_as_rank2_products():
    """The lsq round touches X in exactly two dot_generals, both rank 2:
    X beta and X^T resid.  A grouped (3-D) contraction or a third pass
    (a gap recomputing X beta) fails this."""
    from repro.analysis.jaxpr_lints import iter_eqns
    from repro.core.solver import _screen_round
    from repro.rules import GapSafeRule

    prob, beta = _round_problem(ROUND_SIZES["g7"], "lsq")
    lam_max = lambda_max(prob)
    jaxpr = jax.make_jaxpr(
        lambda p, b, l, lm: _screen_round(p, b, l, lm, rule=GapSafeRule(),
                                          backend="xla"))(
        prob, beta, 0.7 * lam_max, lam_max)
    design = prob.X.size
    on_x = [e for e in iter_eqns(jaxpr.jaxpr)
            if e.primitive.name == "dot_general"
            and any(np.prod(v.aval.shape) == design for v in e.invars)]
    assert len(on_x) == 2
    assert all(v.aval.ndim <= 2 for e in on_x for v in e.invars)
