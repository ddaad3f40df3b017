"""Session-API tests: legacy-wrapper parity (solve / solve_path /
solve_distributed), persistent-transposed-design accounting, unflatten,
st2-consuming screen, and the distributed path with sequential certificates.
"""
import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    RoundResult,
    SGLSession,
    SolverConfig,
    flatten,
    lambda_max,
    make_problem,
    problem_from_grouped,
    solve,
    solve_path,
    unflatten,
)
from repro.core.screening import gap_sphere, screen
from repro.data.synthetic import make_synthetic
from repro.launch import mesh as meshlib


@pytest.fixture(scope="module")
def prob():
    # Reduced synthetic paper config (AR(1) design, equal groups, tau=0.2).
    X, y, _, sizes = make_synthetic(n=40, p=200, n_groups=20, gamma1=4,
                                    gamma2=3, seed=7)
    return make_problem(X, y, sizes, tau=0.2)


@pytest.fixture(scope="module")
def session_path(prob):
    session = SGLSession(prob, SolverConfig(tol=1e-8))
    res = session.solve_path(T=8, delta=2.0)
    return session, res


def test_session_path_matches_legacy_path(prob, session_path):
    """PathResult parity on the synthetic config: betas / gaps / epochs /
    screen counters (acceptance criterion: epochs within +-1 per lambda,
    identical seq/dyn counters)."""
    _, res = session_path
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        legacy = solve_path(prob, T=8, delta=2.0, tol=1e-8)
    np.testing.assert_allclose(res.betas, legacy.betas, atol=1e-10)
    np.testing.assert_allclose(res.gaps, legacy.gaps, rtol=1e-8, atol=1e-14)
    assert (res.gaps <= 1e-8).all()
    assert np.abs(res.epochs - legacy.epochs).max() <= 1
    assert np.array_equal(res.seq_screened, legacy.seq_screened)
    assert np.array_equal(res.dyn_screened, legacy.dyn_screened)
    assert np.array_equal(res.group_active, legacy.group_active)


def test_legacy_solve_delegates_to_session(prob):
    lam = 0.25 * float(lambda_max(prob))
    session = SGLSession(prob, SolverConfig(tol=1e-9))
    r_new = session.solve(lam)
    with pytest.deprecated_call():
        r_old = solve(prob, lam, tol=1e-9)
    np.testing.assert_allclose(np.asarray(r_new.beta),
                               np.asarray(r_old.beta), atol=1e-12)
    assert r_new.n_epochs == r_old.n_epochs
    assert np.array_equal(r_new.group_active, r_old.group_active)


def test_screen_round_is_roundresult(prob, session_path):
    session, res = session_path
    cert = session.screen(0.2 * session.lam_max, res.betas[-1])
    assert isinstance(cert, RoundResult)
    gap, theta, g_act, f_act = cert[:4]      # legacy positional quartet
    assert not bool(cert.compact)            # screen() is always a full round
    assert g_act.shape == (prob.G,)
    assert f_act.shape == (prob.G, prob.ng)
    assert float(gap) >= 0 or np.isfinite(float(gap))


def test_pallas_session_zero_transpose_copies(prob):
    """Acceptance criterion: Pallas-backed certified rounds perform zero
    per-call transposed copies — ONE persistent transposed design serves
    the whole path (built once, reused across solve_path calls)."""
    from repro.kernels import ops as kops

    s_pal = SGLSession(prob, SolverConfig(tol=1e-7,
                                          screen_backend="pallas"))
    s_xla = SGLSession(prob, SolverConfig(tol=1e-7, screen_backend="xla"))
    with kops.audit_scope() as audit:
        p_pal = s_pal.solve_path(T=5, delta=1.5)
    # The real audit: no jitted round traced an on-the-fly transpose — the
    # persistent design reached the kernel (a broken xt_pre wiring would
    # build a transposing trace on the first round and trip this).
    assert audit.transpose_traces == 0
    p_xla = s_xla.solve_path(T=5, delta=1.5)
    np.testing.assert_allclose(p_pal.betas, p_xla.betas, atol=1e-10)
    assert np.array_equal(p_pal.epochs, p_xla.epochs)
    assert p_pal.n_rounds > 0
    assert p_pal.n_transpose_copies == 0
    xt = s_pal.xt_pre
    assert xt is not None and xt.shape[0] >= prob.G * prob.ng
    s_pal.solve_path(T=3, delta=1.0)
    assert s_pal.xt_pre is xt                 # still the same buffer
    # XLA backend needs no transposed design at all.
    assert s_xla.xt_pre is None


def test_unflatten_inverts_flatten():
    rng = np.random.default_rng(3)
    n, sizes = 20, [3, 7, 5, 2]
    X = rng.standard_normal((n, sum(sizes)))
    y = rng.standard_normal(n)
    prob = make_problem(X, y, sizes, tau=0.3)
    beta = jnp.asarray(rng.standard_normal((prob.G, prob.ng))) * prob.feat_mask
    flat = flatten(prob, beta)
    assert flat.shape == (sum(sizes),)
    np.testing.assert_allclose(np.asarray(unflatten(prob, flat)),
                               np.asarray(beta))
    # flatten(unflatten(x)) is the identity on flat vectors too
    np.testing.assert_allclose(
        np.asarray(flatten(prob, unflatten(prob, flat))), np.asarray(flat)
    )


def test_screen_consumes_fused_st2(prob, session_path):
    """screen(backend='pallas') feeds the fused kernel's S_tau(corr)^2 to
    screen_with_corr instead of re-thresholding — masks must be identical
    to the einsum path."""
    session, res = session_path
    lam = 0.2 * session.lam_max
    cert = session.screen(lam, res.betas[-1])
    sphere = gap_sphere(prob, jnp.asarray(res.betas[-1]), cert.theta,
                        jnp.asarray(lam))
    r_x = screen(prob, sphere)
    r_p = screen(prob, sphere, backend="pallas")
    assert np.array_equal(np.asarray(r_x.group_active),
                          np.asarray(r_p.group_active))
    assert np.array_equal(np.asarray(r_x.feat_active),
                          np.asarray(r_p.feat_active))


# ---------------------------------------------------------------------------
# Distributed strategy
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def dist_prob():
    X, y, _, sizes = make_synthetic(n=40, p=160, n_groups=16, gamma1=3,
                                    gamma2=3, seed=3, dtype=np.float64)
    return X, y, sizes


def test_dist_session_matches_legacy_wrapper(dist_prob):
    X, y, sizes = dist_prob
    n, p = X.shape
    G, ng = len(sizes), p // len(sizes)
    tau = 0.3
    problem = make_problem(X, y, sizes, tau=tau)
    lam = float(lambda_max(problem)) / 10.0
    L = float(np.linalg.norm(X, 2) ** 2)
    mesh = meshlib.make_test_mesh()

    session = SGLSession(problem, SolverConfig(tol=1e-7, max_epochs=20_000),
                         mesh=mesh, L=L)
    res = session.solve(lam)

    from repro.distributed.solver_dist import solve_distributed
    Xg = jnp.asarray(X.reshape(n, G, ng))
    w = jnp.sqrt(jnp.full((G,), float(ng), jnp.float64))
    with pytest.deprecated_call():
        beta, gap, gaps, mask = solve_distributed(
            mesh, Xg, jnp.asarray(y), w, tau=tau, lam_=lam, L=L,
            tol=1e-7, max_steps=20_000,
        )
    assert float(res.gap) <= 1e-7 and gap <= 1e-7
    np.testing.assert_allclose(np.asarray(res.beta), np.asarray(beta),
                               atol=1e-9)
    assert res.n_epochs == gaps[-1][0]


def test_dist_path_sequential_certificates_are_safe(dist_prob):
    """Distributed path safety: nothing sequentially (or dynamically)
    screened under the mesh may be nonzero in a single-device tight-tol
    reference solution."""
    X, y, sizes = dist_prob
    tau = 0.3
    problem = make_problem(X, y, sizes, tau=tau)
    mesh = meshlib.make_test_mesh()
    session = SGLSession(problem, SolverConfig(tol=1e-6, max_epochs=20_000),
                         mesh=mesh)
    path = session.solve_path(T=5, delta=1.5)
    assert (path.gaps <= 1e-6).all()
    # Sequential certificates were actually exercised on the mesh, and the
    # coinciding-certificate runs went through the batched-lambda kernel.
    assert path.seq_screened.sum() > 0
    assert session.batched_lambdas > 0

    feat_mask = np.asarray(problem.feat_mask)
    ref_session = SGLSession(problem, SolverConfig(tol=1e-10, rule="none",
                                                   max_epochs=60_000))
    beta_ref = jnp.zeros((problem.G, problem.ng), problem.X.dtype)
    for t, lam_ in enumerate(path.lambdas):
        ref = ref_session.solve(float(lam_), beta0=beta_ref)
        beta_ref = ref.beta
        screened = ~path.feat_active[t] & feat_mask
        leaked = np.abs(np.asarray(ref.beta))[screened]
        assert leaked.size == 0 or leaked.max() < 1e-7, (t, leaked.max())


def test_dist_f32_converged_certificate_not_reported(dist_prob):
    """Sub-f64 mesh runs must not adopt/report the masks of a round the
    solve converged on (cancellation error can mis-certify borderline
    groups) — mirrors the single-device path reporter guard."""
    X, y, sizes = dist_prob
    problem = make_problem(X.astype(np.float32), y.astype(np.float32),
                           sizes, tau=0.3)
    mesh = meshlib.make_test_mesh()
    session = SGLSession(problem, SolverConfig(tol=1e-3, max_epochs=2000),
                         mesh=mesh)
    path = session.solve_path(T=3, delta=1.0)
    # lambda_max converges on its sequential certificate with zero steps;
    # in f32 the certificate is neither applied nor reported.
    assert path.epochs[0] == 0
    assert path.seq_screened[0] == 0
    assert path.group_active[0].all()
    assert float(np.abs(path.betas[0]).max()) == 0.0


def test_dist_lipschitz_safeguard_recovers_from_bad_L(dist_prob):
    """An under-estimated global Lipschitz constant makes FISTA diverge;
    the safeguard must raise L at runtime and still reach tolerance."""
    X, y, sizes = dist_prob
    problem = make_problem(X, y, sizes, tau=0.3)
    lam = float(lambda_max(problem)) / 10.0
    L_exact = float(np.linalg.norm(X, 2) ** 2)
    mesh = meshlib.make_test_mesh()
    session = SGLSession(problem, SolverConfig(tol=1e-6, max_epochs=40_000),
                         mesh=mesh, L=L_exact / 16.0)
    res = session.solve(lam)
    assert float(res.gap) <= 1e-6
    assert session._dist.L >= L_exact * 0.9     # safeguard raised it
    ref = SGLSession(problem, SolverConfig(tol=1e-8)).solve(lam)
    np.testing.assert_allclose(np.asarray(res.beta), np.asarray(ref.beta),
                               atol=5e-3)


def test_dist_nan_round_does_not_adopt_masks(dist_prob):
    """A FISTA blow-up makes the screen round's comparisons all read False;
    adopting those masks would permanently zero beta and report false
    zero-certificates.  The driver must skip non-finite rounds' masks,
    rewind, and still converge to the right solution."""
    X, y, sizes = dist_prob
    problem = make_problem(X, y, sizes, tau=0.3)
    lam = float(lambda_max(problem)) / 10.0
    L_exact = float(np.linalg.norm(X, 2) ** 2)
    mesh = meshlib.make_test_mesh()
    session = SGLSession(problem, SolverConfig(tol=1e-6, max_epochs=40_000),
                         mesh=mesh, L=L_exact / 2 ** 40)
    res = session.solve(lam)
    assert float(res.gap) <= 1e-6
    assert res.group_active.any()               # not the all-False wipe-out
    ref = SGLSession(problem, SolverConfig(tol=1e-8)).solve(lam)
    np.testing.assert_allclose(np.asarray(res.beta), np.asarray(ref.beta),
                               atol=5e-3)
    support = np.abs(np.asarray(ref.beta)) > 1e-7
    assert not np.any(support & ~res.feat_active)


def test_dist_session_rejects_non_gap_rules(dist_prob):
    """The sharded screen kernel only produces GAP certificates; a mesh
    session must refuse other rules instead of silently relabeling."""
    X, y, sizes = dist_prob
    problem = make_problem(X, y, sizes, tau=0.3)
    mesh = meshlib.make_test_mesh()
    with pytest.raises(ValueError, match="rule='gap' only"):
        SGLSession(problem, SolverConfig(rule="dynamic"), mesh=mesh)
    session = SGLSession(problem, SolverConfig(tol=1e-6), mesh=mesh)
    with pytest.raises(ValueError, match="rule='gap' only"):
        session.screen(1.0, rule="dst3")


def test_problem_from_grouped_safe_bounds(dist_prob):
    """The cheap grouped constructor must over-estimate (never under-) the
    spectral norms, keeping Theorem-1 tests safe."""
    X, y, sizes = dist_prob
    n, p = X.shape
    G, ng = len(sizes), p // len(sizes)
    exact = make_problem(X, y, sizes, tau=0.3)
    cheap = problem_from_grouped(X.reshape(n, G, ng), y, tau=0.3)
    assert np.all(np.asarray(cheap.Xnorm_grp) >=
                  np.asarray(exact.Xnorm_grp) - 1e-8)
    np.testing.assert_allclose(np.asarray(cheap.Xnorm_col),
                               np.asarray(exact.Xnorm_col), rtol=1e-10)
    assert np.array_equal(np.asarray(cheap.feat_mask),
                          np.asarray(exact.feat_mask))


def test_unknown_backend_raises_at_config_construction():
    """Backend typos fail at SolverConfig() with the valid choices — not
    as a jit-time error deep inside the first certified round."""
    with pytest.raises(ValueError, match="screen backend.*cuda"):
        SolverConfig(screen_backend="cuda")
    with pytest.raises(ValueError, match="solver backend.*gpu"):
        SolverConfig(solver_backend="gpu")
    # the valid values (and _replace) still construct fine
    cfg = SolverConfig(screen_backend="pallas", solver_backend="xla")
    assert cfg._replace(tol=1e-6).screen_backend == "pallas"


