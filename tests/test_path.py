"""Path-engine tests: sequential-screening safety (Thm 1/2 along a path),
engine/naive-loop equivalence, option plumbing, and backend parity."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    lambda_max,
    make_problem,
    screen_round,
    sequential_sphere,
    solve,
    solve_path,
)
from repro.core.screening import screen
from repro.data.synthetic import make_synthetic


@pytest.fixture(scope="module")
def prob():
    X, y, _, sizes = make_synthetic(n=30, p=120, n_groups=15, gamma1=3,
                                    gamma2=3, seed=9)
    return make_problem(X, y, sizes, tau=0.3)


@pytest.fixture(scope="module")
def engine_path(prob):
    return solve_path(prob, T=8, delta=2.0, tol=1e-8, rule="gap")


def test_path_screening_is_safe(prob, engine_path):
    """Safety invariant of Thm 1/2 across the whole path: no variable
    screened out (sequentially or dynamically) may be non-zero in a
    high-precision unscreened reference solution."""
    feat_mask = np.asarray(prob.feat_mask)
    beta_ref = jnp.zeros((prob.G, prob.ng), prob.X.dtype)
    for t, lam_ in enumerate(engine_path.lambdas):
        ref = solve(prob, float(lam_), beta0=beta_ref, tol=1e-11,
                    rule="none", max_epochs=60_000)
        beta_ref = ref.beta
        screened = ~engine_path.feat_active[t] & feat_mask
        leaked = np.abs(np.asarray(ref.beta))[screened]
        assert leaked.size == 0 or leaked.max() < 1e-8, (t, leaked.max())


def test_engine_matches_naive_loop(prob, engine_path):
    naive = solve_path(prob, T=8, delta=2.0, tol=1e-8, rule="gap",
                       sequential=False, check_every=None)
    np.testing.assert_allclose(engine_path.betas, naive.betas, atol=1e-4)
    assert (engine_path.gaps <= 1e-8).all()
    # The per-epoch early exit removes whole-block overshoot, but screening
    # at different iterates can perturb a trajectory by a few passes — allow
    # one block of slack rather than asserting strict dominance.
    assert engine_path.epochs.sum() <= naive.epochs.sum() + 10


def test_sequential_screening_zero_work_at_lambda_max(engine_path):
    # lambda_0 = lambda_max: warm gap is already 0 => zero BCD epochs, and
    # the radius-0 GAP sphere screens out non-equicorrelated groups.
    assert engine_path.epochs[0] == 0
    assert engine_path.seq_screened[0] > 0
    assert float(np.abs(engine_path.betas[0]).max()) == 0.0
    # counters are consistent: seq + dyn never exceeds G
    assert ((engine_path.seq_screened + engine_path.dyn_screened)
            <= engine_path.betas.shape[1]).all()
    assert (engine_path.dyn_screened >= 0).all()


def test_cache_carrying_reduces_gathers(prob, engine_path):
    naive = solve_path(prob, T=8, delta=2.0, tol=1e-8, rule="gap",
                       sequential=False, check_every=None)
    assert engine_path.n_gathers <= naive.n_gathers


def test_solve_path_forwards_compact_and_inner_rounds(prob):
    res_c = solve_path(prob, T=5, delta=1.5, tol=1e-7, rule="gap",
                       compact=True, inner_rounds=2)
    res_f = solve_path(prob, T=5, delta=1.5, tol=1e-7, rule="gap",
                       compact=False)
    np.testing.assert_allclose(res_c.betas, res_f.betas, atol=1e-4)
    assert (res_c.gaps <= 1e-7).all() and (res_f.gaps <= 1e-7).all()


def test_sequential_sphere_is_safe(prob):
    """The sequential GAP sphere built at a new lambda from the previous
    lambda's solution must contain the new dual optimum (Thm 2)."""
    lmax = float(lambda_max(prob))
    prev = solve(prob, 0.5 * lmax, tol=1e-10, rule="none", max_epochs=40_000)
    lam_new = 0.4 * lmax
    sph = sequential_sphere(prob, prev.beta, lam_new)
    opt = solve(prob, lam_new, tol=1e-12, rule="none", max_epochs=60_000)
    dist = float(jnp.linalg.norm(opt.theta - sph.center))
    assert dist <= float(sph.radius) + 1e-8
    # and screening with it keeps every support variable of the optimum
    res = screen(prob, sph)
    support = np.abs(np.asarray(opt.beta)) > 1e-8
    assert not np.any(support & ~np.asarray(res.feat_active))


def test_screen_round_backends_agree(prob):
    """Pallas-kernel round (interpret mode off-TPU) == XLA einsum round."""
    lmax = float(lambda_max(prob))
    res = solve(prob, 0.3 * lmax, tol=1e-8, rule="gap")
    out_x = screen_round(prob, res.beta, 0.25 * lmax, rule="gap",
                         backend="xla")
    out_p = screen_round(prob, res.beta, 0.25 * lmax, rule="gap",
                         backend="pallas")
    np.testing.assert_allclose(float(out_x[0]), float(out_p[0]), rtol=1e-8)
    np.testing.assert_allclose(np.asarray(out_x[1]), np.asarray(out_p[1]),
                               atol=1e-12)
    assert np.array_equal(np.asarray(out_x[2]), np.asarray(out_p[2]))
    assert np.array_equal(np.asarray(out_x[3]), np.asarray(out_p[3]))


def test_solve_path_pallas_backend_end_to_end(prob):
    res = solve_path(prob, T=4, delta=1.5, tol=1e-7, rule="gap",
                     screen_backend="pallas")
    assert (res.gaps <= 1e-7).all()


def test_path_counts_group_steps_inside_the_k_done_read(monkeypatch):
    """``n_group_steps`` is what the compacted epoch blocks ran: per epoch
    the slots up to the chunk holding the last live group, fewer than the
    buffer's ``n_group_slots`` once a bucket of 32 holds at most 24 active
    groups; and it rides in the ``k_done`` read, one per ``_inner_rounds``
    call, with no read of its own."""
    from repro.core import session as session_mod
    from repro.core import solver as solver_mod
    from repro.core.session import SGLSession, SolverConfig
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as ot

    calls = []
    inner = session_mod._inner_rounds

    def logged(Xt, Lg, w, y, beta, feat_active, take, gmask, *rest):
        out = inner(Xt, Lg, w, y, beta, feat_active, take, gmask, *rest)
        calls.append((Lg * gmask, rest[3], out[1]))   # block_epochs, k
        return out

    monkeypatch.setattr(session_mod, "_inner_rounds", logged)
    X, y, _, sizes = make_synthetic(n=30, p=200, n_groups=40, gamma1=3,
                                    gamma2=3, seed=9)
    prob = make_problem(X, y, sizes, tau=0.3)
    steps0 = obs_metrics.REGISTRY.counter("solver.epoch_group_steps").value
    slots0 = obs_metrics.REGISTRY.counter("solver.epoch_group_slots").value
    ot.configure(enabled=True, sample_every=1)
    ot.TRACER.reset()
    try:
        res = SGLSession(prob, SolverConfig(tol=1e-8, rule="gap")
                         ).solve_path(T=5, delta=2.0)
        k_done_reads = [r for r in ot.TRACER.records("read")
                        if r["attrs"].get("what") == "k_done"]
    finally:
        ot.configure(enabled=False)
        ot.TRACER.reset()

    C = solver_mod._GROUP_CHUNK
    steps = slots = 0
    for live_L, block_epochs, k in calls:
        live_L = np.asarray(live_L)
        last = np.nonzero(live_L > 0)[0].max() + 1 if (live_L > 0).any() else 0
        epochs = block_epochs * int(k)
        steps += -(-last // C) * C * epochs
        slots += live_L.size * epochs
    assert calls and len(k_done_reads) == len(calls)
    assert res.n_group_steps == steps > 0
    assert res.n_group_slots == slots
    assert res.n_group_steps < res.n_group_slots
    assert obs_metrics.REGISTRY.counter(
        "solver.epoch_group_steps").value - steps0 == steps
    assert obs_metrics.REGISTRY.counter(
        "solver.epoch_group_slots").value - slots0 == slots
