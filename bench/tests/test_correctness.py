"""The comparison that decides ``correct``: sound runs pass it, and the
control and each fault the cell can have fail it."""
from __future__ import annotations

import numpy as np
import pytest

from bench import check, reference


def test_sound_run_is_correct(run_tiny):
    out = run_tiny(seed=2**31 + 11)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"path_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_control_float32_reference_is_not_correct():
    """The reference in the program's place, in float32 (the precision
    below the configuration's float64), fails the comparison."""
    from bench.drivers.path import make_data
    from bench.tests.conftest import TINY_CONFIG

    X, ng, ys = make_data(TINY_CONFIG, 2)
    answers = check.control_answers(TINY_CONFIG, X, ng, ys)
    numbers = {n: (v, lim) for n, v, lim in
               check.judge(TINY_CONFIG, X, ng, ys, answers)[0]}
    assert not check.passed(list((n, v, lim) for n, (v, lim) in numbers.items()))
    for name in ("own_gap_max", "gap_understated_rel", "lambda_rel_max"):
        assert numbers[name][0] > numbers[name][1], name


def _broken(monkeypatch, alter):
    """Break the timed path underneath: every ``solve_path`` result goes
    through ``alter`` before the benchmark sees it."""
    from repro.core import SGLSession

    original = SGLSession.solve_path

    def solve_path(self, *args, **kwargs):
        return alter(original(self, *args, **kwargs))

    monkeypatch.setattr(SGLSession, "solve_path", solve_path)


def _unchanged(res):
    """The solve returns its state unchanged: the zero start."""
    return res._replace(betas=np.zeros_like(res.betas))


def _answer_altered(res):
    betas = res.betas.copy()
    t = len(betas) - 1
    g, k = np.unravel_index(np.argmax(np.abs(betas[t])), betas[t].shape)
    betas[t, g, k] *= 1.001
    return res._replace(betas=betas)


def _half_left_out(res):
    half = len(res.lambdas) // 2
    return res._replace(**{f: getattr(res, f)[:half] for f in (
        "lambdas", "betas", "gaps", "epochs", "feat_active", "group_active")})


def _certified_zero_altered(res):
    """A feature that is nonzero at the optimum certified as zero."""
    feat = res.feat_active.copy()
    t = len(feat) - 1
    g, k = np.unravel_index(np.argmax(np.abs(res.betas[t])), feat[t].shape)
    feat[t, g, k] = False
    return res._replace(feat_active=feat)


@pytest.mark.parametrize("alter", [_unchanged, _answer_altered,
                                   _half_left_out, _certified_zero_altered])
def test_fault_is_not_correct(run_tiny, monkeypatch, alter):
    _broken(monkeypatch, alter)
    out = run_tiny(seed=17)
    assert not out["correct"], (alter.__name__, out["checks"])


def test_reference_epsilon_norm_solves_its_equation():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((50, 7)) * rng.uniform(0.01, 10, (50, 1))
    x[3] = 0.0
    x[4, 1:] = 0.0
    eps = rng.uniform(0.05, 0.95, 50)
    nu = reference.epsilon_norm(x, eps)
    lhs = (np.maximum(np.abs(x) - (1 - eps)[:, None] * nu[:, None], 0) ** 2).sum(1)
    np.testing.assert_allclose(lhs, (eps * nu) ** 2, rtol=1e-12, atol=1e-300)
    assert nu[3] == 0.0
    np.testing.assert_allclose(nu[4], np.abs(x[4, 0]), rtol=1e-13)


def test_reference_agrees_with_the_program_on_the_cpu():
    """Written independently, the reference and the program agree on
    lambda_max and on the gap of the same beta (IEEE f64 on the CPU)."""
    import jax.numpy as jnp
    from repro.core import SGLSession, SolverConfig, lambda_grid, make_problem, sgl
    from repro.data.synthetic import make_synthetic

    X, y, _, sizes = make_synthetic(n=40, p=300, n_groups=30, seed=4)
    prob = make_problem(X, y, sizes, tau=0.2)
    session = SGLSession(prob, SolverConfig(tol=1e-8))
    ref = reference.Problem(X, y, sizes[0], 0.2)
    assert ref.lambda_max() == pytest.approx(session.lam_max, rel=1e-14)
    lams = lambda_grid(session.lam_max, T=100, delta=3.0)[:6]
    path = session.solve_path(lams)
    for t, lam in enumerate(lams):
        beta = jnp.asarray(path.betas[t])
        resid = prob.y - jnp.einsum("ngk,gk->n", prob.X, beta)
        theta = sgl.dual_scale(prob, resid, lam)
        want = float(sgl.duality_gap(prob, beta, theta, lam))
        assert abs(ref.gap(path.betas[t], lam) - want) <= 1e-10
    betas, gaps = reference.solve_path(ref, lams, check.REF_TOL)
    assert np.all(gaps <= check.REF_TOL)
    np.testing.assert_allclose(betas, path.betas, atol=1e-4)


def test_seed_pass_answers_are_judged():
    """The responses drawn from ``--seed`` after the window are judged with
    the window's answers: a fault in one of them alone is not correct."""
    from bench.drivers.path import Driver, seed_responses
    from bench.tests.conftest import TINY_CONFIG

    driver = Driver(TINY_CONFIG, {"responses": 2, "seed_responses": 1},
                    seed=2**33 + 5)
    driver.setup()
    driver.cycle()
    driver.seed_pass()
    assert [a["response"] for a in driver.seed_answers] == [2]
    assert not np.allclose(driver.ys[2], driver.ys[0])
    assert not np.allclose(seed_responses(TINY_CONFIG, driver.X, driver.ng, 6, 1)[0],
                           driver.ys[2])
    driver.release()
    assert check.passed(driver.check()[0])
    betas = driver.seed_answers[0]["betas"]
    g, k = np.unravel_index(np.argmax(np.abs(betas[-1])), betas[-1].shape)
    betas[-1, g, k] *= 1.001
    assert not check.passed(driver.check()[0])
