"""Fused BCD-epoch mega-kernel: interpret-mode bit-parity vs the lax.scan
reference, batched-lambda grid semantics, and the session-level pin that
``solver_backend="pallas"`` reproduces the XLA path exactly."""
import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import sgl
from repro.core.session import SGLSession, SolverConfig
from repro.core.solver import (
    bcd_epochs,
    bcd_epochs_loss,
    resolve_solver_backend,
)
from repro.data.synthetic import make_synthetic
from repro.kernels import ops, ref
from repro.losses import resolve_loss


def _gathered_like(rng, Gb, n, ng, B=1, dead_frac=0.3, dup_alias=True):
    """Random compacted-buffer state with masked/padded groups.

    ``dead`` groups model screened + bucket-padded slots: Lg = 0, zero
    feature mask, zero coefficients — and (dup_alias) the last dead slot
    carries a COPY of group 0's design, mimicking _gather_static's padded
    ``take`` slots that alias group 0.
    """
    Xt = rng.standard_normal((Gb, n, ng))
    Lg = rng.uniform(0.5, 3.0, Gb)
    dead = rng.random(Gb) < dead_frac
    dead[0] = False                      # keep the aliased group live
    if dup_alias and dead.any():
        Xt[np.nonzero(dead)[0][-1]] = Xt[0]
    Lg[dead] = 0.0
    fm = (rng.random((B, Gb, ng)) < 0.85).astype(float)
    fm[:, dead] = 0.0
    w = np.sqrt(ng) * np.ones(Gb)
    beta = rng.standard_normal((B, Gb, ng)) * fm
    resid = rng.standard_normal((B, n))
    return (jnp.asarray(Xt), jnp.asarray(Lg), jnp.asarray(w),
            jnp.asarray(fm), jnp.asarray(beta), jnp.asarray(resid))


@pytest.mark.parametrize("Gb,n,ng,n_epochs", [
    (8, 17, 5, 1),      # minimum bucket
    (16, 40, 10, 3),    # multi-epoch block
    (32, 100, 7, 5),    # paper-config-like odd ng
    (10, 25, 4, 2),     # Gb not a block_g multiple (wrapper pads)
    (64, 30, 3, 1),     # multi-tile group stream
])
def test_fused_epochs_bit_identical_to_scan(Gb, n, ng, n_epochs, rng):
    """f64 interpret-mode fused kernel == lax.scan reference, bit for bit,
    across bucket sizes, masked/padded (duplicate-alias) groups, and
    multi-epoch blocks."""
    Xt, Lg, w, fm, beta, resid = _gathered_like(rng, Gb, n, ng)
    tau, lam = jnp.asarray(0.3), jnp.asarray(0.45)
    want_b, want_r = bcd_epochs(Xt, Lg, w, fm[0], beta[0], resid[0],
                                tau, lam, n_epochs)
    got_b, got_r = ops.bcd_epochs_fused(Xt, Lg, w, fm, beta, resid, tau,
                                        jnp.reshape(lam, (1,)), n_epochs)
    np.testing.assert_array_equal(np.asarray(got_b[0]), np.asarray(want_b))
    np.testing.assert_array_equal(np.asarray(got_r[0]), np.asarray(want_r))


def test_fused_epochs_matches_ref_oracle(rng):
    """kernels.ref.bcd_epochs_ref is the same reference (bench parity)."""
    Xt, Lg, w, fm, beta, resid = _gathered_like(rng, 16, 20, 6, B=2)
    tau = jnp.asarray(0.4)
    lam_b = jnp.asarray([0.3, 0.9])
    want = ref.bcd_epochs_ref(Xt, Lg, w, fm, beta, resid, tau, lam_b, 3)
    got = ops.bcd_epochs_fused(Xt, Lg, w, fm, beta, resid, tau, lam_b, 3)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_epochs_batched_grid_equals_per_lambda(rng):
    """The lambda-batch grid axis: B lambdas in one launch, each carrying
    its own beta/resid/mask/threshold, bit-identical to B separate
    single-lambda launches (and hence to B scan references)."""
    B = 4
    Xt, Lg, w, fm, beta, resid = _gathered_like(rng, 16, 30, 6, B=B)
    tau = jnp.asarray(0.35)
    lam_b = jnp.asarray([0.2, 0.5, 0.9, 1.7])
    got_b, got_r = ops.bcd_epochs_fused(Xt, Lg, w, fm, beta, resid, tau,
                                        lam_b, 4)
    for b in range(B):
        want_b, want_r = bcd_epochs(Xt, Lg, w, fm[b], beta[b], resid[b],
                                    tau, lam_b[b], 4)
        np.testing.assert_array_equal(np.asarray(got_b[b]),
                                      np.asarray(want_b))
        np.testing.assert_array_equal(np.asarray(got_r[b]),
                                      np.asarray(want_r))


def _loss_epochs_full_scan(Xt, Lg, w, fm, beta, z, y, tau, lam_, loss,
                           n_epochs):
    """``bcd_epochs_loss`` as a scan over every slot: the oracle for the
    lsq loss (``ref.bcd_epochs_logistic_ref`` is the logistic one)."""
    live = (Lg > 0).astype(beta.dtype)
    safe_L = jnp.where(Lg > 0, loss.nu * Lg, 1.0)
    step = lam_ / safe_L
    thr1 = tau * step
    thr2 = (1.0 - tau) * w * step

    def group_update(z, inputs):
        Xg, bg, L, t1, t2, m, lv = inputs
        grad_step = (Xg.T @ loss.neg_grad(y, z)) / L
        u = (bg + grad_step) * m
        u = jnp.sign(u) * jnp.maximum(jnp.abs(u) - t1, 0.0)
        nrm = jnp.linalg.norm(u)
        u = jnp.maximum(1.0 - t2 / jnp.maximum(nrm, 1e-30), 0.0) * u
        new_bg = jnp.where(lv > 0, u, bg)
        return z + Xg @ (new_bg - bg), new_bg

    def epoch(carry, _):
        beta, z = carry
        z, beta = jax.lax.scan(group_update, z,
                               (Xt, beta, safe_L, thr1, thr2, fm, live))
        return (beta, z), None

    (beta, z), _ = jax.lax.scan(epoch, (beta, z), None, length=n_epochs)
    return beta, z


def _live_pattern(rng, Gb, pattern):
    if pattern == "all":
        return np.ones(Gb, bool)
    if pattern == "none":
        return np.zeros(Gb, bool)
    if pattern == "scattered":       # the uncompacted solve's dead groups
        live = rng.random(Gb) < 0.5
        live[[1, -2]] = True, False
        return live
    return np.arange(Gb) < pattern   # a live prefix, as _gather_static makes


_LIVE_CASES = ([(8, k) for k in ("all", 1, 7, "scattered", "none")]
               + [(64, k) for k in ("all", 1, 7, 8, 9, 63, "scattered",
                                    "none")]
               + [(10, "scattered"), (15, "scattered")])


@pytest.mark.parametrize("epochs", ["lsq_resid", "lsq", "logistic"])
@pytest.mark.parametrize("Gb,pattern", _LIVE_CASES)
def test_live_bounded_epochs_bit_identical_to_full_scan(Gb, pattern, epochs,
                                                        rng):
    """The group loop stops after the chunk holding the last live slot;
    beta and the carry match a scan over every slot bit for bit, and with
    no live slot they come back unchanged."""
    n, ng = 17, 5
    live = _live_pattern(rng, Gb, pattern)
    Xt = rng.standard_normal((Gb, n, ng))
    Lg = np.where(live, rng.uniform(0.5, 3.0, Gb), 0.0)
    fm = (rng.random((Gb, ng)) < 0.85) * live[:, None].astype(float)
    w = np.sqrt(ng) * np.ones(Gb)
    beta = rng.standard_normal((Gb, ng)) * fm
    carry = rng.standard_normal(n)
    y = (rng.random(n) < 0.5).astype(float)
    Xt, Lg, w, fm, y = map(jnp.asarray, (Xt, Lg, w, fm, y))
    tau, lam = jnp.asarray(0.3), jnp.asarray(0.45)

    def args():                      # fresh buffers: beta/carry are donated
        return jnp.asarray(beta), jnp.asarray(carry)

    if epochs == "lsq_resid":
        got = bcd_epochs(Xt, Lg, w, fm, *args(), tau, lam, 3)
        want = ref.bcd_epochs_ref(Xt, Lg, w, fm[None],
                                  *(a[None] for a in args()), tau,
                                  jnp.reshape(lam, (1,)), 3)
        want = want[0][0], want[1][0]
    else:
        loss = resolve_loss(epochs)
        got = bcd_epochs_loss(Xt, Lg, w, fm, *args(), tau, lam, y, loss, 3)
        if epochs == "logistic":
            b0, z0 = args()
            want = ref.bcd_epochs_logistic_ref(Xt, Lg, w, fm[None], b0[None],
                                               z0[None], y, tau,
                                               jnp.reshape(lam, (1,)), 3)
            want = want[0][0], want[1][0]
        else:
            want = _loss_epochs_full_scan(Xt, Lg, w, fm, *args(), y, tau,
                                          lam, loss, 3)
    for g, wnt in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(wnt))
    if pattern == "none":
        np.testing.assert_array_equal(np.asarray(got[0]), beta)
        np.testing.assert_array_equal(np.asarray(got[1]), carry)


def test_fused_epochs_zero_epochs_is_identity(rng):
    Xt, Lg, w, fm, beta, resid = _gathered_like(rng, 8, 10, 4)
    out_b, out_r = ops.bcd_epochs_fused(Xt, Lg, w, fm, beta, resid,
                                        jnp.asarray(0.3),
                                        jnp.asarray([0.5]), 0)
    np.testing.assert_array_equal(np.asarray(out_b), np.asarray(beta))
    np.testing.assert_array_equal(np.asarray(out_r), np.asarray(resid))


def test_resolve_solver_backend_validates():
    assert resolve_solver_backend("xla", np.float64) == "xla"
    assert resolve_solver_backend("pallas", np.float64) == "pallas"
    assert resolve_solver_backend("auto", np.float32) in ("xla", "pallas")
    with pytest.raises(ValueError, match="solver backend"):
        resolve_solver_backend("cuda", np.float32)
    with pytest.raises(ValueError, match="solver backend"):
        SGLSession(
            sgl.make_problem(np.eye(4), np.ones(4), [2, 2], tau=0.5),
            SolverConfig(solver_backend="cuda"),
        )


@pytest.fixture(scope="module")
def prob():
    X, y, _, sizes = make_synthetic(n=48, p=256, n_groups=32, gamma1=3,
                                    gamma2=3, seed=5)
    return sgl.make_problem(X, y, sizes, tau=0.3)


@pytest.fixture(scope="module")
def xla_path(prob):
    session = SGLSession(prob, SolverConfig(tol=1e-7, max_epochs=20_000,
                                            solver_backend="xla"))
    return session.solve_path(T=8, delta=2.0)


def test_session_pallas_solver_reproduces_xla_path(prob, xla_path):
    """Session pin: solver_backend="pallas" (interpret) reproduces the full
    path of "xla" — betas BIT-identical, epoch counts and seq/dyn screen
    counters equal, round audits equal — while actually dispatching fused
    launches."""
    session = SGLSession(prob, SolverConfig(tol=1e-7, max_epochs=20_000,
                                            solver_backend="pallas"))
    res = session.solve_path(T=8, delta=2.0, batch_lambdas=1)
    ref_res = xla_path
    np.testing.assert_array_equal(res.betas, ref_res.betas)
    np.testing.assert_array_equal(res.epochs, ref_res.epochs)
    np.testing.assert_array_equal(res.seq_screened, ref_res.seq_screened)
    np.testing.assert_array_equal(res.dyn_screened, ref_res.dyn_screened)
    assert res.n_rounds == ref_res.n_rounds
    assert res.n_compact_rounds == ref_res.n_compact_rounds
    assert res.n_full_rounds == ref_res.n_full_rounds
    assert ref_res.n_fused_epoch_launches == 0
    assert res.n_fused_epoch_launches > 0
    assert res.batched_lambdas == 0          # batch_lambdas=1: no batching


def test_session_pallas_single_solve_bit_parity(prob):
    """Single-lambda solves agree bit-for-bit too (incl. the non-compact
    branch, which dispatches the fused kernel on the full buffer)."""
    lam = float(sgl.lambda_max(prob)) / 15.0
    for compact in (True, False):
        r_x = SGLSession(prob, SolverConfig(
            tol=1e-7, compact=compact, solver_backend="xla")).solve(lam)
        s_p = SGLSession(prob, SolverConfig(
            tol=1e-7, compact=compact, solver_backend="pallas"))
        r_p = s_p.solve(lam)
        np.testing.assert_array_equal(np.asarray(r_p.beta),
                                      np.asarray(r_x.beta))
        assert r_p.n_epochs == r_x.n_epochs
        assert s_p.fused_epoch_launches > 0


def test_batched_lambda_path_single_device(prob):
    """Coinciding-active-set WARM path points (dense grid — batching is
    gated to warm stretches) solve through the kernel's lambda-batch axis:
    audit counters move, every lambda still meets tol, and the path stays
    within solver tolerance of the per-lambda XLA reference (trajectories
    differ — all batched lambdas warm-start from the same beta — so parity
    is tol-level, not bit-level)."""
    xla_dense = SGLSession(prob, SolverConfig(
        tol=1e-7, max_epochs=20_000, solver_backend="xla",
    )).solve_path(T=8, delta=0.5)
    session = SGLSession(prob, SolverConfig(tol=1e-7, max_epochs=20_000,
                                            solver_backend="pallas"))
    res = session.solve_path(T=8, delta=0.5, batch_lambdas=4)
    assert res.batched_lambdas > 0
    assert session.batched_lambdas == res.batched_lambdas
    assert res.n_fused_epoch_launches > 0
    assert (res.gaps <= 1e-7).all()
    np.testing.assert_allclose(res.betas, xla_dense.betas, atol=1e-7)
    # Batched-lambda runs must preserve path SAFETY: certified masks can
    # never kill a coefficient that is nonzero at the optimum.
    nz = np.abs(xla_dense.betas) > 1e-9
    assert not (nz & ~res.feat_active).any()


def test_batched_path_respects_screen_counters(prob):
    """seq/dyn counters stay consistent under batching: dyn_screened is
    non-negative and seq_screened counts the adopted certificates."""
    session = SGLSession(prob, SolverConfig(tol=1e-7, max_epochs=20_000,
                                            solver_backend="pallas"))
    res = session.solve_path(T=8, delta=0.5, batch_lambdas=3)
    assert res.batched_lambdas > 0
    assert (res.dyn_screened >= 0).all()
    assert (res.seq_screened >= 0).all()
    n_groups = res.group_active.shape[1]
    assert (res.seq_screened <= n_groups).all()
