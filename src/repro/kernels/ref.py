"""Pure-jnp oracles for the Pallas kernels (ground truth for allclose tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sgl_prox_ref(beta, step, w, tau, lam):
    """Two-level prox, grouped layout (G, ng); step/w are (G,)."""
    t1 = tau * lam * step[:, None]
    z = jnp.sign(beta) * jnp.maximum(jnp.abs(beta) - t1, 0.0)
    nrm = jnp.linalg.norm(z, axis=1, keepdims=True)
    t2 = (1.0 - tau) * lam * (w * step)[:, None]
    scale = jnp.maximum(1.0 - t2 / jnp.maximum(nrm, 1e-30), 0.0)
    return scale * z


def dual_norm_ref(x, alpha, R):
    """Exact sorted-prefix-sum Lambda per group (paper Algorithm 1)."""
    from repro.core.epsilon_norm import lam as lam_exact

    return lam_exact(x, alpha, R)


def screening_scores_ref(Xt, theta, tau):
    corr = Xt @ theta
    st = jnp.maximum(jnp.abs(corr) - tau, 0.0)
    return corr, st * st


def bcd_epochs_ref(Xt, Lg, w, fmask, beta, resid, tau, lam_b, n_epochs):
    """Batched cyclic-BCD oracle: a per-lambda ``lax.scan`` over groups.

    The per-group update is line-for-line
    :func:`repro.core.solver.bcd_epochs` (the solver's XLA path), applied
    independently per lambda b — the fused kernel must match this
    BIT-exactly in f64 interpret mode.  This oracle scans every slot;
    ``bcd_epochs`` does not visit the slots past the chunk holding the
    last live group, whose updates are exact no-ops.  ``Xt (Gb, n, ng)``, ``Lg``/``w``
    ``(Gb,)``, ``fmask``/``beta`` ``(B, Gb, ng)``, ``resid (B, n)``,
    ``lam_b (B,)``.
    """
    live = (Lg > 0).astype(beta.dtype)
    safe_L = jnp.where(Lg > 0, Lg, 1.0)

    def one_lambda(bb, rr, fm, lam_):
        step = lam_ / safe_L
        thr1 = tau * step
        thr2 = (1.0 - tau) * w * step

        def group_update(resid, inputs):
            Xg, bg, L, t1, t2, m, lv = inputs
            grad_step = (Xg.T @ resid) / L
            z = (bg + grad_step) * m
            z = jnp.sign(z) * jnp.maximum(jnp.abs(z) - t1, 0.0)
            nrm = jnp.linalg.norm(z)
            z = jnp.maximum(1.0 - t2 / jnp.maximum(nrm, 1e-30), 0.0) * z
            new_bg = jnp.where(lv > 0, z, bg)
            resid = resid + Xg @ (bg - new_bg)
            return resid, new_bg

        def epoch(carry, _):
            bb, rr = carry
            rr, bb = jax.lax.scan(
                group_update, rr, (Xt, bb, safe_L, thr1, thr2, fm, live)
            )
            return (bb, rr), None

        (bb, rr), _ = jax.lax.scan(epoch, (bb, rr), None, length=n_epochs)
        return bb, rr

    outs = [one_lambda(beta[b], resid[b], fmask[b], lam_b[b])
            for b in range(beta.shape[0])]
    return (jnp.stack([o[0] for o in outs]),
            jnp.stack([o[1] for o in outs]))


def bcd_epochs_logistic_ref(Xt, Lg, w, fmask, beta, z, y, tau, lam_b,
                            n_epochs):
    """Batched majorized-BCD oracle for the logistic mega-kernel.

    The per-group update is line-for-line
    :func:`repro.core.solver.bcd_epochs_loss` with ``LogisticLoss``
    (majorization bound ``Lg / 4``, fresh ``rho = y - sigmoid(z)`` per
    group, rank-one linear-predictor update), applied independently per
    lambda — the fused logistic kernel must match BIT-exactly in f64
    interpret mode.  This oracle scans every slot; ``bcd_epochs_loss``
    does not visit the slots past the chunk holding the last live group.  ``z (B, n)`` is the linear predictor carry.
    """
    live = (Lg > 0).astype(beta.dtype)
    Lmaj = 0.25 * Lg
    safe_L = jnp.where(Lg > 0, Lmaj, 1.0)

    def one_lambda(bb, zz, fm, lam_):
        step = lam_ / safe_L
        thr1 = tau * step
        thr2 = (1.0 - tau) * w * step

        def group_update(z, inputs):
            Xg, bg, L, t1, t2, m, lv = inputs
            rho = y - jax.nn.sigmoid(z)
            grad_step = (Xg.T @ rho) / L
            u = (bg + grad_step) * m
            u = jnp.sign(u) * jnp.maximum(jnp.abs(u) - t1, 0.0)
            nrm = jnp.linalg.norm(u)
            u = jnp.maximum(1.0 - t2 / jnp.maximum(nrm, 1e-30), 0.0) * u
            new_bg = jnp.where(lv > 0, u, bg)
            z = z + Xg @ (new_bg - bg)
            return z, new_bg

        def epoch(carry, _):
            bb, zz = carry
            zz, bb = jax.lax.scan(
                group_update, zz, (Xt, bb, safe_L, thr1, thr2, fm, live)
            )
            return (bb, zz), None

        (bb, zz), _ = jax.lax.scan(epoch, (bb, zz), None, length=n_epochs)
        return bb, zz

    outs = [one_lambda(beta[b], z[b], fmask[b], lam_b[b])
            for b in range(beta.shape[0])]
    return (jnp.stack([o[0] for o in outs]),
            jnp.stack([o[1] for o in outs]))
