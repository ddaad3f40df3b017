"""Distributed SGL solver: FISTA + GAP safe screening under shard_map.

The paper's BCD is inherently sequential over groups; the parallel-safe
variant is proximal gradient (ISTA/FISTA) with the *global* Lipschitz
constant L = ||X||_2^2, which updates every group simultaneously — each
model-shard owns a slice of the groups, each data-shard a slice of the rows.

Communication pattern per FISTA step (see DESIGN.md §5):
    grad   = X^T resid          local matmul + psum over "data"
    prox   = two-level ST       local (Pallas kernel on TPU)
    resid  = y - X beta         local matmul + psum over "model"
Screening round (every f_ce steps):
    dual norm Omega^D           local eps-norms + pmax over "model"
    gap / primal / dual         scalar psums
    masks (Thm 1)               local per group shard

Screened groups stay in place but are masked (zero columns contribute
nothing); a host-side *rebalance* (launch/train.py --elastic) periodically
compacts surviving groups across shards — safe because certificates are
permanent.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.sgl import epsilons, group_weight_total, soft_threshold
from repro.core.epsilon_norm import lam as lam_exact
from repro.core.precision import one_minus


class DistKernels(NamedTuple):
    fista: object          # one FISTA step, single lambda
    screen: object         # certified GAP screen round (Thm 1-2)
    norms: object          # column/group norms of X (compute once)
    fista_batch: object    # batched-lambda FISTA (path points in parallel)
    place: object          # (X, y, w) -> the same arrays laid out on the mesh


class DistSGLState(NamedTuple):
    beta: jax.Array       # (G, ng) sharded P("model", None)
    z: jax.Array          # FISTA momentum iterate
    t: jax.Array          # FISTA momentum scalar
    feat_mask: jax.Array  # (G, ng) float — 0 for screened/padded
    group_mask: jax.Array # (G,) float
    gap: jax.Array
    step: jax.Array


def _dp_axes(multi_pod):
    return ("pod", "data") if multi_pod else ("data",)


def make_dist_step(mesh: Mesh, *, tau: float, multi_pod: bool = False,
                   f32=jnp.float32):
    """Builds (init_fn, fista_step, screen_step) shard_mapped on ``mesh``.

    Arrays: X (n, G, ng), y (n,), w (G,), Lg global Lipschitz scalar.
    """
    dp = _dp_axes(multi_pod)
    xspec = P(dp, "model", None)
    yspec = P(dp)
    gspec = P("model", None)
    sspec = P("model")
    bspec_g = P(None, "model", None)   # (B, G_l, ng) batched-lambda state

    def local_corr(X, v):
        # X (n_l, G_l, ng) v (n_l,) -> psum over data
        # f32 accumulation so a bf16 X (mixed-precision FISTA) keeps
        # full-precision partial sums
        c = jnp.einsum("ngk,n->gk", X, v.astype(X.dtype),
                       preferred_element_type=jnp.promote_types(
                           X.dtype, jnp.float32))
        return jax.lax.psum(c, dp)

    def local_matvec(X, b):
        r = jnp.einsum("ngk,gk->n", X, b.astype(X.dtype),
                       preferred_element_type=jnp.promote_types(
                           X.dtype, jnp.float32))
        return jax.lax.psum(r, "model")

    # --- FISTA step (jit over shard_map) ---
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(xspec, yspec, gspec, gspec, gspec, sspec, P(), P(), P()),
        out_specs=(gspec, gspec, P()),
        check_vma=False,
    )
    def fista_kernel(X, y, beta, z, feat_mask, w, t, lam_, L):
        resid = y - local_matvec(X, z)
        grad = -local_corr(X, resid)                    # (G_l, ng)
        u = (z - grad / L) * feat_mask
        # two-level prox at step 1/L
        a = soft_threshold(u, tau * lam_ / L)
        thr = (one_minus(tau) * lam_ * w / L)[:, None]
        nrm = jnp.linalg.norm(a, axis=-1, keepdims=True)
        scale = jnp.maximum(one_minus(thr / jnp.maximum(nrm, 1e-30)), 0.0)
        beta_new = scale * a * feat_mask
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        z_new = beta_new + ((t - 1.0) / t_new) * (beta_new - beta)
        return beta_new, z_new, t_new

    # --- batched-lambda FISTA: solve B path points simultaneously.
    # The matvec becomes a matmul with B columns — arithmetic intensity
    # scales by B, the lever that moves this memory-bound workload toward
    # the compute roofline (§Perf iteration 3 on the sgl-paper cell). ---
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(xspec, yspec, bspec_g, bspec_g, bspec_g, sspec,
                  P(), P(), P()),
        out_specs=(bspec_g, bspec_g, P()),
        check_vma=False,
    )
    def fista_batch_kernel(X, y, beta, z, feat_mask, w, t, lam_, L):
        """beta/z/feat_mask: (B, G_l, ng); lam_/t: (B,)."""
        # resid (B, n_l): one X read serves all B lambdas
        acc = jnp.promote_types(X.dtype, jnp.float32)
        r = jnp.einsum("ngk,bgk->bn", X, z.astype(X.dtype),
                       preferred_element_type=acc)
        resid = y[None, :] - jax.lax.psum(r, "model")
        g = jnp.einsum("ngk,bn->bgk", X, resid.astype(X.dtype),
                       preferred_element_type=acc)
        grad = -jax.lax.psum(g, dp)
        u = (z - grad / L) * feat_mask
        step = (lam_ / L)[:, None, None]
        a = soft_threshold(u, tau * step)
        thr = one_minus(tau) * step * w[None, :, None]
        nrm = jnp.linalg.norm(a, axis=-1, keepdims=True)
        scale = jnp.maximum(one_minus(thr / jnp.maximum(nrm, 1e-30)), 0.0)
        beta_new = scale * a * feat_mask
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        z_new = beta_new + ((t - 1.0) / t_new)[:, None, None] * (
            beta_new - beta)
        return beta_new, z_new, t_new

    # --- design-matrix norms (constants of the problem; computed ONCE at
    # setup — hoisting these two full passes over X out of every screening
    # round was §Perf iteration 1 on the sgl-paper cell) ---
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(xspec,),
        out_specs=(gspec, sspec),
        check_vma=False,
    )
    def norms_kernel(X):
        accn = jnp.promote_types(X.dtype, jnp.float32)
        colnorm = jax.lax.psum(
            jnp.einsum("ngk,ngk->gk", X, X,
                       preferred_element_type=accn), dp) ** 0.5
        # ||X_g||_2 <= ||X_g||_F: Frobenius is a safe (over-)estimate, so
        # the screening ball bound (Thm 1) stays valid without a
        # distributed power iteration
        gfro = jnp.sqrt(jax.lax.psum(
            jnp.sum((X * X).astype(accn), axis=(0, 2)), dp))
        return colnorm, gfro

    # --- screening round ---
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(xspec, yspec, gspec, gspec, sspec, gspec, sspec,
                  P(), P()),
        out_specs=(gspec, sspec, P(), P()),
        check_vma=False,
    )
    def screen_kernel(X, y, beta, feat_mask, w, colnorm, gfro, lam_, ynorm2):
        """GAP sphere + Theorem-1 tests, fully sharded.

        Returns (feat_mask, group_mask, gap, theta_scale).
        """
        resid = y - local_matvec(X, beta)
        corr = local_corr(X, resid)                     # (G_l, ng), full rows

        eps = epsilons(tau, w)
        scale_g = group_weight_total(tau, w)
        per_group = lam_exact(corr, one_minus(eps), eps) / scale_g
        dual_norm = jax.lax.pmax(jnp.max(per_group), "model")
        sc = jnp.maximum(lam_, dual_norm)

        # primal / dual / gap (resid is replicated across model shards;
        # beta terms psum over model)
        fit = 0.5 * jnp.sum(resid * resid)
        l1 = jax.lax.psum(jnp.sum(jnp.abs(beta)), "model")
        l2 = jax.lax.psum(jnp.sum(w * jnp.linalg.norm(beta, axis=-1)),
                          "model")
        # row shards: fit must also psum over data
        fit = jax.lax.psum(fit, dp)
        primal = fit + lam_ * (tau * l1 + one_minus(tau) * l2)
        ydist = jax.lax.psum(
            jnp.sum((resid / sc - y / lam_) ** 2), dp
        )
        dual_val = 0.5 * ynorm2 - 0.5 * lam_ * lam_ * ydist
        gap = jnp.maximum(primal - dual_val, 0.0)
        r = jnp.sqrt(2.0 * gap) / lam_

        # Theorem 1 tests on theta = resid / sc
        corr_t = corr / sc
        st = soft_threshold(corr_t, tau)
        st_norm = jnp.linalg.norm(st, axis=-1)
        inf_norm = jnp.max(jnp.abs(corr_t), axis=-1)
        Tg = jnp.where(
            inf_norm > tau,
            st_norm + r * gfro,
            jnp.maximum(inf_norm + r * gfro - tau, 0.0),
        )
        gmask = (Tg >= one_minus(tau) * w).astype(X.dtype)
        fmask = (
            (jnp.abs(corr_t) + r * colnorm >= tau).astype(X.dtype)
            * gmask[:, None]
            * feat_mask
        )
        return fmask, gmask, gap, sc

    def place(X, y, w):
        """Lay the problem out on the mesh once, in the kernels' in_specs;
        an array left on one device would be resharded on every call."""
        return tuple(jax.device_put(a, NamedSharding(mesh, spec))
                     for a, spec in ((X, xspec), (y, yspec), (w, sspec)))

    return DistKernels(fista=fista_kernel, screen=screen_kernel,
                       norms=norms_kernel, fista_batch=fista_batch_kernel,
                       place=place)


def solve_distributed(
    mesh: Mesh,
    X, y, w,
    *,
    tau: float,
    lam_: float,
    L: float,
    multi_pod: bool = False,
    tol: float = 1e-6,
    max_steps: int = 2000,
    f_ce: int = 10,
):
    """Host driver: FISTA with screening every f_ce steps on a live mesh.

    .. deprecated::
        Thin wrapper over the session API — the raw-array signature became
        ``SGLSession(problem_from_grouped(X, y, tau, w), mesh=mesh)``::

            from repro.core import SGLSession, SolverConfig, problem_from_grouped
            session = SGLSession(problem_from_grouped(X, y, tau=tau, w=w),
                                 SolverConfig(tol=tol, max_epochs=max_steps,
                                              f_ce=f_ce),
                                 mesh=mesh, L=L)
            res = session.solve(lam_)

        The session form additionally exposes ``solve_path`` (sequential
        certificates + batched-lambda FISTA on the mesh) and ``screen``.

    Returns the legacy tuple ``(beta, gap, gaps, feat_mask)``.
    """
    import warnings

    from repro.core.session import SGLSession, SolverConfig
    from repro.core.sgl import problem_from_grouped

    warnings.warn(
        "solve_distributed() is deprecated; use "
        "SGLSession(problem_from_grouped(...), mesh=mesh).solve(lam_)",
        DeprecationWarning, stacklevel=2,
    )
    problem = problem_from_grouped(X, y, tau=tau, w=w)
    cfg = SolverConfig(tol=tol, max_epochs=max_steps, f_ce=f_ce)
    session = SGLSession(problem, cfg, mesh=mesh, multi_pod=multi_pod, L=L)
    res = session.solve(lam_)
    feat_mask = jnp.asarray(res.feat_active, problem.X.dtype)
    return res.beta, float(res.gap), res.gap_history, feat_mask


# ----------------------------------------------------------------------------
# Static-analysis hook: the mesh kernels are built per-mesh, so the factory
# itself is registered; the analysis template instantiates it on the (1, 1)
# test mesh (repro.analysis.entrypoints, dist_fista/* specs).
# ----------------------------------------------------------------------------

from ..analysis.registry import register_traceable  # noqa: E402

register_traceable("dist_step_factory", make_dist_step,
                   module=__name__, kind="factory")
