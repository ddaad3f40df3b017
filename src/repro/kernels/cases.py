"""One launch of every Pallas kernel at a problem's shapes, with its twin.

``kernel_cases(n, G, ng, dtype)`` gives, per kernel (keyed by its
``LaunchSpec.name``), the :mod:`repro.kernels.ops` dispatch wrapper the
solver calls, its :mod:`repro.kernels.ref` / plain-XLA twin, and a pure
``key -> args`` builder.  The builder is traceable, so
``jax.eval_shape(case.make_args, key)`` gives the argument shapes without
allocating them (compile rehearsals), while calling it gives data (runs
on the chip).  The arguments mirror what the solver feeds each kernel:
a design with unit-scale columns, block Lipschitz constants that bound
each group's spectral norm, warm-start state and a lambda below
lambda_max.
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

from . import ops as kops
from . import ref as kref


class KernelCase(NamedTuple):
    fn: Callable           # dispatch wrapper, static arguments bound
    ref: Callable          # reference twin with the same signature
    make_args: Callable    # key -> argument tuple (pure jnp)


def _design(key, n, G, ng, dtype):
    """(G, n, ng) group-major design and its Frobenius block constants."""
    Xt = jax.random.normal(key, (G, n, ng), dtype) / jnp.sqrt(
        jnp.asarray(n, dtype))
    Lg = jnp.sum(Xt * Xt, axis=(1, 2))            # >= ||X_g||_2^2
    return Xt, Lg


def kernel_cases(n: int, G: int, ng: int, dtype, *, B: int = 1,
                 n_epochs: int = 2, tau: float = 0.2,
                 lam: float = 0.1) -> Dict[str, KernelCase]:
    p = G * ng

    def corr_args(key):
        k1, k2 = jax.random.split(key)
        return (jax.random.normal(k1, (p, n), dtype),
                jax.random.normal(k2, (n,), dtype))

    def group_args(key):
        k1, k2 = jax.random.split(key)
        x = jax.random.normal(k1, (G, ng), dtype)
        u = jax.random.uniform(k2, (G,), dtype, 0.1, 0.9)
        return x, u, 1.0 - u

    def prox_args(key):
        x, u, _ = group_args(key)
        return x, u, jnp.sqrt(jnp.full((G,), ng, dtype))

    def bcd_args(key):
        k1, k2 = jax.random.split(key)
        Xt, Lg = _design(k1, n, G, ng, dtype)
        w = jnp.sqrt(jnp.full((G,), ng, dtype))
        fmask = jnp.ones((B, G, ng), dtype)
        beta = jnp.zeros((B, G, ng), dtype)
        resid = jnp.broadcast_to(jax.random.normal(k2, (n,), dtype), (B, n))
        lam_b = jnp.full((B,), lam, dtype)
        return Xt, Lg, w, fmask, beta, resid, jnp.asarray(tau, dtype), lam_b

    def logistic_args(key):
        Xt, Lg, w, fmask, beta, resid, tau_, lam_b = bcd_args(key)
        y = (resid[0] > 0).astype(dtype)
        z = jnp.zeros((B, n), dtype)
        return Xt, Lg, w, fmask, beta, z, y, tau_, lam_b

    def bcd_ref(Xt, Lg, w, fmask, beta, resid, tau_, lam_b):
        return kref.bcd_epochs_ref(Xt, Lg, w, fmask, beta, resid, tau_,
                                   lam_b, n_epochs)

    def logistic_ref(Xt, Lg, w, fmask, beta, z, y, tau_, lam_b):
        return kref.bcd_epochs_logistic_ref(Xt, Lg, w, fmask, beta, z, y,
                                            tau_, lam_b, n_epochs)

    return {
        "screening_corr": KernelCase(
            kops.screening_corr, lambda Xt, th: Xt @ th, corr_args),
        "screening_scores": KernelCase(
            lambda Xt, th: kops.screening_scores(Xt, th, tau),
            lambda Xt, th: kref.screening_scores_ref(Xt, th, tau),
            corr_args),
        "dual_norm": KernelCase(
            kops.dual_norm_groups, kref.dual_norm_ref, group_args),
        "sgl_prox": KernelCase(
            lambda b, s, w: kops.sgl_prox(b, s, w, tau, lam),
            lambda b, s, w: kref.sgl_prox_ref(b, s, w, tau, lam),
            prox_args),
        "bcd_epoch": KernelCase(
            lambda *a: kops.bcd_epochs_fused(*a, n_epochs=n_epochs),
            bcd_ref, bcd_args),
        "bcd_epoch_logistic": KernelCase(
            lambda *a: kops.bcd_epochs_logistic_fused(*a,
                                                      n_epochs=n_epochs),
            logistic_ref, logistic_args),
    }
