"""Burdakov's epsilon-norm and the paper's Algorithm 1 (Lambda(x, alpha, R)).

The epsilon-norm ||x||_eps is the unique nu >= 0 solving

    sum_i S_{(1-eps) nu}(x_i)^2 = (eps nu)^2            (paper Eq. 16/17)

and more generally ``Lambda(x, alpha, R)`` is the unique nu >= 0 solving

    sum_i S_{nu alpha}(x_i)^2 = (nu R)^2                (paper Prop. 9)

so ``||x||_eps = Lambda(x, 1 - eps, eps)``.

Two implementations are provided:

* :func:`lam` — the exact sorted prefix-sum algorithm (paper Algorithm 1),
  vectorised so a whole batch of groups is handled by one ``jnp.sort`` over
  the trailing axis.  O(d log d) per group, exact.
* :func:`lam_bisect` — a fixed-iteration bisection on the monotone function
  g(nu) = sum S_{nu alpha}(x)^2 - (nu R)^2.  All operations are elementwise
  (TPU-friendly, no sort); ``n_iter=80`` reaches f32/f64 machine precision.
  This is the formulation the Pallas kernel uses.

Both operate on the *absolute values* of x (the equation only depends on
|x_i|), accept arbitrary leading batch dimensions, and treat x == 0 rows by
returning 0 (the natural continuous extension: ||0||_eps = 0).

Special cases (paper Algorithm 1):
    alpha = 0, R = 0  ->  +inf (excluded upstream; Omega not a norm there)
    alpha = 0         ->  ||x|| / R
    R = 0             ->  ||x||_inf / alpha
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .precision import one_minus

__all__ = [
    "lam",
    "lam_bisect",
    "epsilon_norm",
    "epsilon_norm_dual",
    "epsilon_decomposition",
]


def _lam_sorted_core(ax: jax.Array, alpha: jax.Array, R: jax.Array) -> jax.Array:
    """Generic-case Lambda via the sorted prefix-sum search.

    ``ax``: |x| with shape (..., d);  alpha, R broadcastable to (...,).
    Assumes alpha > 0 and R > 0 (callers handle the special cases).
    """
    d = ax.shape[-1]
    dtype = ax.dtype
    alpha = jnp.asarray(alpha, dtype)[..., None]  # (..., 1)
    R = jnp.asarray(R, dtype)[..., None]

    xs = jnp.sort(ax, axis=-1)[..., ::-1]  # descending: x_(1) >= ... >= x_(d)
    S = jnp.cumsum(xs, axis=-1)            # S_k  = sum_{j<=k} x_(j)
    S2 = jnp.cumsum(xs * xs, axis=-1)      # S2_k = sum_{j<=k} x_(j)^2
    k = jnp.arange(1, d + 1, dtype=dtype)

    # B(k) = g(x_(k)/alpha) / alpha^2 where g(nu) = sum S_{nu alpha}(x)^2:
    #   B(k) = S2_k / x_(k)^2 - 2 S_k / x_(k) + k
    # B is nondecreasing in k, B(1) = 0.  The bucket j0 is the largest k with
    # alpha^2 B(k) <= R^2 and x_(k) > 0 (zero entries can never be active).
    safe = jnp.where(xs > 0, xs, 1.0)
    B = jnp.where(xs > 0, S2 / (safe * safe) - 2.0 * S / safe + k, jnp.inf)
    target = (R / alpha) ** 2
    j0 = jnp.sum((B <= target) & (xs > 0), axis=-1)  # (...,) in [1, d]
    j0 = jnp.maximum(j0, 1)  # x != 0 guaranteed by caller
    idx = j0 - 1

    Sj = jnp.take_along_axis(S, idx[..., None], axis=-1)
    S2j = jnp.take_along_axis(S2, idx[..., None], axis=-1)
    j0f = j0[..., None].astype(dtype)

    # Solve (alpha^2 j0 - R^2) nu^2 - 2 alpha S_j0 nu + S2_j0 = 0 on the
    # bucket; the valid root is nu_1 (paper Eq. 36), except the degenerate
    # linear case alpha^2 j0 = R^2.
    a = alpha * alpha * j0f - R * R
    disc = alpha * alpha * Sj * Sj - S2j * a
    disc = jnp.maximum(disc, 0.0)
    linear = S2j / (2.0 * alpha * Sj)
    # For a != 0 use the stable ratio form: nu1 = S2j / (alpha Sj + sqrt(disc))
    # (equivalent to (alpha Sj - sqrt(disc)) / a, but avoids cancellation and
    # is well-behaved for a < 0 too).
    quad = S2j / (alpha * Sj + jnp.sqrt(disc))
    nu = jnp.where(jnp.abs(a) < jnp.finfo(dtype).tiny * 8, linear, quad)
    return nu[..., 0]


@functools.partial(jax.jit, static_argnames=())
def lam(x: jax.Array, alpha: jax.Array, R: jax.Array) -> jax.Array:
    """Exact Lambda(x, alpha, R) (paper Algorithm 1), batched over leading dims.

    x: (..., d); alpha, R: scalars or broadcastable to x.shape[:-1].
    Returns shape x.shape[:-1].
    """
    x = jnp.asarray(x)
    ax = jnp.abs(x)
    dtype = ax.dtype
    batch_shape = ax.shape[:-1]
    alpha = jnp.broadcast_to(jnp.asarray(alpha, dtype), batch_shape)
    R = jnp.broadcast_to(jnp.asarray(R, dtype), batch_shape)

    l2 = jnp.linalg.norm(ax, axis=-1)
    linf = jnp.max(ax, axis=-1)

    # Guard degenerate inputs for the generic branch.
    safe_alpha = jnp.where(alpha > 0, alpha, 1.0)
    safe_R = jnp.where(R > 0, R, 1.0)
    generic = _lam_sorted_core(ax, safe_alpha, safe_R)

    out = generic
    out = jnp.where(R == 0, linf / safe_alpha, out)
    out = jnp.where(alpha == 0, l2 / safe_R, out)
    out = jnp.where((alpha == 0) & (R == 0), jnp.inf, out)
    out = jnp.where(linf == 0, 0.0, out)  # x == 0 row
    return out


@functools.partial(jax.jit, static_argnames=("n_iter",))
def lam_bisect(
    x: jax.Array, alpha: jax.Array, R: jax.Array, n_iter: int = 80
) -> jax.Array:
    """Lambda(x, alpha, R) by fixed-iteration bisection (TPU-friendly form).

    g(nu) = sum_i S_{nu alpha}(x_i)^2 - (nu R)^2 is continuous and strictly
    decreasing-through-zero on (0, ||x||_inf / alpha); the root lies in
    [||x||_inf / (alpha + R), ||x||_inf / alpha] (paper, App. proof of Prop 9).
    """
    x = jnp.asarray(x)
    ax = jnp.abs(x)
    dtype = ax.dtype
    batch_shape = ax.shape[:-1]
    alpha = jnp.broadcast_to(jnp.asarray(alpha, dtype), batch_shape)
    R = jnp.broadcast_to(jnp.asarray(R, dtype), batch_shape)

    l2 = jnp.linalg.norm(ax, axis=-1)
    linf = jnp.max(ax, axis=-1)

    safe_alpha = jnp.where(alpha > 0, alpha, 1.0)
    safe_R = jnp.where(R > 0, R, 1.0)

    lo = linf / (safe_alpha + safe_R)
    hi = linf / safe_alpha

    def g(nu):
        st = jnp.maximum(ax - (nu * safe_alpha)[..., None], 0.0)
        return jnp.sum(st * st, axis=-1) - (nu * safe_R) ** 2

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        gm = g(mid)
        lo = jnp.where(gm > 0, mid, lo)
        hi = jnp.where(gm > 0, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, n_iter, body, (lo, hi))
    out = 0.5 * (lo + hi)
    out = jnp.where(R == 0, linf / safe_alpha, out)
    out = jnp.where(alpha == 0, l2 / safe_R, out)
    out = jnp.where((alpha == 0) & (R == 0), jnp.inf, out)
    out = jnp.where(linf == 0, 0.0, out)
    return out


def epsilon_norm(x: jax.Array, eps: jax.Array) -> jax.Array:
    """||x||_eps = Lambda(x, 1 - eps, eps)  (paper Eq. 16)."""
    eps = jnp.asarray(eps, jnp.asarray(x).dtype)
    return lam(x, one_minus(eps), eps)


def epsilon_norm_dual(x: jax.Array, eps: jax.Array) -> jax.Array:
    """Dual of the eps-norm: eps ||x|| + (1 - eps) ||x||_1  (paper Lemma 4)."""
    x = jnp.asarray(x)
    eps = jnp.asarray(eps, x.dtype)
    return eps * jnp.linalg.norm(x, axis=-1) + one_minus(eps) * jnp.sum(
        jnp.abs(x), axis=-1
    )


def epsilon_decomposition(x: jax.Array, eps: jax.Array):
    """x = x_eps + x_{1-eps} with ||x_eps|| = eps||x||_e, ||x_{1-eps}||_inf =
    (1-eps)||x||_e  (paper Lemma 1). Returns (x_eps, x_one_minus_eps, nu)."""
    x = jnp.asarray(x)
    nu = epsilon_norm(x, eps)
    thr = (one_minus(eps) * nu)[..., None]
    x_eps = jnp.sign(x) * jnp.maximum(jnp.abs(x) - thr, 0.0)
    return x_eps, x - x_eps, nu
