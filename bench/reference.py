"""Plain sparse-group lasso reference, in NumPy on the host.

Written from the paper (Ndiaye, Fercoq, Gramfort, Salmon, NIPS 2016) and
independent of the system under test: it imports nothing of it and takes
nothing it made.  Every function takes a ``dtype``: the benchmark runs it
in IEEE float64; its control runs it in float32.

Problem, with groups of ``ng`` contiguous features and ``w_g = sqrt(ng)``:

    P(beta)  = 1/2 ||y - X beta||^2 + lam Omega(beta)
    Omega    = tau ||beta||_1 + (1 - tau) sum_g w_g ||beta_g||
    D(theta) = 1/2 ||y||^2 - lam^2 / 2 ||theta - y / lam||^2
    Omega^D(xi) = max_g ||xi_g||_{eps_g} / (tau + (1 - tau) w_g),
        eps_g = (1 - tau) w_g / (tau + (1 - tau) w_g)
    theta    = r / max(lam, Omega^D(X^T r)),  r = y - X beta

The epsilon-norm ||x||_eps is the nu >= 0 with
sum_i (|x_i| - (1 - eps) nu)_+^2 = (eps nu)^2.
"""
from __future__ import annotations

import numpy as np


def epsilon_norm(x: np.ndarray, eps: np.ndarray) -> np.ndarray:
    """||x_g||_{eps_g} for each row of ``x`` (G, d); ``eps`` is (G,)."""
    a = -np.sort(-np.abs(x), axis=1)                 # descending
    alpha = (1 - eps)[:, None]
    ratio2 = (eps[:, None] / alpha) ** 2
    s1 = np.cumsum(a, axis=1) - a                    # sum of the k-1 larger
    s2 = np.cumsum(a * a, axis=1) - a * a
    k = np.arange(a.shape[1], dtype=a.dtype)[None, :]  # k - 1
    # h(nu) = sum_i (a_i - alpha nu)_+^2 - (eps nu)^2 falls strictly in
    # nu.  At nu = a_k / alpha it reads alpha^2-free:
    # s2 - 2 a_k s1 + (k - 1) a_k^2 - ratio2 a_k^2.  The root lies past
    # every breakpoint where h <= 0, so their count is the active size.
    h = s2 - 2 * a * s1 + k * a * a - ratio2 * a * a
    m = np.maximum(np.sum((h <= 0) & (a > 0), axis=1), 1)
    S1 = np.take_along_axis(np.cumsum(a, axis=1), (m - 1)[:, None], 1)[:, 0]
    S2 = np.take_along_axis(np.cumsum(a * a, axis=1), (m - 1)[:, None], 1)[:, 0]
    al, e, mf = alpha[:, 0], eps, m.astype(a.dtype)
    # (m al^2 - e^2) nu^2 - 2 al S1 nu + S2 = 0; the smaller positive root.
    quad = mf * al * al - e * e
    disc = np.maximum(al * al * S1 * S1 - quad * S2, 0)
    nu = S2 / np.where(S1 > 0, al * S1 + np.sqrt(disc), 1)
    return np.where(a[:, 0] > 0, nu, 0).astype(x.dtype)


class Problem:
    """One design and response, held in ``dtype`` on the host."""

    def __init__(self, X, y, ng: int, tau: float, dtype=np.float64):
        self.dtype = np.dtype(dtype)
        self.X = np.ascontiguousarray(X, dtype=self.dtype)
        self.y = np.asarray(y, dtype=self.dtype)
        self.n, p = self.X.shape
        self.ng = int(ng)
        self.G = p // self.ng
        if self.G * self.ng != p:
            raise ValueError(f"p={p} is not a whole number of groups of {ng}")
        one = self.dtype.type(1)
        self.tau = self.dtype.type(tau)
        self.w = np.full(self.G, np.sqrt(self.dtype.type(self.ng)), self.dtype)
        self.denom = self.tau + (one - self.tau) * self.w
        self.eps = (one - self.tau) * self.w / self.denom

    def corr(self, r: np.ndarray) -> np.ndarray:
        return (self.X.T @ r).reshape(self.G, self.ng)

    def dual_norm_terms(self, xi: np.ndarray) -> np.ndarray:
        return epsilon_norm(xi, self.eps) / self.denom

    def omega(self, beta: np.ndarray) -> np.ndarray:
        one = self.dtype.type(1)
        return (self.tau * np.abs(beta).sum()
                + (one - self.tau) * (self.w * np.linalg.norm(beta, axis=1)).sum())

    def resid(self, beta: np.ndarray) -> np.ndarray:
        beta = np.asarray(beta, self.dtype)
        on = np.flatnonzero(np.any(beta != 0, axis=1))
        if on.size == 0:
            return self.y.copy()
        cols = (on[:, None] * self.ng + np.arange(self.ng)).ravel()
        return self.y - self.X[:, cols] @ beta[on].ravel()

    def gap(self, beta: np.ndarray, lam: float, r=None, terms=None):
        """Duality gap of ``beta`` at ``lam``, with the paper's dual point."""
        beta = np.asarray(beta, self.dtype)
        lam = self.dtype.type(lam)
        r = self.resid(beta) if r is None else r
        if terms is None:
            terms = self.dual_norm_terms(self.corr(r))
        theta = r / max(lam, terms.max())
        half = self.dtype.type(0.5)
        primal = half * (r @ r) + lam * self.omega(beta)
        d = theta - self.y / lam
        dual = half * (self.y @ self.y) - half * lam * lam * (d @ d)
        return primal - dual

    def lambda_max(self) -> float:
        return self.dual_norm_terms(self.corr(self.y)).max()


def lambda_grid(lam_max: float, T: int, delta: float, count: int):
    """The first ``count`` of lam_max * 10^(-delta t / (T - 1))."""
    t = np.arange(count)
    return lam_max * 10.0 ** (-delta * t / (T - 1))


def solve_path(prob: Problem, lambdas, tol: float, max_iter: int = 50_000,
               check_every: int = 10, strict: bool = True):
    """Unscreened solve of each ``lambdas`` point to a gap <= ``tol``.

    Proximal gradient (FISTA) on a working set of groups.  The working set
    is grown from the groups whose zero optimality condition fails at the
    current dual point; no rule discards a group, and every gap is the
    gap of the whole problem.  Warm-started down the grid.  Returns
    ``(betas (T, G, ng), gaps (T,))``.  A point that has not reached
    ``tol`` after ``max_iter`` raises, or with ``strict=False`` is
    returned as it stands.
    """
    dt = prob.dtype
    beta = np.zeros((prob.G, prob.ng), dt)
    work: list[int] = []
    betas, gaps = [], []
    for lam in np.asarray(lambdas, dt):
        spent = 0
        while True:
            r = prob.resid(beta)
            terms = prob.dual_norm_terms(prob.corr(r))
            g = prob.gap(beta, lam, r=r, terms=terms)
            if g <= tol:
                break
            if spent >= max_iter:
                if strict:
                    raise RuntimeError(f"reference: gap {g:.3e} > {tol:g} at "
                                       f"lam={lam:g} after {spent} iterations")
                break
            inwork = np.zeros(prob.G, bool)
            inwork[work] = True
            viol = np.flatnonzero(~inwork & (terms > lam))
            viol = viol[np.argsort(-terms[viol])][: max(8, len(work))]
            work.extend(int(v) for v in viol)
            spent += _fista(prob, beta, work, lam, tol / 4,
                            max_iter - spent, check_every)
        betas.append(beta.copy())
        gaps.append(float(g))
    return np.stack(betas), np.asarray(gaps)


def _fista(prob, beta, work, lam, tol, max_iter, check_every):
    """FISTA with gradient restart on the groups in ``work``, the others
    held at zero, until the working set's own gap is <= ``tol``.  Updates
    ``beta`` in place and returns the iterations run."""
    if not work:
        return max_iter
    dt = prob.dtype
    one, half = dt.type(1), dt.type(0.5)
    idx = np.asarray(work)
    ng = prob.ng
    cols = (idx[:, None] * ng + np.arange(ng)).ravel()
    XW = np.ascontiguousarray(prob.X[:, cols])
    Q = XW.T @ XW
    q = XW.T @ prob.y
    lip = dt.type(np.linalg.eigvalsh(Q)[-1])
    l1 = prob.tau * lam / lip
    l2 = ((one - prob.tau) * prob.w[idx] * lam / lip)[:, None]
    eps, denom, w = prob.eps[idx], prob.denom[idx], prob.w[idx]

    def prox(v):
        v = v.reshape(len(idx), ng)
        st = np.sign(v) * np.maximum(np.abs(v) - l1, 0)
        nrm = np.linalg.norm(st, axis=1, keepdims=True)
        shrink = np.maximum(one - l2 / np.where(nrm > 0, nrm, one), 0)
        return (st * np.where(nrm > 0, shrink, 0)).ravel()

    b = beta[idx].ravel()
    z, t = b.copy(), one
    it = 0
    while it < max_iter:
        nb = prox(z - (Q @ z - q) / lip)
        it += 1
        if (z - nb) @ (nb - b) > 0:          # momentum points uphill
            z, t = nb, one
        else:
            nt = (one + np.sqrt(one + 4 * t * t)) * half
            z, t = nb + ((t - one) / nt) * (nb - b), nt
        b = nb
        if it % check_every == 0:
            r = prob.y - XW @ b
            bw = b.reshape(len(idx), ng)
            terms = epsilon_norm((XW.T @ r).reshape(len(idx), ng), eps) / denom
            theta = r / max(lam, terms.max())
            om = (prob.tau * np.abs(bw).sum()
                  + (one - prob.tau) * (w * np.linalg.norm(bw, axis=1)).sum())
            d = theta - prob.y / lam
            g = (half * (r @ r) + lam * om
                 - half * (prob.y @ prob.y) + half * lam * lam * (d @ d))
            if g <= tol:
                break
    beta[idx] = b.reshape(len(idx), ng)
    return it
