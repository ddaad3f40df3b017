"""Quickstart: solve one Sparse-Group Lasso instance with GAP safe screening.

    PYTHONPATH=src python examples/quickstart.py

Reproduces the paper's core loop on a small synthetic instance through the
**session API**: builds the problem, opens an :class:`SGLSession` (which
owns the solver configuration, the screening backend, and the persistent
transposed design for the Pallas kernels), computes lambda_max via the
epsilon-norm trick (Eq. 22), solves at lambda = lambda_max / 20 with
Algorithm 2 (ISTA-BC + GAP safe rules), and reports the duality gap, the
screening statistics, and support recovery.

Migration note: the legacy ``solve(problem, lam, tol=..., rule=..., ...)``
kwargs became :class:`SolverConfig` fields with the same names (``tol``,
``max_epochs``, ``f_ce``, ``rule``, ``compact``, ``inner_rounds``,
``check_every``, ``screen_backend``, ``warm_gap_factor``); the lambda and
warm-start state stay on ``session.solve(lam, beta0=...)``.

Migration note (rule objects): ``SolverConfig.rule`` now takes a
:mod:`repro.rules` **strategy object** — ``rule=GapSafeRule()`` below —
with string names (``"gap"``, ``"static"``, ``"dynamic"``, ``"dst3"``,
``"none"``, ``"strong"``) kept as registry aliases resolving to the same
singletons, bit-identically for ``"gap"``.  Unknown names now fail at
session construction with the registered list.  New rule families
subclass :class:`repro.rules.ScreeningRule` (one sphere construction) and
``register_rule`` themselves — the solver, the path engine, and the
Fig. 2/3 sweep harness (``benchmarks/sweep_rules.py``) pick them up
unchanged.  Unsafe heuristics (``StrongSequentialRule``) are flagged:
their rounds carry ``safe=False`` and paths ``certificates_safe=False``.

``SolverConfig.solver_backend`` (new) picks the inner-epoch engine:
``"auto"`` (default) fuses whole BCD epoch blocks into ONE Pallas kernel
launch on TPU (``kernels/bcd_epoch.py`` — VMEM-resident residual, and a
lambda-batch axis that solves coinciding-active-set path points together)
and keeps the ``lax.scan`` reference elsewhere; force ``"pallas"`` /
``"xla"`` to override.  The fused kernel's epoch math is bit-identical to
the scan in f64, so switching is a performance choice, not a numerics one
(the backends' between-block early-exit heuristics can in principle differ
in the last ulp; the CI smoke pins end-to-end equality on its config).
On warm path stretches whose certified active sets coincide, the Pallas
backend additionally batches consecutive lambdas through the kernel's
lambda-batch axis (``solve_path(batch_lambdas=...)``) — results there are
tol-level equivalent, not bit-equal; pass ``batch_lambdas=1`` for exact
per-lambda reproduction.
"""
import os

os.environ.setdefault("JAX_ENABLE_X64", "1")

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import SGLSession, SolverConfig, make_problem
from repro.data.synthetic import make_synthetic
from repro.rules import GapSafeRule


def main():
    enable_compile_cache()
    X, y, beta_true, sizes = make_synthetic(
        n=100, p=1000, n_groups=100, gamma1=5, gamma2=4, seed=0
    )
    problem = make_problem(X, y, sizes, tau=0.2)
    # rule= takes a repro.rules strategy object; the string "gap" remains
    # a registry alias resolving to this same singleton (bit-identical).
    session = SGLSession(problem, SolverConfig(tol=1e-8,
                                               rule=GapSafeRule()))

    lam_max = session.lam_max
    lam = lam_max / 20.0
    print(f"lambda_max = {lam_max:.4f}  (Eq. 22, epsilon-norm Algorithm 1)")
    print(f"solving at lambda = lambda_max/20 = {lam:.4f}, tol = 1e-8")

    res = session.solve(lam)

    G, ng = problem.G, problem.ng
    beta = np.asarray(res.beta).reshape(-1)
    true_groups = {
        g for g in range(G) if np.any(beta_true[g * ng:(g + 1) * ng] != 0)
    }
    found_groups = {
        g for g in range(G) if np.any(np.abs(beta[g * ng:(g + 1) * ng]) > 1e-10)
    }

    print(f"\nconverged: duality gap = {float(res.gap):.3e} "
          f"after {res.n_epochs} BCD epochs "
          f"({session.rounds} certified screening rounds)")
    print(f"active groups at solution: {int(res.group_active.sum())}/{G} "
          f"(GAP rule screened out {G - int(res.group_active.sum())})")
    print(f"active features: {int(res.feat_active.sum())}/{G * ng}")
    print(f"true support: {sorted(true_groups)}")
    print(f"recovered   : {sorted(found_groups)}")

    # GAP screening is SAFE: no group with a nonzero optimal coefficient
    # may ever be screened out.
    for g in found_groups:
        assert res.group_active[g], f"unsafe screen of group {g}!"
    print("\nsafety check passed: every nonzero group survived screening")

    # The session is warm: a second solve nearby reuses the gather caches
    # and (on TPU) the persistent transposed design, and can be seeded with
    # a sequential certificate — the paper's sequential screening rule.
    cert = session.screen(lam / 2.0, res.beta)
    res2 = session.solve(lam / 2.0, beta0=res.beta, first_round=cert)
    print(f"warm re-solve at lambda/2: sequential certificate screened "
          f"{G - int(np.asarray(cert.group_active).sum())}/{G} groups "
          f"up front; gap {float(res2.gap):.3e} "
          f"in {res2.n_epochs} epochs")
    assert float(res2.gap) <= 1e-8


if __name__ == "__main__":
    main()
