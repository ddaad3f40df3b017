"""Climate regression (paper Section 7.1, Figures 3-4, reduced scale).

    PYTHONPATH=src python examples/climate_path.py

Fits the Sparse-Group Lasso path on the climate-like dataset (groups = grid
points, 7 physical variables each) through the **session API**, comparing
the GAP safe rule against no screening, and prints the "support map" —
which grid regions predict the target, the paper's Figure 4.

Migration note: the legacy ``solve_path(problem, lambdas=..., tol=...,
rule=..., max_epochs=...)`` kwargs became :class:`SolverConfig` fields of
the same names on an :class:`SGLSession`; the grid stays on
``session.solve_path(lambdas=...)``.  One session per rule keeps each
rule's gather caches (and, on TPU, the persistent transposed design)
across everything that session solves.
"""
import os

os.environ.setdefault("JAX_ENABLE_X64", "1")

import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import SGLSession, SolverConfig, make_problem, lambda_grid
from repro.data.climate import make_climate_like

N_LON, N_LAT = 16, 8


def main():
    enable_compile_cache()
    X, y, beta_true, sizes = make_climate_like(
        n=256, n_lon=N_LON, n_lat=N_LAT, seed=0
    )
    problem = make_problem(X, y, sizes, tau=0.4)  # paper's tau* = 0.4
    sessions = {
        rule: SGLSession(
            problem, SolverConfig(tol=1e-6, rule=rule, max_epochs=2000)
        )
        for rule in ("gap", "none")
    }
    lam_max = sessions["gap"].lam_max
    lambdas = lambda_grid(lam_max, T=20, delta=2.5)

    times = {}
    for rule, session in sessions.items():
        t0 = time.perf_counter()
        res = session.solve_path(lambdas=lambdas)
        times[rule] = time.perf_counter() - t0
        print(f"rule={rule:5s}: path time {times[rule]:7.2f}s, "
              f"total epochs {int(res.epochs.sum())}")
        if rule == "gap":
            print(f"             sequential screen discarded "
                  f"{int(res.seq_screened.sum())} group certificates, "
                  f"{int((res.epochs == 0).sum())}/{len(lambdas)} lambdas "
                  f"needed zero epochs, {res.n_gathers} design gathers, "
                  f"{res.n_rounds} certified rounds "
                  f"({res.n_transpose_copies} transposed copies of X)")
    print(f"GAP speed-up over no screening: "
          f"{times['none'] / times['gap']:.2f}x")

    # Support map at the sparsest informative lambda (Figure 4 analogue).
    # Reusing the "gap" session keeps its caches warm for the partial grid.
    res = sessions["gap"].solve_path(lambdas=lambdas[:8])
    beta = np.asarray(res.betas[-1])          # (G, ng)
    strength = np.abs(beta).max(axis=1).reshape(N_LON, N_LAT)

    print("\nsupport map (max |coef| per grid point; '#'=strong, '.'=zero):")
    q = strength.max() or 1.0
    for j in range(N_LAT - 1, -1, -1):
        row = "".join(
            "#" if strength[i, j] > 0.5 * q
            else "+" if strength[i, j] > 0.05 * q
            else "." for i in range(N_LON)
        )
        print("   " + row)
    n_active = int((strength > 0).sum())
    print(f"\nactive grid points: {n_active}/{N_LON * N_LAT}")


if __name__ == "__main__":
    main()
