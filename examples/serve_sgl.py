"""Serving the sparse-group lasso path solver: start the serve loop,
submit a handful of tenant requests, and watch them coalesce.

    PYTHONPATH=src python examples/serve_sgl.py

Three tenants ask for the identical path (one coalesced solve serves
all three, betas bit-identical to a solo run), a fourth repeats the
request later (served straight from the certificate store, zero solver
work), and a fifth re-solves a perturbed ``y`` on the tail of the grid
(warm-started from the stored path — the stored state seeds the solver
but every screening decision is re-certified by a fresh GAP round, so
the perturbed solve's certificates are its own).
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import sgl
from repro.core.session import SolverConfig, lambda_grid
from repro.data.synthetic import make_synthetic
from repro.serve import PathRequest, ServeConfig, SGLServer


def main():
    enable_compile_cache()
    X, y, _beta, sizes = make_synthetic(
        n=64, p=512, n_groups=64, gamma1=3, gamma2=3, seed=11)
    problem = sgl.make_problem(X, y, sizes, tau=0.3)
    grid = lambda_grid(float(sgl.lambda_max(problem)), T=10, delta=0.5)

    server = SGLServer(ServeConfig(
        default_solver=SolverConfig(tol=1e-7, max_epochs=20_000),
        coalesce_window_s=0.1,
    )).start()
    try:
        # Wave 1: three tenants, identical request -> one solve.
        futs = [server.submit(PathRequest(f"tenant-{i}", problem, grid))
                for i in range(3)]
        wave1 = [f.result(timeout=600) for f in futs]
        for r in wave1:
            print(f"{r.tenant}: served_from={r.served_from} "
                  f"coalesced_n={r.coalesced_n} "
                  f"seq_screened={int(np.sum(r.result.seq_screened))}")
        assert all(np.array_equal(r.result.betas, wave1[0].result.betas)
                   for r in wave1)

        # Wave 2: exact repeat (store hit) + perturbed-y tail re-solve
        # (warm start from the stored path, certificates re-earned).
        rng = np.random.default_rng(0)
        problem2 = sgl.make_problem(
            X, y + 0.02 * rng.standard_normal(y.shape), sizes, tau=0.3)
        repeat = server.submit(PathRequest("tenant-3", problem, grid))
        perturbed = server.submit(
            PathRequest("tenant-4", problem2, grid[len(grid) // 2:]))
        r3, r4 = repeat.result(timeout=600), perturbed.result(timeout=600)
        print(f"{r3.tenant}: served_from={r3.served_from} "
              f"(exact repeat, no solver work)")
        print(f"{r4.tenant}: served_from={r4.served_from} "
              f"warm_started={r4.warm_started} "
              f"warm_source_lam={r4.warm_source_lam} "
              f"certificates_safe={r4.result.certificates_safe}")
        assert r3.store_hit
        assert r4.result.certificates_safe
    finally:
        server.stop()

    stats = server.stats()
    print(f"requests={stats['requests']} "
          f"path_solves={stats['path_solves']} "
          f"coalesced={stats['coalesced_requests']} "
          f"store_served={stats['store_served']} "
          f"warm_started={stats['warm_started']}")
    print(f"session cache: {stats['cache']}")
    print(f"certificate store: {stats['store']}")
    assert stats["path_solves"] < stats["requests"]
    print("serve_sgl OK")


if __name__ == "__main__":
    main()
