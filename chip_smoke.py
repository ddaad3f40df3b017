#!/usr/bin/env python3
"""Smoke run of the certified lambda path on a TPU.

    python chip_smoke.py              # one chip: path, serve, kernels
    python chip_smoke.py --chips 4    # mesh strategy on a 2x2 mesh only

Everything runs in this one process, through the entry points a user
calls (``SGLSession``, ``SGLServer``), on the paper's synthetic problem at
full size: n=100, p=10,000 in 1,000 groups of 10, tau=0.2, made from
``--seed``.  Each phase prints its wall time split into compile (tracing,
lowering and XLA compilation, read from ``jax.monitoring`` spans) and run.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, without the ``repro`` package next to this file, or when a
check fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TOL = 1e-8                  # certified duality gap of every path point
N_LAMBDAS = 10              # first points of the paper grid (T=100, delta=3)
REF_TOL = 1e-10             # gap of the unscreened reference solve
REF_MAX_EPOCHS = 20_000     # per lambda; the reference needs far fewer
BETA_ATOL = 1e-4            # |beta - beta_ref| bound, both solved to TOL
KERNEL_RTOL = {             # f32 kernel vs twin, max |err| / max |ref|
    "screening_corr": 1e-5, "screening_scores": 1e-5, "dual_norm": 1e-5,
    "sgl_prox": 1e-6, "bcd_epoch": 1e-4, "bcd_epoch_logistic": 1e-4,
}
MESH_REL_TOL = 1e-6         # f32 mesh gap / (||y||^2 / 2)
MESH_BETA_RTOL = 1e-2       # f32 mesh beta vs f64 one chip, / max |beta|

_COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
))


class Checks:
    """Collects failed checks so that one run reports all of them."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok, what: str) -> None:
        status = "ok  " if ok else "FAIL"
        print(f"  check {status} {what}", flush=True)
        if not ok:
            self.failed.append(what)


class CompileClock:
    """Union of JAX's compile spans, so nested traces count once."""

    def __init__(self, jax):
        self.spans = []
        self.cache_hits = 0
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _span(self, event, start, end, **_):
        if event in _COMPILE_EVENTS:
            self.spans.append((start, end))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def seconds(self, t0: float, t1: float) -> float:
        total, reach = 0.0, t0
        for s, e in sorted(self.spans):
            s, e = max(s, reach), min(e, t1)
            if e > s:
                total += e - s
                reach = e
        return total

    @contextlib.contextmanager
    def phase(self, name: str):
        print(f"[{name}]", flush=True)
        t0 = time.time()
        yield
        t1 = time.time()
        comp = self.seconds(t0, t1)
        print(f"[{name}] wall {t1 - t0:.3f} s = compile {comp:.3f} s "
              f"+ run {t1 - t0 - comp:.3f} s", flush=True)


def paper_problem(seed: int, dtype):
    from repro.core import make_problem
    from repro.data.synthetic import make_synthetic

    X, y, _beta, sizes = make_synthetic(seed=seed)
    return make_problem(X.astype(dtype), y.astype(dtype), sizes, tau=0.2)


def paper_lambdas(session):
    from repro.core import lambda_grid

    return lambda_grid(session.lam_max, T=100, delta=3)[:N_LAMBDAS]


def reference_path(problem, lambdas):
    """Unscreened, tight-tolerance f64 XLA solve at each lambda, on the
    host's CPU backend: IEEE f64, where the TPU only emulates it."""
    import jax
    import jax.numpy as jnp
    from repro.core import SGLSession, SolverConfig

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref = SGLSession(jax.device_put(problem, cpu), SolverConfig(
            tol=REF_TOL, rule="none", max_epochs=REF_MAX_EPOCHS,
            screen_backend="xla", solver_backend="xla"))
        beta = jnp.zeros((problem.G, problem.ng), problem.X.dtype)
        out = []
        for lam in lambdas:
            res = ref.solve(float(lam), beta0=beta)
            beta = res.beta
            out.append(res)
    return out


def host_gaps_of(problem, lambdas, betas):
    """Duality gap of each beta, computed on the host's CPU backend."""
    import jax
    import jax.numpy as jnp
    from repro.core import sgl

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        prob = jax.device_put(problem, cpu)
        gaps = []
        for lam, beta in zip(lambdas, betas):
            beta = jnp.asarray(beta)
            resid = prob.y - jnp.einsum("ngk,gk->n", prob.X, beta)
            lam = jnp.asarray(lam)
            theta = sgl.dual_scale(prob, resid, lam)
            gaps.append(float(sgl.duality_gap(prob, beta, theta, lam)))
    return gaps


def phase_path(clock, check, seed):
    import numpy as np
    from repro.core import SGLSession, SolverConfig
    from repro.rules import GapSafeRule

    problem = paper_problem(seed, np.float64)
    config = SolverConfig(tol=TOL, rule=GapSafeRule())
    with clock.phase("path"):
        session = SGLSession(problem, config)
        lambdas = paper_lambdas(session)
        print(f"  n={problem.n} p={problem.G * problem.ng} G={problem.G} "
              f"ng={problem.ng} dtype={problem.X.dtype} "
              f"backend={session.backend} "
              f"solver_backend={session.solver_backend}")
        path = session.solve_path(lambdas)
    for t, lam in enumerate(lambdas):
        print(f"  lambda[{t}]={lam:.6g} epochs={int(path.epochs[t])} "
              f"gap={path.gaps[t]:.3e} "
              f"active_groups={int(path.group_active[t].sum())}")
    check(bool(np.all(path.gaps <= TOL)),
          f"every gap <= {TOL:g} (max {path.gaps.max():.3e})")
    check(path.certificates_safe, "certificates_safe")
    host_gaps = host_gaps_of(problem, lambdas, path.betas)
    print(f"  the chip's betas re-certified on the host CPU (IEEE f64): "
          f"gaps {[f'{g:.3e}' for g in host_gaps]}")
    check(max(host_gaps) <= TOL, f"host re-certified gaps <= {TOL:g}")

    with clock.phase("path reference (rule=none, f64, host CPU)"):
        ref = reference_path(problem, lambdas)
    feat = np.asarray(problem.feat_mask)
    leaked, dbeta = 0.0, 0.0
    for t, r in enumerate(ref):
        beta_ref = np.asarray(r.beta)
        screened = ~path.feat_active[t] & feat
        if screened.any():
            leaked = max(leaked, float(np.abs(beta_ref[screened]).max()))
        dbeta = max(dbeta, float(np.abs(path.betas[t] - beta_ref).max()))
    ref_gap = max(float(r.gap) for r in ref)
    print(f"  reference epochs {[int(r.n_epochs) for r in ref]}")
    print(f"  reference gaps max {ref_gap:.3e}; "
          f"max |beta_ref| on screened features {leaked:.3e}; "
          f"max |beta - beta_ref| {dbeta:.3e}")
    check(ref_gap <= REF_TOL, f"reference gaps <= {REF_TOL:g}")
    check(leaked == 0.0, "nothing screened is nonzero in the reference")
    check(dbeta <= BETA_ATOL, f"betas agree with the reference to "
                              f"{BETA_ATOL:g}")
    return problem, config, lambdas, path


def phase_serve(clock, check, problem, config, lambdas, path):
    import numpy as np
    from repro.core import make_problem
    from repro.serve import PathRequest, PathResponse, ServeConfig, SGLServer

    rng = np.random.default_rng(1)
    y2 = np.asarray(problem.y) + 0.02 * rng.standard_normal(problem.n)
    X = np.asarray(problem.X).reshape(problem.n, -1)
    perturbed = make_problem(X, y2, [problem.ng] * problem.G,
                             tau=float(problem.tau))
    with clock.phase("serve"):
        server = SGLServer(ServeConfig()).start()
        try:
            futs = [server.submit(PathRequest(t, pr, lambdas, config=config))
                    for t, pr in (("tenant-a", problem),
                                  ("tenant-b", problem),
                                  ("tenant-c", perturbed))]
            resp = []
            for f in futs:
                try:
                    resp.append(f.result(timeout=1800))
                except Exception as e:          # Degraded or a typed error
                    resp.append(e)
        finally:
            server.stop()
    for r in resp:
        if isinstance(r, PathResponse):
            print(f"  {r.tenant}: served_from={r.served_from} "
                  f"coalesced_n={r.coalesced_n} "
                  f"max_gap={r.result.gaps.max():.3e} "
                  f"solve_s={r.solve_s:.3f}")
        else:
            print(f"  {type(r).__name__}: {r}")
    check(all(isinstance(r, PathResponse) for r in resp),
          "every future resolved to a PathResponse")
    if not all(isinstance(r, PathResponse) for r in resp):
        return
    a, b, c = resp
    check(max(a.coalesced_n, b.coalesced_n) >= 2,
          "the two identical requests coalesced into one solve")
    check(np.array_equal(a.result.betas, path.betas)
          and np.array_equal(b.result.betas, path.betas),
          "coalesced betas bit-identical to the solo path")
    check(bool(np.all(c.result.gaps <= TOL))
          and c.result.certificates_safe,
          "perturbed-y path certified")


def phase_kernels(clock, check, seed, problem):
    import jax
    import numpy as np
    from repro.analysis.registry import kernel_audits
    from repro.kernels.cases import kernel_cases

    registered = {build().name for build in kernel_audits().values()}
    cases = kernel_cases(problem.n, problem.G, problem.ng, np.float32)
    check(registered <= set(cases),
          f"a case for every registered kernel {sorted(registered)}")
    key = jax.random.PRNGKey(seed)
    for name, case in cases.items():
        with clock.phase(f"kernel {name} (f32)"):
            key, sub = jax.random.split(key)
            args = case.make_args(sub)
            text = jax.jit(case.fn).lower(*args).compile().as_text()
            got = jax.block_until_ready(case.fn(*args))
            with jax.default_matmul_precision("highest"):
                want = jax.block_until_ready(jax.jit(case.ref)(*args))
        got = [np.asarray(g) for g in jax.tree.leaves(got)]
        want = [np.asarray(w) for w in jax.tree.leaves(want)]
        err = max(float(np.abs(g - w).max()) / max(float(np.abs(w).max()),
                                                    1e-30)
                  for g, w in zip(got, want))
        finite = all(np.isfinite(g).all() for g in got)
        print(f"  {name}: shapes {[g.shape for g in got]} "
              f"rel err {err:.3e}")
        check("tpu_custom_call" in text, f"{name} compiled as a kernel")
        check(finite and err <= KERNEL_RTOL[name],
              f"{name} matches its twin to {KERNEL_RTOL[name]:g}")


def phase_mesh(clock, check, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import SGLSession, SolverConfig, sgl
    from repro.launch.mesh import make_local_mesh
    from repro.rules import GapSafeRule

    problem = paper_problem(seed, np.float64)
    problem32 = paper_problem(seed, np.float32)
    half_y2 = 0.5 * float(jnp.sum(problem.y * problem.y))
    mesh_tol = MESH_REL_TOL * half_y2
    with clock.phase("mesh 2x2 (f32 FISTA)"):
        mesh = make_local_mesh(2, 2)
        session = SGLSession(problem32, SolverConfig(
            tol=mesh_tol, max_epochs=100_000), mesh=mesh)
        lambdas = paper_lambdas(session)
        X = session._dist.X
        devs = {s.device for s in X.addressable_shards}
        print(f"  X shards: {sorted(str(s.device) for s in X.addressable_shards)} "
              f"shard shape {X.addressable_shards[0].data.shape}")
        mpath = session.solve_path(lambdas)
    check(len(devs) == 4, "X's shards sit on 4 distinct devices")
    with clock.phase("one chip (f64 xla)"):
        ref = SGLSession(problem, SolverConfig(
            tol=TOL, rule=GapSafeRule(), screen_backend="xla",
            solver_backend="xla")).solve_path(lambdas)
    scale = max(float(np.abs(ref.betas).max()), 1e-30)
    worst_gap, worst_beta = 0.0, 0.0
    for t, lam in enumerate(lambdas):
        beta = jnp.asarray(mpath.betas[t], jnp.float64)
        resid = problem.y - jnp.einsum("ngk,gk->n", problem.X, beta)
        theta = sgl.dual_scale(problem, resid, jnp.asarray(lam))
        gap = float(sgl.duality_gap(problem, beta, theta, jnp.asarray(lam)))
        dbeta = float(np.abs(mpath.betas[t] - ref.betas[t]).max()) / scale
        worst_gap, worst_beta = max(worst_gap, gap), max(worst_beta, dbeta)
        print(f"  lambda[{t}]={lam:.6g} mesh steps={int(mpath.epochs[t])} "
              f"f64 re-certified gap={gap:.3e} one-chip gap="
              f"{ref.gaps[t]:.3e} |dbeta|/max|beta|={dbeta:.3e}")
    check(worst_gap <= mesh_tol,
          f"f64 re-certified mesh gaps <= {MESH_REL_TOL:g} * ||y||^2/2 "
          f"= {mesh_tol:.3e}")
    check(worst_beta <= MESH_BETA_RTOL,
          f"mesh betas agree with one chip to {MESH_BETA_RTOL:g} "
          "of max |beta|")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh strategy on a 2x2 mesh "
                         "against one chip")
    args = ap.parse_args(argv)

    try:
        import jax
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package from "
              f"{Path(__file__).resolve().parent / 'src'}: {e}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (jax.devices()[0] is "
              f"{devices[0].platform}); nothing runs without the chip",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    clock = CompileClock(jax)
    check = Checks()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache_dir}", flush=True)

    try:
        from repro.kernels import ops as kops

        if args.chips == 4:
            phase_mesh(clock, check, args.seed)
        else:
            problem, config, lambdas, path = phase_path(clock, check,
                                                        args.seed)
            phase_serve(clock, check, problem, config, lambdas, path)
            phase_kernels(clock, check, args.seed, problem)
        demotions = kops.kernel_demotion_count()
        print(f"kernel_demotions={demotions}")
        check(demotions == 0, "no Pallas launch was demoted")
    except Exception:
        traceback.print_exc()
        print("chip_smoke: a phase raised", file=sys.stderr)
        return 1
    print(f"compile cache hits={clock.cache_hits} "
          f"entries={sum(1 for _ in Path(cache_dir).glob('*'))}")
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
