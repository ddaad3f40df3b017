"""Figure 3b: whole-path computation time on the climate-like dataset as a
function of the prescribed duality-gap accuracy, GAP rule vs no screening —
the sequential path engine vs the legacy naive per-lambda loop vs the
session front-end.

Paper: NCEP/NCAR Reanalysis 1, n=814, p=73577 (groups of 7 variables per
grid point), delta=2.5, tau*=0.4.  The offline generator reproduces the
group structure and preprocessing; the default grid is reduced so the
harness completes in CPU-minutes (``--full`` restores 144x73).

Modes:
* ``naive``   — the seed loop: warm-started beta only, fresh caches and a
  full active-set re-derivation at every lambda, f_ce-block epoch counts.
* ``engine``  — sequential GAP screening before the first epoch of each
  lambda, carried gather cache, sequential-gap-adaptive early exit
  (via the legacy ``solve_path`` wrapper).
* ``session`` — the same engine driven through ``SGLSession.solve_path``
  directly: one session per (rule, tol) owning the caches and, on the
  Pallas backend, ONE persistent transposed design for every certified
  round of the whole path.  ``transpose_copies_eliminated`` counts the
  per-round (p, n) copies of X the pre-session design materialised
  (``n_rounds``) minus the copies actually measured (trace audit,
  ``PathResult.n_transpose_copies``); reported as 0 on the XLA backend,
  where no transposed copy was ever at stake.

The session mode additionally reports the compacted-certified-round audit:
``compact_rounds`` / ``full_rounds`` split the path's certified rounds by
whether they ran on the compacted (n, p_active) buffer or the full
problem, and ``round_flop_reduction`` is the measured ratio between what
full-rounds-only would have cost (rounds x ~4 n p) and the round FLOPs
actually spent (``PathResult.round_flops``, fallback attempts included).

The ``path_pr4`` case records the fused-BCD-solver trajectory
(``solver_backend="pallas"``): wall-clock, epochs, certified-round split,
round FLOPs, fused-epoch-launch and batched-lambda counts, against the XLA
``lax.scan`` twin on the same grid.  ``--json PATH`` dumps every emitted row
(plus environment metadata) as machine-readable JSON — the recorded
``BENCH_pr4.json`` baseline future PRs diff against.

``--smoke`` runs a reduced synthetic config and *asserts* the audits the CI
watches — zero on-the-fly transposed copies, compact rounds actually
exercised, engine-vs-naive beta parity, AND the fused-solver invariants:
``solver_backend="pallas"`` (interpret mode on CPU) reproduces the XLA
path bit-for-bit with ``n_fused_epoch_launches > 0``, and the
batched-lambda run batches at least one coinciding-active-set stretch
(``batched_lambdas > 0``) while staying within tolerance — then exits.
"""
from __future__ import annotations

import time
import warnings

from repro.core import sgl
from repro.core.path import lambda_grid, solve_path
from repro.core.session import SGLSession, SolverConfig
from repro.core.solver import resolve_screen_backend
from repro.data.climate import make_climate_like

from .common import emit

MODES = ("naive", "engine", "session")
MODE_KWARGS = {
    "naive": dict(sequential=False, check_every=None),
    "engine": dict(sequential=True, check_every="auto"),
}


def smoke(n=64, p=512, n_groups=64, T=10, delta=2.0, tau=0.3,
          tol=1e-7, max_epochs=20_000) -> None:
    """CI-sized audit run: transpose + compact-round accounting asserted.

    Exercises both audits on every PR instead of only in manual benchmark
    runs: a session-wiring regression that reintroduced per-round (p, n)
    transposed copies, or one that silently stopped dispatching compact
    rounds, fails this step outright.
    """
    import numpy as np

    from repro.data.synthetic import make_synthetic

    X, y, _, sizes = make_synthetic(n=n, p=p, n_groups=n_groups, gamma1=3,
                                    gamma2=3, seed=11)
    problem = sgl.make_problem(X, y, sizes, tau=tau)

    # full_round_every is disabled so full rounds can ONLY come from the T
    # sequential screens, bound-crossing fallbacks, oversized buffers, and
    # the converged-round confirmation — which makes the full-round floor
    # below a real check of the confirmation invariant instead of being
    # satisfied by the sequential rounds alone.
    session = SGLSession(problem, SolverConfig(tol=tol,
                                               max_epochs=max_epochs,
                                               full_round_every=10 ** 9))
    res = session.solve_path(T=T, delta=delta)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        naive = solve_path(problem, T=T, delta=delta, tol=tol,
                           max_epochs=max_epochs, **MODE_KWARGS["naive"])

    assert (res.gaps <= tol).all(), "session path missed tolerance"
    assert res.n_transpose_copies == 0, (
        f"per-round transposed copies are back: {res.n_transpose_copies}"
    )
    assert res.n_compact_rounds > 0, "no compact certified rounds dispatched"
    # One sequential full round per lambda PLUS one converged full round
    # per lambda that ran epochs (lambdas converging on the sequential
    # round itself already reported a full-round gap).
    worked = int((res.epochs > 0).sum())
    assert res.n_full_rounds >= T + worked, (
        "every lambda's converged round must be a full round "
        f"(full={res.n_full_rounds}, T={T}, worked={worked})"
    )
    np.testing.assert_allclose(res.betas, naive.betas, atol=1e-8)
    full_equiv = res.n_rounds * 4.0 * problem.n * problem.G * problem.ng
    emit("path_smoke", "audit", "compact_rounds", res.n_compact_rounds)
    emit("path_smoke", "audit", "full_rounds", res.n_full_rounds)
    emit("path_smoke", "audit", "transpose_copies", res.n_transpose_copies)
    emit("path_smoke", "audit", "round_flop_reduction",
         full_equiv / max(res.round_flops, 1.0))

    # ---- fused-BCD solver backend (interpret mode on CPU) ----
    # Bit parity: the Pallas mega-kernel path must reproduce the XLA
    # lax.scan path exactly — betas, epoch counts, and screen counters —
    # while actually dispatching fused launches (so the kernel path cannot
    # silently rot on CPU-only CI).
    sess_p = SGLSession(problem, SolverConfig(tol=tol,
                                              max_epochs=max_epochs,
                                              full_round_every=10 ** 9,
                                              solver_backend="pallas"))
    res_p = sess_p.solve_path(T=T, delta=delta, batch_lambdas=1)
    assert res_p.n_fused_epoch_launches > 0, "no fused epoch launches"
    np.testing.assert_array_equal(res_p.betas, res.betas)
    assert (res_p.epochs == res.epochs).all(), "epoch counts diverged"
    assert np.array_equal(res_p.seq_screened, res.seq_screened)
    assert np.array_equal(res_p.dyn_screened, res.dyn_screened)
    emit("path_smoke", "pallas", "fused_epoch_launches",
         res_p.n_fused_epoch_launches)

    # Batched-lambda single-device path, on a DENSE grid whose warm tail
    # has coinciding certified active sets (batching is gated to warm
    # stretches — see SGLSession.solve_path): the stretch must batch
    # through the kernel's lambda-batch grid axis, stay safe, and land
    # within solver tolerance of the per-lambda XLA reference.
    dense = dict(T=T, delta=0.5)
    ref_d = SGLSession(problem, SolverConfig(
        tol=tol, max_epochs=max_epochs, full_round_every=10 ** 9,
    )).solve_path(batch_lambdas=1, **dense)
    sess_b = SGLSession(problem, SolverConfig(tol=tol,
                                              max_epochs=max_epochs,
                                              full_round_every=10 ** 9,
                                              solver_backend="pallas"))
    res_b = sess_b.solve_path(batch_lambdas=4, **dense)
    assert res_b.batched_lambdas > 0, "no batched lambdas on this grid"
    assert (res_b.gaps <= tol).all(), "batched path missed tolerance"
    np.testing.assert_allclose(res_b.betas, ref_d.betas, atol=1e-8)
    emit("path_smoke", "pallas_batched", "batched_lambdas",
         res_b.batched_lambdas)
    emit("path_smoke", "pallas_batched", "fused_epoch_launches",
         res_b.n_fused_epoch_launches)

    obs_payload = _obs_overhead_check(problem, T=T, delta=delta, tol=tol,
                                      max_epochs=max_epochs)
    print("SMOKE PASS")
    return obs_payload


def _obs_overhead_check(problem, *, T, delta, tol, max_epochs,
                        reps=3, budget=0.03) -> dict:
    """The obs zero-cost contract, measured on the smoke path: tracing
    enabled (sample_every=1) must leave the betas bit-identical and the
    wall-clock within ``budget`` of the untraced run.

    min-of-``reps`` on both sides damps scheduler noise — spans cost
    microseconds against a multi-second jitted solve, so any apparent
    overhead above noise is a real regression in the span fast path.
    """
    import numpy as np

    from repro.obs import trace as obs_trace

    def run_once():
        session = SGLSession(problem, SolverConfig(
            tol=tol, max_epochs=max_epochs, full_round_every=10 ** 9))
        t0 = time.perf_counter()
        res = session.solve_path(T=T, delta=delta)
        return time.perf_counter() - t0, np.asarray(res.betas)

    run_once()          # jit warm (XLA caches are process-global)
    t_off, betas_off = zip(*(run_once() for _ in range(reps)))
    obs_trace.configure(enabled=True, sample_every=1)
    obs_trace.TRACER.reset()
    t_on, betas_on = zip(*(run_once() for _ in range(reps)))
    counts = dict(obs_trace.TRACER.counts())
    stages = obs_trace.TRACER.stage_summary()
    obs_trace.configure(enabled=False)

    np.testing.assert_array_equal(
        betas_on[-1], betas_off[-1],
        err_msg="enabling tracing changed the path betas")
    assert counts.get("path", 0) == reps and counts.get("round", 0) > 0, (
        f"span sites silent under tracing: {counts}")
    overhead = min(t_on) / min(t_off) - 1.0
    emit("path_smoke", "obs", "overhead_frac", overhead)
    emit("path_smoke", "obs", "spans_counted", sum(counts.values()))
    assert overhead <= budget, (
        f"obs-enabled path overhead {overhead:.1%} exceeds {budget:.0%}")
    return {
        "shape": {"n": int(problem.n), "G": int(problem.G),
                  "ng": int(problem.ng), "T": T, "delta": delta,
                  "tol": tol},
        "base_s": float(min(t_off)),
        "obs_s": float(min(t_on)),
        "overhead_frac": float(overhead),
        "bit_identical": True,
        "span_counts": counts,
        "stages": stages,
    }


def main(n=256, n_lon=16, n_lat=8, T=20, delta=2.5, tau=0.4,
         tols=(1e-4, 1e-6, 1e-8), max_epochs=3000) -> None:
    X, y, _, sizes = make_climate_like(n=n, n_lon=n_lon, n_lat=n_lat)
    problem = sgl.make_problem(X, y, sizes, tau=tau)
    lam_max = float(sgl.lambda_max(problem))
    lambdas = lambda_grid(lam_max, T=T, delta=delta)

    for rule in ("gap", "none"):
        for tol in tols:
            for mode in MODES:
                t0 = time.perf_counter()
                if mode == "session":
                    session = SGLSession(problem, SolverConfig(
                        tol=tol, max_epochs=max_epochs, rule=rule,
                    ))
                    res = session.solve_path(lambdas=lambdas)
                else:
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore", DeprecationWarning)
                        res = solve_path(
                            problem, lambdas=lambdas, tol=tol,
                            max_epochs=max_epochs, rule=rule,
                            **MODE_KWARGS[mode],
                        )
                dt = time.perf_counter() - t0
                case = f"{rule}_{mode}_tol{tol:g}"
                emit("path_fig3b", case, "path_seconds", dt)
                emit("path_fig3b", case, "total_epochs", int(res.epochs.sum()))
                emit("path_fig3b", case, "zero_epoch_lambdas",
                     int((res.epochs == 0).sum()))
                emit("path_fig3b", case, "gathers", res.n_gathers)
                emit("path_fig3b", case, "certified_rounds", res.n_rounds)
                # (p, n) transposed copies of X eliminated by the persistent
                # transposed design: one per certified round on the Pallas
                # backend (pre-session behavior), minus any measured copies
                # (res.n_transpose_copies, from the trace audit).  Only the
                # Pallas backend ever had a copy at stake, so XLA-backed
                # runs report 0.
                pallas = resolve_screen_backend(
                    "auto", problem.X.dtype) == "pallas"
                emit("path_fig3b", case, "transpose_copies_eliminated",
                     res.n_rounds - res.n_transpose_copies if pallas else 0)
                if mode == "session":
                    # Compacted-certified-round audit (session engine only;
                    # the legacy wrappers spin up their own sessions whose
                    # counters are not surfaced here).
                    emit("path_fig3b", case, "compact_rounds",
                         res.n_compact_rounds)
                    emit("path_fig3b", case, "full_rounds", res.n_full_rounds)
                    full_equiv = (res.n_rounds * 4.0 * problem.n
                                  * problem.G * problem.ng)
                    emit("path_fig3b", case, "round_flop_reduction",
                         full_equiv / max(res.round_flops, 1.0))
                    emit("path_fig3b", case, "round_flops", res.round_flops)
                    emit("path_fig3b", case, "fused_epoch_launches",
                         res.n_fused_epoch_launches)
                    emit("path_fig3b", case, "batched_lambdas",
                         res.batched_lambdas)
                if rule == "gap":
                    emit("path_fig3b", case, "seq_screened_groups",
                         int(res.seq_screened.sum()))
                    emit("path_fig3b", case, "dyn_screened_groups",
                         int(res.dyn_screened.sum()))


def pallas_case(n=64, p=512, n_groups=64, T=12, delta=2.0, tau=0.3,
                tol=1e-6, max_epochs=20_000) -> None:
    """Fused-BCD-solver trajectory vs its XLA twin on one synthetic grid.

    On this CPU container the fused kernel runs interpreted, so its
    wall-clock is an upper bound on dispatch overhead rather than a TPU
    number — the launch/batching audits and the epoch counts are the
    durable metrics (compiled-TPU wall-clock belongs in EXPERIMENTS.md).
    """
    import numpy as np

    from repro.data.synthetic import make_synthetic

    X, y, _, sizes = make_synthetic(n=n, p=p, n_groups=n_groups, gamma1=3,
                                    gamma2=3, seed=11)
    problem = sgl.make_problem(X, y, sizes, tau=tau)
    # Batching is gated to warm stretches, so the batched case runs on a
    # DENSE grid (delta=0.5: near-duplicate consecutive lambdas) where
    # coinciding-active-set warm stretches actually occur; its reference
    # is the XLA run of the SAME grid.
    runs = (
        ("xla", "xla", 1, delta),
        ("pallas", "pallas", 1, delta),
        ("xla_dense", "xla", 1, 0.5),
        ("pallas_batched", "pallas", 4, 0.5),
    )
    betas_ref = {}
    for case, backend, batch, delta_c in runs:
        session = SGLSession(problem, SolverConfig(
            tol=tol, max_epochs=max_epochs, solver_backend=backend,
        ))
        t0 = time.perf_counter()
        res = session.solve_path(T=T, delta=delta_c, batch_lambdas=batch)
        dt = time.perf_counter() - t0
        emit("path_pr4", case, "path_seconds", dt)
        emit("path_pr4", case, "total_epochs", int(res.epochs.sum()))
        emit("path_pr4", case, "certified_rounds", res.n_rounds)
        emit("path_pr4", case, "compact_rounds", res.n_compact_rounds)
        emit("path_pr4", case, "full_rounds", res.n_full_rounds)
        emit("path_pr4", case, "round_flops", res.round_flops)
        emit("path_pr4", case, "fused_epoch_launches",
             res.n_fused_epoch_launches)
        emit("path_pr4", case, "batched_lambdas", res.batched_lambdas)
        if delta_c not in betas_ref:
            betas_ref[delta_c] = np.asarray(res.betas)
        else:
            emit("path_pr4", case, "beta_max_diff_vs_xla",
                 float(np.abs(np.asarray(res.betas)
                              - betas_ref[delta_c]).max()))


if __name__ == "__main__":
    import argparse

    from .common import header, write_json

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run asserting the transpose, "
                         "compact-round, and fused-solver audits")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="dump emitted rows as machine-readable JSON "
                         "(the BENCH_pr4.json perf-trajectory record)")
    ap.add_argument("--obs-json", metavar="PATH", default=None,
                    help="with --smoke: merge the obs overhead check and "
                         "the measured per-kernel timing harness into a "
                         "repro.obs.bench/v1 file (BENCH_pr10.json)")
    args = ap.parse_args()
    header()
    if args.smoke:
        obs_payload = smoke()
        if args.obs_json:
            from repro.obs.export import merge_bench
            from repro.obs.timing import measure_kernels

            merge_bench(args.obs_json, "path", obs_payload)
            merge_bench(args.obs_json, "kernels",
                        {"scale": "smoke",
                         "kernels": measure_kernels(scale="smoke")})
    elif args.full:
        main(n=814, n_lon=144, n_lat=73, T=100)
        pallas_case()
    else:
        main()
        pallas_case()
    if args.json:
        write_json(args.json)
