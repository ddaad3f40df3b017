"""Certified-path traffic: R responses over one design.

The design and the R responses of the window are made from the
configuration's ``data_seed``, so every run times the same work;
``--seed`` sets the order in which a cycle visits them.  (Made from
``--seed``, the data changed a path's work by up to 5x from seed to seed,
far more than two runs of one seed differ.)  After the window, a seed
pass solves ``seed_responses`` more responses over the same design, drawn
from ``--seed``, through the same call; they are judged with the window's
answers and are not timed, so every run looks at data it has not seen.

Set-up builds the problem once through ``make_problem`` and solves each
response's path once, which compiles or loads every program the window
uses.  A cycle then solves the R responses in the seed's order, each as a
fresh ``SGLSession`` and one ``solve_path`` over the first
``path_points`` lambdas of its own grid.  Every path ends in a host read
of its result (``PathResult`` holds NumPy arrays).
"""
from __future__ import annotations

import importlib
import time

import numpy as np

from bench import check


SEED_PASS = 7                   # first word of a seed-pass response's rng


def make_data(config: dict, n_responses: int):
    """The design, its group size and ``n_responses`` responses, made by
    ``bench/designs/<design>.py`` from the configuration's ``data_seed``."""
    design = importlib.import_module(f"bench.designs.{config['design']}")
    seed = int(config["data_seed"])
    X, ng = design.make_design(config["generator"], seed)
    ys = [design.make_response(config["generator"], X, ng,
                               np.random.default_rng([seed, r]))
          for r in range(n_responses)]
    return X, ng, ys


def seed_responses(config: dict, X, ng: int, seed: int, count: int) -> list:
    """``count`` responses over the design, drawn from ``--seed``."""
    design = importlib.import_module(f"bench.designs.{config['design']}")
    return [design.make_response(config["generator"], X, ng,
                                 np.random.default_rng([SEED_PASS, seed, i]))
            for i in range(count)]


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.n_responses = int(traffic["responses"])
        self.n_seed_responses = int(traffic.get("seed_responses", 0))
        self.seed = seed
        self.order = [int(r) for r in
                      np.random.default_rng(seed).permutation(self.n_responses)]
        self.answers: list[dict] = []
        self.seed_answers: list[dict] = []

    # -- set-up ------------------------------------------------------------

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.core import SolverConfig, make_problem

        cfg = self.config
        t0 = time.monotonic()
        self.X, self.ng, self.ys = make_data(cfg, self.n_responses)
        t1 = time.monotonic()
        dtype = np.dtype(cfg["dtype"])
        G = self.X.shape[1] // self.ng
        base = make_problem(self.X.astype(dtype, copy=False),
                            self.ys[0].astype(dtype),
                            [self.ng] * G, tau=cfg["tau"])
        self.problems = [base._replace(y=jnp.asarray(y, dtype)) for y in self.ys]
        jax.block_until_ready(self.problems)
        t2 = time.monotonic()
        self.solver_config = SolverConfig(tol=cfg["tol"], rule=cfg["rule"])
        for r in self.order:
            self._solve(r)
        self.setup_phases = {"data_s": t1 - t0, "make_problem_s": t2 - t1,
                             "warmup_s": time.monotonic() - t2}

    def _solve(self, r: int) -> dict:
        from repro.core import SGLSession, lambda_grid

        from jax.profiler import TraceAnnotation

        cfg = self.config
        t0 = time.perf_counter()
        try:
            with TraceAnnotation("bench.session"):
                session = SGLSession(self.problems[r], self.solver_config)
                lams = lambda_grid(session.lam_max, T=cfg["T"],
                                   delta=cfg["delta"])[:cfg["path_points"]]
            with TraceAnnotation("bench.solve_path"):
                res = session.solve_path(lams)
        except Exception as e:          # a path that raised is a failure
            return {"response": r, "error": f"{type(e).__name__}: {e}",
                    "seconds": time.perf_counter() - t0}
        return {
            "response": r,
            "seconds": time.perf_counter() - t0,
            "lambdas": np.asarray(res.lambdas),
            "betas": res.betas,
            "feat_active": res.feat_active,
            "gaps": np.asarray(res.gaps),
            "epochs": int(np.sum(res.epochs)),
            "n_full_rounds": int(res.n_full_rounds),
            "n_compact_rounds": int(res.n_compact_rounds),
            "certificates_safe": bool(res.certificates_safe),
            "degraded": res.degraded,
        }

    # -- window ------------------------------------------------------------

    def cycle(self) -> None:
        """Solve every response once, in the seed's order."""
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("bench.cycle"):
            for r in self.order:
                self.answers.append(self._solve(r))

    def failed(self) -> int:
        tol = self.config["tol"]
        return sum(1 for a in self.answers
                   if "error" in a or not a["certificates_safe"]
                   or a["degraded"] or not np.all(a["gaps"] <= tol))

    def counters(self) -> dict:
        """The solve's own counters, summed over the window's paths."""
        done = [a for a in self.answers if "error" not in a]
        keys = ("epochs", "n_full_rounds", "n_compact_rounds")
        return {"paths": len(self.answers),
                **{k: sum(a[k] for a in done) for k in keys}}

    def shape(self) -> tuple:
        return self.X.shape

    def end_to_end(self, window_s: float) -> dict:
        done = sum(1 for a in self.answers if "error" not in a)
        return {"path_s": window_s / max(done, 1)}

    def expected_executions(self) -> dict:
        """Program executions the traced window must hold, by the solve's
        own counters: a trace cut short holds fewer."""
        return {r"^jit__screen_round$": self.counters()["n_full_rounds"]}

    def seed_pass(self) -> None:
        """Solve the responses drawn from ``--seed``, after the window."""
        import jax.numpy as jnp

        ys = seed_responses(self.config, self.X, self.ng, self.seed,
                            self.n_seed_responses)
        dtype = np.dtype(self.config["dtype"])
        for y in ys:
            self.ys.append(y)
            self.problems.append(self.problems[0]._replace(y=jnp.asarray(y, dtype)))
            self.seed_answers.append(self._solve(len(self.ys) - 1))

    def release(self) -> None:
        """Drop every device array the program holds."""
        self.problems = None
        self.solver_config = None

    # -- correctness -------------------------------------------------------

    def check(self) -> list:
        """Compare the window's and the seed pass's answers with the plain
        reference."""
        return check.judge(self.config, self.X, self.ng, self.ys,
                           self.answers + self.seed_answers)
