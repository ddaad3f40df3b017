"""The comparison that decides ``correct`` for certified-path traffic.

Each answer is one path: the lambdas it was solved at, its betas, the
duality gap it certifies at each lambda and the features it certifies as
zero.  Five numbers are compared, each with its limit:

- ``own_gap_max``: the largest gap the answer certifies.  Limit: the
  configuration's ``tol``, the guarantee it states.
- ``gap_understated_rel``: the largest amount by which the answer's
  certified gap falls short of the gap of its betas recomputed in IEEE
  float64 by the plain reference, over ||y||^2 / 2, the scale at which a
  float64 evaluation of the gap rounds.  A certificate computed with a
  loss of precision reads here.  Limit ``UNDERSTATED_REL_LIMIT``.
- ``screened_nonzero_max``: the largest |beta| of the reference's
  unscreened solve (gap <= ``REF_TOL``) on a feature the answer certified
  as zero.  Exact: limit 0.
- ``lambda_rel_max``: the largest relative distance of an answer's lambdas
  from the reference's own grid.  Limit ``LAMBDA_REL_LIMIT``.
- ``points_missing``: grid points asked for and not answered, because a
  path raised or came back short.  Exact: limit 0.

The limits set from readings give those readings in PERF.md: sound runs
of the program over a dozen seeds and more, and the float32 control.
"""
from __future__ import annotations

import math

import numpy as np

from bench import reference

REF_TOL = 1e-10             # gap of the reference's unscreened solve
UNDERSTATED_REL_LIMIT = 1e-13   # sound <= 9.1e-15; float32 control >= 6.7e-8
LAMBDA_REL_LIMIT = 1e-10        # sound <= 3.0e-14; float32 control >= 5.3e-8


def _worst(current: float, new: float) -> float:
    """max() that lets a NaN through as +inf instead of dropping it."""
    return math.inf if not np.isfinite(new) else max(current, float(new))


def reference_path(config: dict, X, ng: int, y, dtype=np.float64):
    """The reference's problem, grid and unscreened path for one response."""
    prob = reference.Problem(X, y, ng, config["tau"], dtype)
    lams = reference.lambda_grid(prob.lambda_max(), config["T"],
                                 config["delta"], config["path_points"])
    return prob, lams


def judge(config: dict, X, ng: int, ys, answers) -> tuple:
    """``([(name, value, limit), ...], notes)`` for the answers of one
    run; ``notes`` holds the largest gap the reference recomputed."""
    count = config["path_points"]
    gap_max = leak = lam_rel = own_max = 0.0
    understated = -math.inf
    missing = 0
    refs: dict = {}
    seen: set = set()
    for a in answers:
        r = a["response"]
        if r not in refs:
            prob, lams = reference_path(config, X, ng, ys[r])
            betas, _ = reference.solve_path(prob, lams, REF_TOL)
            refs[r] = (prob, lams, betas)
        prob, lams, ref_betas = refs[r]
        if "error" in a:
            missing += count
            continue
        got = len(a["lambdas"])
        missing += max(count - got, 0)
        t = min(got, count)
        lam_rel = _worst(lam_rel, np.max(np.abs(a["lambdas"][:t] / lams[:t] - 1),
                                         initial=0.0))
        key = (r, a["lambdas"].tobytes(), a["betas"].tobytes(),
               a["feat_active"].tobytes())
        if key in seen:             # the same answer again: same verdict
            continue
        seen.add(key)
        half_y2 = 0.5 * float(prob.y @ prob.y)
        for k in range(t):
            g = prob.gap(a["betas"][k].reshape(prob.G, prob.ng), a["lambdas"][k])
            gap_max = _worst(gap_max, g)
            own = float(a["gaps"][k])
            own_max = _worst(own_max, own)
            understated = _worst(understated, (g - own) / half_y2)
            screened = ~a["feat_active"][k].reshape(prob.G, prob.ng)
            if screened.any():
                leak = _worst(leak, np.abs(ref_betas[k][screened]).max())
    numbers = [
        ("own_gap_max", own_max, config["tol"]),
        ("gap_understated_rel", understated, UNDERSTATED_REL_LIMIT),
        ("screened_nonzero_max", leak, 0.0),
        ("lambda_rel_max", lam_rel, LAMBDA_REL_LIMIT),
        ("points_missing", missing, 0),
    ]
    return numbers, {"reference_gap_max": gap_max}


def passed(numbers: list) -> bool:
    return all(value <= limit for _name, value, limit in numbers)


def control_answers(config: dict, X, ng: int, ys, dtype=np.float32,
                    max_iter: int = 10_000) -> list:
    """The reference in the program's place, in the precision below the
    configuration's (float32 for float64): its grid, its betas and the gaps
    it computes for them, with nothing certified as zero."""
    answers = []
    for r, y in enumerate(ys):
        prob, lams = reference_path(config, X, ng, y, dtype)
        betas, gaps = reference.solve_path(prob, lams, config["tol"],
                                           max_iter=max_iter, strict=False)
        answers.append({
            "response": r,
            "lambdas": np.asarray(lams, np.float64),
            "betas": betas.astype(np.float64),
            "gaps": gaps,
            "feat_active": np.ones(betas.shape, bool),
        })
    return answers
