#!/usr/bin/env python3
"""The control of a cell's comparison, at the cell's own size.

    python3 bench/control.py --workload <cell> --data-seed <n> [--data-seed <m> ...]

Puts the plain reference in the program's place, computed in the
precision below the configuration's (float32 for float64), over the
inputs a run of the cell makes (its configuration's ``data_seed``) and
over those other data seeds would make, and prints the numbers the
comparison reads beside their limits.  The control has to come out as not
correct.  The benchmark's own runs never run it; it needs no accelerator.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, run  # noqa: E402


def control(workload: str, data_seed: int, root: Path = ROOT) -> list:
    spec = run.load_cell(root, workload)
    cfg = dict(spec["config"], data_seed=data_seed)
    driver = run.load_file(root / "bench" / "drivers"
                           / f"{spec['traffic']['driver']}.py")
    X, ng, ys = driver.make_data(cfg, int(spec["traffic"]["responses"]))
    answers = check.control_answers(cfg, X, ng, ys)
    return check.judge(cfg, X, ng, ys, answers)[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--data-seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    worst_ok = True
    for seed in args.data_seed:
        t0 = time.monotonic()
        numbers = control(args.workload, seed)
        ok = check.passed(numbers)
        worst_ok &= not ok
        print(json.dumps({"workload": args.workload, "data_seed": seed,
                          "correct": ok, "seconds": time.monotonic() - t0,
                          "checks": {n: {"value": v, "limit": lim}
                                     for n, v, lim in numbers}}), flush=True)
    return 0 if worst_ok else 1


if __name__ == "__main__":
    sys.exit(main())
