#!/usr/bin/env python3
"""Record the chip trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py bench/tests/data/small_path.xplane.pb.gz

On the accelerator: one cycle of the path driver over a 30 x 200 problem
(one response, 2 lambdas), compiled and traced as ``bench/run.py`` does
it, inside ``bench.window``.  Writes the trace gzipped and, beside it
(``small_path.counters.json``), the solve's counters for that cycle.
"""
from __future__ import annotations

import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402  (sets the compile flags before JAX starts)

CONFIG = {
    "name": "trace-small", "design": "synthetic",
    "generator": {"n": 30, "p": 200, "n_groups": 20, "rho": 0.5,
                  "gamma1": 3, "gamma2": 4, "noise": 0.01},
    "data_seed": 0, "tau": 0.2, "T": 100, "delta": 3.0, "path_points": 2,
    "tol": 1e-8, "dtype": "float64", "rule": "gap",
}


def main(out: str) -> int:
    import jax

    from bench import trace
    from bench.drivers.path import Driver

    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    run.require_devices(1, peaks)
    run.enable_compile_cache()
    driver = Driver(CONFIG, {"responses": 1}, seed=0)
    driver.setup()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench.window"):
            driver.cycle()
        jax.profiler.stop_trace()
        xplane = trace.find_xplane(tmp)
        with open(xplane, "rb") as f, gzip.open(out, "wb") as g:
            shutil.copyfileobj(f, g)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    counters = driver.counters()
    Path(out).with_name("small_path.counters.json").write_text(
        json.dumps(counters) + "\n")
    s = trace.reduce_file(out)
    print(json.dumps({"counters": counters, "busy_s": s.busy_s,
                      "window_s": s.window_s,
                      "executions": {k: len(v) for k, v in s.executions.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
