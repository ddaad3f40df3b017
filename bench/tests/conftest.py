"""Fixtures of the benchmark's own tests, which run on the CPU:
``python -m pytest bench/tests``."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

REPO = Path(__file__).resolve().parents[2]
for _p in (str(REPO), str(REPO / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY_CONFIG = {
    "name": "tiny-synth",
    "source": "bench/configs/paper-synth.json at a size a test run holds",
    "design": "synthetic",
    "generator": {"n": 40, "p": 300, "n_groups": 30, "rho": 0.5,
                  "gamma1": 3, "gamma2": 4, "noise": 0.01},
    "data_seed": 0, "tau": 0.2, "T": 100, "delta": 3.0, "path_points": 6, "tol": 1e-8,
    "dtype": "float64", "rule": "gap",
}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-like directory: the benchmark's files, plus one extra
    cell ``tiny`` added as new files and entries only."""
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "configs" / "tiny-synth.json").write_text(
        json.dumps(TINY_CONFIG))
    (tmp_path / "bench" / "traffic" / "paths-r2-tiny.json").write_text(
        json.dumps({"driver": "path", "responses": 2, "seed_responses": 1}))
    bench["configs"].append({
        "name": "tiny-synth", "source": TINY_CONFIG["source"],
        "file": "bench/configs/tiny-synth.json", "reduced": ["generator"],
        "why": "test size"})
    bench["workloads"].append({
        "name": "tiny", "config": "tiny-synth", "traffic": "paths-r2-tiny",
        "chips": 1, "why": "test size"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def cpu_guard(chips, peaks):
    import jax

    return jax.devices("cpu")[:chips]


@pytest.fixture
def run_tiny(tiny_root):
    """Drive a whole run of the tiny cell on the CPU, past the chip guard."""
    from bench import run

    def go(seed=5, seconds=0.3, workload="tiny"):
        args = run.parse_args(["--workload", workload, "--seed", str(seed),
                               "--seconds", str(seconds), "--trace", "0"])
        return run.run(args, root=tiny_root, guard=cpu_guard)

    return go
