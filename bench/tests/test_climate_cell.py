"""The ``climate-path`` cell's shape at a size a test run holds: the
climate stand-in with its target point dropped, two responses cycled and
one drawn from ``--seed``, driven through ``bench.run.run`` on the CPU."""
from __future__ import annotations

import json

import pytest

from bench import run
from bench.tests.conftest import cpu_guard
from bench.tests.test_correctness import _broken, _certified_zero_altered

TINY_CLIMATE = {
    "name": "tiny-climate",
    "source": "bench/configs/ncep-climate.json at a size a test run holds",
    "design": "climate",
    "generator": {"n": 60, "n_lon": 6, "n_lat": 4, "n_vars": 7,
                  "n_active_regions": 6, "noise": 0.05, "drop_point": [4, 1]},
    "data_seed": 0, "tau": 0.4, "T": 100, "delta": 2.5, "path_points": 4,
    "tol": 1e-8, "dtype": "float64", "rule": "gap",
}


@pytest.fixture
def run_climate(tiny_root):
    """Add the tiny climate cell to ``tiny_root`` by new files and entries,
    and drive whole untraced runs of it on the CPU."""
    (tiny_root / "bench" / "configs" / "tiny-climate.json").write_text(
        json.dumps(TINY_CLIMATE))
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-climate", "source": TINY_CLIMATE["source"],
        "file": "bench/configs/tiny-climate.json", "reduced": ["generator"],
        "why": "test size"})
    bench["workloads"].append({
        "name": "tiny-climate", "config": "tiny-climate",
        "traffic": "paths-r2-seed1", "chips": 1, "why": "test size"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))

    def go(seed):
        args = run.parse_args(["--workload", "tiny-climate", "--seed",
                               str(seed), "--seconds", "0.3", "--trace", "0"])
        return run.run(args, root=tiny_root, guard=cpu_guard)

    return go


def test_climate_cell_is_correct_and_reads_its_problem_build(run_climate, tiny_root):
    from repro.core import sgl  # noqa: F401  (declares the gauge)
    from repro.obs import metrics

    metrics.REGISTRY.reset(["sgl.make_problem_s"])
    out = run_climate(seed=2**32 + 3)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert set(out["metrics"]) == {"path_s", "setup_s"}
    # The per-layer reader a traced run calls reads the driver's one build.
    spec = run.load_cell(tiny_root, "climate-path")
    assert "problem_build_s" in [m["name"] for m in spec["per_layer"]]
    reader = run.load_file(tiny_root / "bench" / "metrics" / "problem_build_s.py")
    value = reader.read({})
    assert isinstance(value, float) and 0 < value < out["metrics"]["setup_s"]["value"]


def test_problem_build_reads_nothing_without_the_gauge(monkeypatch):
    from repro.core import sgl  # noqa: F401  (declares the gauge)
    from repro.obs import metrics

    reader = run.load_file(run.ROOT / "bench" / "metrics" / "problem_build_s.py")
    monkeypatch.setattr(metrics, "REGISTRY", metrics.MetricsRegistry())
    assert reader.read({}) is None            # a program that never made it
    metrics.REGISTRY.gauge("sgl.make_problem_s")
    assert reader.read({}) is None            # made, never set


def test_climate_cell_fault_is_not_correct(run_climate, monkeypatch):
    _broken(monkeypatch, _certified_zero_altered)
    out = run_climate(seed=23)
    assert not out["correct"], out["checks"]
    assert out["checks"]["screened_nonzero_max"]["value"] > 0
