"""The harness: driven by data, and silent without the chip."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.tests.conftest import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_follows_its_schema():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert bench["command"][1] == "bench/run.py"
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["source"]) <= 200
        assert c["file"].startswith("bench/") and (REPO / c["file"]).exists()
        data = json.loads((REPO / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert set(c["reduced"]) <= set(data["reduced"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert UNIT.match(m["unit"]) and 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert (REPO / "bench" / "metrics" / f"{m['name']}.py").exists()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        traffic = json.loads((REPO / "bench" / "traffic"
                              / f"{w['traffic']}.json").read_text())
        assert (REPO / "bench" / "drivers" / f"{traffic['driver']}.py").exists()
        spec = run.load_cell(REPO, w["name"])
        names = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert spec["per_layer"]


def test_a_cell_and_a_metric_are_added_by_new_files_only(tiny_root):
    """The tiny cell of ``tiny_root`` and a new per-layer metric are new
    files and new entries; the harness finds both by name."""
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "rounds_per_path", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "certified round",
        "moves": "path_s", "workloads": ["tiny"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    (tiny_root / "bench" / "metrics" / "rounds_per_path.py").write_text(
        "def read(ctx):\n"
        "    c = ctx['counters']\n"
        "    return (c['n_full_rounds'] + c['n_compact_rounds']) / c['paths']\n")
    spec = run.load_cell(tiny_root, "tiny")
    assert spec["config"]["name"] == "tiny-synth"
    assert spec["traffic"]["responses"] == 2
    assert [m["name"] for m in spec["per_layer"]] == ["rounds_per_path"]
    reader = run.load_file(tiny_root / "bench" / "metrics" / "rounds_per_path.py")
    ctx = {"counters": {"paths": 4, "n_full_rounds": 6, "n_compact_rounds": 2}}
    assert reader.read(ctx) == 2.0
    # The existing cells are untouched by the addition.
    assert run.load_cell(tiny_root, "synth-path") == run.load_cell(REPO, "synth-path")


def _command(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "synth-path",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_an_accelerator_it_exits_nonzero_and_prints_nothing():
    proc = _command(REPO, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "nothing runs without the chip" in proc.stderr


def test_without_the_program_it_exits_nonzero(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _command(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("field", ["--seed", "--workload"])
def test_arguments_are_required(field):
    argv = ["--workload", "synth-path", "--seed", "1", "--seconds", "1"]
    i = argv.index(field)
    with pytest.raises(SystemExit):
        run.parse_args(argv[:i] + argv[i + 2:])
