"""The reduction from a profiler trace to device busy time, device time
per program and labelled idle gaps."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import trace

SMALL_TRACE = Path(__file__).parent / "data" / "small_path.xplane.pb.gz"
COUNTERS = Path(__file__).parent / "data" / "small_path.counters.json"


def _ev(name, start_ns, dur_ns):
    return NS(name=name, start_ns=start_ns, duration_ns=dur_ns)


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 1000, 9000),
        _ev("bench.session", 1000, 2000),
        _ev("bench.solve_path", 3000, 7000),
    ])])
    ops = [_ev("fusion.1", 2000, 1000), _ev("fusion.2", 2500, 1000),
           _ev("dot.3", 6000, 500), _ev("late", 11000, 100)]
    mods = [_ev("jit__screen_round(7)", 2000, 1500),
            _ev("jit__inner_rounds(9)", 6000, 500)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                          NS(name="XLA Modules", events=mods)])
    return [host, dev, NS(name="/device:CPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("x", 1000, 9000)])])]


def test_union_merges_and_clips():
    s, e = trace.union([3, 1, 4, 9, 5], [5, 2, 8, 12, 6], 0, 10)
    assert s.tolist() == [1, 3, 9] and e.tolist() == [2, 8, 10]
    s, e = trace.union([1], [2], 5, 6)
    assert s.size == 0 and e.size == 0


def test_reduction_of_a_hand_made_trace():
    s = trace.reduce_planes(_planes())
    assert s.devices == 1                       # the CPU plane is no device
    assert s.window_s == pytest.approx(9e-6)
    assert s.busy_s == pytest.approx(2e-6)      # [2000, 3500] and [6000, 6500]
    assert s.executions == {"jit__screen_round": [pytest.approx(1.5e-6)],
                            "jit__inner_rounds": [pytest.approx(5e-7)]}
    assert s.device_seconds(r"^jit__screen_round$") == (1, pytest.approx(1.5e-6))
    assert set(s.gaps) == {"bench.session", "bench.solve_path"}
    assert s.gaps["bench.session"] == pytest.approx([1e-6])
    assert sorted(s.gaps["bench.solve_path"]) == pytest.approx([2.5e-6, 3.5e-6])
    b = s.breakdown()
    assert b["device_ops"][0] == ["jit__screen_round", pytest.approx(1.5e-6)]
    assert b["idle_gaps"][0][0].startswith("bench.solve_path: 2 gaps")


def test_without_per_operation_events_programs_are_the_busy_time():
    """Busy time is the union of program executions: a per-operation
    line, present or not, changes nothing."""
    planes = _planes()
    planes[1].lines = [line for line in planes[1].lines
                       if line.name != "XLA Ops"]
    s = trace.reduce_planes(planes)
    assert s.busy_s == pytest.approx(2e-6)      # [2000, 3500] and [6000, 6500]
    assert s.busy_s == trace.reduce_planes(_planes()).busy_s
    assert s.device_seconds(r"^jit__inner_rounds$") == (1, pytest.approx(5e-7))


def _brute_busy(planes, lo_ns, hi_ns):
    """Busy time on a 1 ns grid: an independent count of the union."""
    grid = np.zeros(int(hi_ns - lo_ns), bool)
    for plane in planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for ev in line.events:
                a = int(max(ev.start_ns, lo_ns) - lo_ns)
                b = int(min(ev.start_ns + ev.duration_ns, hi_ns) - lo_ns)
                if b > a:
                    grid[a:b] = True
    return grid.sum() * 1e-9


def _chip_trace():
    import gzip

    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(gzip.decompress(
        SMALL_TRACE.read_bytes()))
    return data, list(data.planes)


def test_reduction_of_a_trace_recorded_on_the_chip():
    """One path of a 30 x 200 problem, recorded on a TPU v5e inside
    ``bench.window`` with the driver's spans, compiled as the benchmark
    compiles (``bench/tests/record_trace.py``)."""
    _data, planes = _chip_trace()
    s = trace.reduce_planes(planes)
    assert s.busy_s == trace.reduce_file(str(SMALL_TRACE)).busy_s
    assert s.devices == 1
    lo, hi = s.window
    assert 0 < s.busy_s < s.window_s
    assert s.busy_s == pytest.approx(
        _brute_busy(planes, round(lo * 1e9), round(hi * 1e9)), rel=1e-3)
    counters = json.loads(COUNTERS.read_text())
    assert s.device_seconds(r"^jit__screen_round$")[0] == counters["n_full_rounds"]
    assert set(s.gaps) <= {"bench.window", "bench.cycle", "bench.session",
                           "bench.solve_path"}
    assert sum(g.sum() for g in s.gaps.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)


@pytest.mark.parametrize("extra", [0, 1])
def test_a_trace_cut_short_reads_no_metric(extra):
    """The full rounds in the trace are held against the solve's count; a
    trace that holds fewer is read by no trace metric."""
    from bench import run

    _data, planes = _chip_trace()
    s = trace.reduce_planes(planes)
    counters = json.loads(COUNTERS.read_text())
    counters["n_full_rounds"] += extra
    expected = {r"^jit__screen_round$": counters["n_full_rounds"]}
    ctx = {"trace": s, "trace_complete": run.trace_complete(s, expected),
           "counters": counters, "shape": (30, 200),
           "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9}}
    assert ctx["trace_complete"] == (extra == 0)
    for name in ("device_idle_share", "round_ms_per_path",
                 "epoch_ms_per_path", "full_round_roofline"):
        value = run.load_file(run.ROOT / "bench" / "metrics"
                              / f"{name}.py").read(ctx)
        assert (value is None) == bool(extra), name
