"""The program's own spans in a profiler trace: gap labels and idle time
under ``repro.*`` spans (``bench/spans.py``), and the reader of
``host_syncs_per_path``."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import run, spans, trace

DATA = Path(__file__).parent / "data"
SPANLESS_TRACE = DATA / "small_path.xplane.pb.gz"
SPANS_TRACE = DATA / "small_path_spans.xplane.pb.gz"
SPANS_COUNTERS = DATA / "small_path_spans.counters.json"


def _ev(name, start_ns, dur_ns, **stats):
    return NS(name=name, start_ns=start_ns, duration_ns=dur_ns,
              stats=list(stats.items()))


def _planes():
    """bench.window [1000, 10000]; bench.solve_path [3000, 10000] holding
    repro.path [3100, 9900], repro.lambda [3200, 9800] and, inside it,
    a round, two reads and a masks span.  The device runs
    [2000, 3500] and [6000, 6500]."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 1000, 9000),
        _ev("bench.session", 1000, 2000),
        _ev("bench.solve_path", 3000, 7000),
        _ev("repro.path", 3100, 6800),
        _ev("repro.lambda", 3200, 6600),
        _ev("repro.round", 3200, 200, compact=0),
        _ev("repro.read", 3400, 1000, what="gap"),
        _ev("repro.masks", 4500, 1400),
        _ev("repro.read", 4600, 200, what="masks"),
        _ev("repro.read", 6600, 3000, what="k_done"),
        _ev("outside.anything", 1000, 9000),
    ])])
    mods = [_ev("jit__screen_round(7)", 2000, 1500),
            _ev("jit__inner_rounds(9)", 6000, 500)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Modules",
                                              events=mods)])
    return [host, dev]


def test_idle_gaps_carry_the_innermost_span_of_either_family():
    s = spans.reduce_planes(_planes())
    assert s.window == pytest.approx((1e-6, 1e-5))
    assert s.count("repro.read") == 3 and s.count("repro.path") == 1
    assert "outside.anything" not in s.intervals
    # gaps: [1000, 2000] session; [3500, 6000] mid 4750 -> read (masks);
    # [6500, 10000] mid 8250 -> read (k_done)
    assert set(s.gaps) == {"bench.session", "repro.read"}
    assert s.gaps["bench.session"] == pytest.approx([1e-6])
    assert sorted(s.gaps["repro.read"]) == pytest.approx([2.5e-6, 3.5e-6])
    assert s.breakdown()[0][0].startswith("repro.read: 2 gaps")
    assert s.idle_under(r"^repro\.") == pytest.approx(6e-6)


def test_idle_seconds_inside_each_span_name():
    s = spans.reduce_planes(_planes())
    # idle is [1000, 2000], [3500, 6000] and [6500, 10000]
    assert s.idle_s["repro.path"] == pytest.approx(
        (6000 - 3500 + 9900 - 6500) * 1e-9)
    assert s.idle_s["repro.lambda"] == pytest.approx(
        (6000 - 3500 + 9800 - 6500) * 1e-9)
    # reads [3400, 4400], [4600, 4800], [6600, 9600]: union, then overlap
    assert s.idle_s["repro.read"] == pytest.approx((900 + 200 + 3000) * 1e-9)
    assert s.idle_s["repro.masks"] == pytest.approx(1400e-9)
    assert s.idle_s["repro.round"] == pytest.approx(0.0)
    assert s.idle_s["bench.window"] == pytest.approx(
        s.window[1] - s.window[0] - 2e-6)


def test_the_bench_labels_are_unchanged_where_the_program_has_no_spans():
    """On the older chip trace, written before the program had profiler
    spans, the reduction reads the gaps exactly as ``bench/trace.py``."""
    _data, planes = spans.load_planes(str(SPANLESS_TRACE))
    s = spans.reduce_planes(planes, offset=0.0)
    old = trace.reduce_planes(planes)
    assert s.window == old.window
    assert set(s.gaps) == set(old.gaps)
    for label, g in old.gaps.items():
        np.testing.assert_array_equal(np.sort(s.gaps[label]), np.sort(g))
    assert not any(name.startswith("repro.") for name in s.intervals)


def test_device_times_move_to_the_host_clock_by_the_launches():
    """Each execution starts no earlier than its launch on the host: the
    least shift that makes it so is the offset, and the gaps move with
    it."""
    planes = _planes()
    assert spans.clock_offset(planes) == 0.0          # no launches traced
    planes[0].lines.append(NS(name="main", events=[
        _ev(spans.LAUNCH, 1900, 50), _ev(spans.LAUNCH, 6400, 50)]))
    assert spans.clock_offset(planes) == pytest.approx(400e-9)
    s = spans.reduce_planes(planes)
    assert s.offset_s == pytest.approx(400e-9)
    # the device now runs [2400, 3900] and [6400, 6900]
    assert s.idle_s["repro.round"] == pytest.approx(0.0)
    # reads [3400, 4400], [4600, 4800], [6600, 9600]; 4100 ns unshifted
    assert s.idle_s["repro.read"] == pytest.approx((500 + 200 + 2700) * 1e-9)
    assert s.idle_s["repro.path"] == pytest.approx(
        (6400 - 3900 + 9900 - 6900) * 1e-9)
    planes[0].lines[-1].events.pop()                # one launch missing
    assert spans.clock_offset(planes) == 0.0


def _reader():
    return run.load_file(run.ROOT / "bench" / "metrics"
                         / "host_syncs_per_path.py")


@pytest.mark.parametrize("case,want", [
    ("complete", 37.5), ("cut_short", None), ("no_profiler_spans", None),
    ("parent_program", None), ("another_session", None)])
def test_host_syncs_per_path_reader(monkeypatch, case, want):
    from repro.obs import trace as obs_trace

    counts = {"path": 4, "lambda": 40, "read": 150}
    stub = NS(profiler_counts=lambda: dict(counts))
    if case == "no_profiler_spans":
        counts.clear()
    if case == "parent_program":            # a TRACER without the output
        stub = NS()
    if case == "another_session":
        counts["path"] = 9
    monkeypatch.setattr(obs_trace, "TRACER", stub)
    ctx = {"trace_complete": case != "cut_short",
           "counters": {"paths": 4}}
    assert _reader().read(ctx) == want


def _span_events(planes):
    """(name, start s, end s, metadata) of the ``repro.*`` spans, in time
    order."""
    return sorted((ev.name, ev.start_ns * 1e-9,
                   (ev.start_ns + ev.duration_ns) * 1e-9, dict(ev.stats))
                  for plane in planes if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("repro."))


def _runs(planes, program):
    """(start s, end s) of a program's executions, in time order."""
    return np.asarray(sorted(
        (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
        for plane in planes if trace._DEVICE_PLANE.match(plane.name)
        for line in plane.lines if line.name == trace.MODULES_LINE
        for ev in line.events if trace.program_name(ev.name) == program))


def test_span_counts_of_a_trace_recorded_on_the_chip():
    """One path of the 30 x 200 problem of ``record_trace.py``, recorded
    on a TPU v5e with the program's profiler spans: the spans match the
    device's program executions and the solve's counters, and the clock
    shift agrees with them."""
    _data, planes = spans.load_planes(str(SPANS_TRACE))
    counters = json.loads(SPANS_COUNTERS.read_text())
    s = spans.reduce_planes(planes)
    summary = trace.reduce_planes(planes)
    events = _span_events(planes)
    names = [e[0] for e in events]
    full = [e for e in events
            if e[0] == "repro.round" and not e[3]["compact"]]
    k_done = [e for e in events
              if e[0] == "repro.read" and e[3]["what"] == "k_done"]
    reads = [e[3]["what"] for e in events if e[0] == "repro.read"]
    rounds, blocks = (_runs(planes, "jit__screen_round"),
                      _runs(planes, "jit__inner_rounds"))
    assert names.count("repro.path") == counters["paths"] == 1
    assert len(full) == counters["n_full_rounds"] == len(rounds)
    assert names.count("repro.epoch_block") == len(blocks) == len(k_done)
    assert reads.count("gap") >= counters["n_full_rounds"]
    assert reads.count("result") == 2                  # one per lambda
    assert 0 < s.idle_s["repro.path"] <= s.idle_s["bench.solve_path"]
    # Shifted, each full round runs after its span opened and each epoch
    # block ends before the k_done read that waits for it returns.
    assert 0 < s.offset_s < 5e-3
    assert np.all(rounds[:, 0] + s.offset_s >= [e[1] for e in full])
    assert np.all(blocks[:, 1] + s.offset_s <= [e[2] for e in k_done])
    unshifted = spans.reduce_planes(planes, offset=0.0)
    assert sum(g.sum() for g in unshifted.gaps.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-9)
