"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
device time per program, and idle gaps labelled by the host's spans.

Planes named ``/device:<KIND>:<i>`` (not CPU) are devices.  On each, the
line ``XLA Modules`` holds one event per execution of a compiled program,
named ``jit_<function>(<id>)``; the union of those executions is the
device's busy time.  (The benchmark compiles without per-HLO tracing, so
a per-operation line, where a trace has one, is not read.)  The host
plane ``/host:CPU`` holds the ``jax.profiler.TraceAnnotation`` spans; the
one named by ``window`` bounds the reduction, and the ``bench.*`` spans
label the idle gaps.  All times are on the profiler's clock, in seconds.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

_ID_SUFFIX = re.compile(r"\(\d+\)$")
_DEVICE_PLANE = re.compile(r"^/device:(?!CPU)[A-Za-z]+:\d+$")
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def program_name(event_name: str) -> str:
    """``jit__screen_round(42)`` -> ``jit__screen_round``."""
    return _ID_SUFFIX.sub("", event_name)


def union(starts, ends, lo: float, hi: float):
    """Merged intervals of ``[starts, ends)`` clipped to ``[lo, hi]``, as
    two arrays (merged starts, merged ends)."""
    s = np.clip(np.asarray(starts, float), lo, hi)
    e = np.clip(np.asarray(ends, float), lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if s.size == 0:
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return s[first], reach[last]


@dataclass
class Summary:
    window: tuple                       # (start, end) on the trace clock
    busy_s: float                       # mean over devices in use
    devices: int
    executions: dict = field(default_factory=dict)  # program -> [seconds]
    gaps: dict = field(default_factory=dict)  # label -> array of seconds

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def device_seconds(self, pattern: str) -> tuple:
        """(executions, device seconds) of the programs matching
        ``pattern`` (a regular expression searched in the program name)."""
        rx = re.compile(pattern)
        runs = [t for name, ts in self.executions.items() if rx.search(name)
                for t in ts]
        return len(runs), float(sum(runs))

    def breakdown(self, top: int = 10) -> dict:
        progs = sorted(((n, float(sum(ts))) for n, ts in self.executions.items()),
                       key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1].sum())[:top]
        return {
            "device_ops": [[n, s] for n, s in progs],
            "idle_gaps": [[f"{label}: {g.size} gaps, longest {float(g.max())!r} s",
                           float(g.sum())] for label, g in gaps],
        }


def _host_spans(planes) -> list:
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9))
    return spans


def _labels(spans, mids: np.ndarray) -> np.ndarray:
    """For each time in ``mids``, the innermost span that covers it."""
    out = np.full(mids.shape, "outside any span", dtype=object)
    for name, a, b in sorted(spans, key=lambda sp: sp[1] - sp[2]):
        out[(mids >= a) & (mids <= b)] = name       # widest first, so the
    return out                                      # innermost wins


def _events(line):
    """(names, start seconds, duration seconds) of a line's events."""
    names, starts, durs = [], [], []
    for ev in line.events:
        names.append(ev.name)
        starts.append(ev.start_ns)
        durs.append(ev.duration_ns)
    return names, np.asarray(starts, float) * 1e-9, np.asarray(durs, float) * 1e-9


def reduce_planes(planes, window: str = "bench.window") -> Summary:
    planes = list(planes)
    spans = _host_spans(planes)
    win = [(a, b) for name, a, b in spans if name == window]
    devices = []
    for plane in planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if MODULES_LINE in lines:
            mods = _events(lines[MODULES_LINE])
            if mods[1].size:
                devices.append(mods)
    if not devices:
        raise ValueError("the trace holds no program executions on a device")
    if win:
        lo, hi = win[0]
    else:
        lo = min(float(s.min()) for _n, s, _d in devices)
        hi = max(float((s + d).max()) for _n, s, d in devices)
    busy_total = 0.0
    executions: dict = {}
    gap_lists: dict = {}
    for names, s, d in devices:
        bs, be = union(s, s + d, lo, hi)
        busy_total += float((be - bs).sum())
        for k in np.flatnonzero((s >= lo) & (s <= hi)):
            executions.setdefault(program_name(names[k]), []).append(float(d[k]))
        gs = np.concatenate([[lo], be])
        ge = np.concatenate([bs, [hi]])
        keep = ge > gs
        gs, ge = gs[keep], ge[keep]
        labels = _labels(spans, 0.5 * (gs + ge))
        for label in set(labels):
            gap_lists.setdefault(label, []).append((ge - gs)[labels == label])
    gaps = {k: np.concatenate(v) for k, v in gap_lists.items()}
    return Summary(window=(lo, hi), busy_s=busy_total / len(devices),
                   devices=len(devices), executions=executions, gaps=gaps)


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {found}")
    return found[0]


def reduce_file(path: str, window: str = "bench.window") -> Summary:
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)  # the planes live as long as it
    return reduce_planes(data.planes, window)


def reduce_dir(trace_dir: str, window: str = "bench.window") -> Summary:
    return reduce_file(find_xplane(trace_dir), window)
