"""Device idle time under the program's own spans.

While a profiler session records, ``repro.obs.trace`` writes the
program's spans (``path``, ``lambda``, ``round``, ``epoch_block``,
``read``, ``masks``, ``gather``, ...) to the host plane as
``repro.<site>`` annotations.  This reduction keeps them beside the
benchmark's ``bench.*`` spans, within the window that ``bench/trace.py``
uses:

* ``intervals``: each span name's intervals inside the window;
* ``idle_s``: the device-idle seconds inside each name's spans (the union
  of its intervals, mean over devices);
* ``gaps``: the idle gaps labelled by the innermost span of either family,
  so that the gaps inside ``bench.solve_path`` read ``repro.read``,
  ``repro.masks``, ... where the program has the spans.

Busy time is the union of program executions, as in ``bench/trace.py``.

The device's executions and the host's spans come from two clocks: in
traces taken on a TPU v5e the executions read 0.3 to 1.2 ms early
against the host, by a different amount in each profiler session, which
is as long as a gap.  So the device's times are first shifted by the
least amount that starts no execution before the host call that launched
it (:func:`clock_offset`).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

from bench import trace

PREFIXES = (trace.SPAN_PREFIX, "repro.")
#: The runtime's host event of one program launch (PJRT's C API entry).
LAUNCH = "PJRT_LoadedExecutable_Execute"


@dataclass
class Spans:
    window: tuple
    devices: int
    offset_s: float                                # added to device times
    intervals: dict = field(default_factory=dict)  # name -> (k, 2) array
    idle_s: dict = field(default_factory=dict)     # name -> seconds
    gaps: dict = field(default_factory=dict)       # label -> array of s

    def count(self, name: str) -> int:
        return len(self.intervals.get(name, ()))

    def idle_under(self, pattern: str) -> float:
        """Idle seconds of the gaps whose label matches ``pattern``."""
        rx = re.compile(pattern)
        return float(sum(g.sum() for label, g in self.gaps.items()
                         if rx.search(label)))

    def breakdown(self) -> list:
        """``breakdown.idle_gaps`` of ``bench/run.py``, labelled by both
        families, largest first."""
        return [[f"{label}: {g.size} gaps, longest {float(g.max())!r} s",
                 float(g.sum())]
                for label, g in sorted(self.gaps.items(),
                                       key=lambda kv: -kv[1].sum())]


def host_spans(planes) -> list:
    """(name, start s, end s) of every ``bench.*`` and ``repro.*`` span."""
    return [(ev.name, ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9)
            for plane in planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PREFIXES)]


def _executions(planes) -> list:
    """(starts, ends) of the program executions of each device."""
    devices = []
    for plane in planes:
        if not trace._DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if trace.MODULES_LINE in lines:
            _names, s, d = trace._events(lines[trace.MODULES_LINE])
            if s.size:
                devices.append((s, s + d))
    return devices


def clock_offset(planes) -> float:
    """Seconds to add to the device's times to put them on the host's
    clock: the least shift after which no execution starts before its
    launch.  Launches (``LAUNCH`` events on the host) pair with the
    executions in order on one device; without one launch per execution,
    or with several devices, no shift (0)."""
    devices = _executions(planes)
    launches = np.sort([ev.start_ns * 1e-9
                        for plane in planes if plane.name.startswith("/host:")
                        for line in plane.lines for ev in line.events
                        if ev.name == LAUNCH])
    if len(devices) != 1 or launches.size != devices[0][0].size:
        return 0.0
    return max(0.0, float((launches - np.sort(devices[0][0])).max()))


def _measure(starts, ends) -> float:
    return float((np.asarray(ends) - np.asarray(starts)).sum())


def reduce_planes(planes, window: str = "bench.window",
                  offset: float | None = None) -> Spans:
    """``offset``: seconds added to the device's times; by default
    :func:`clock_offset` of the trace."""
    planes = list(planes)
    spans = host_spans(planes)
    if offset is None:
        offset = clock_offset(planes)
    devices = [(s + offset, e + offset) for s, e in _executions(planes)]
    if not devices:
        raise ValueError("the trace holds no program executions on a device")
    win = [(a, b) for name, a, b in spans if name == window]
    if win:
        lo, hi = win[0]
    else:
        lo = min(float(s.min()) for s, _e in devices)
        hi = max(float(e.max()) for _s, e in devices)
    inside = [sp for sp in spans if sp[2] > lo and sp[1] < hi]
    by_name: dict = {}
    for name, a, b in inside:
        by_name.setdefault(name, []).append((max(a, lo), min(b, hi)))
    intervals = {k: np.asarray(sorted(v)) for k, v in by_name.items()}
    idle_s = dict.fromkeys(intervals, 0.0)
    gap_lists: dict = {}
    for starts, ends in devices:
        bs, be = trace.union(starts, ends, lo, hi)
        gs = np.concatenate([[lo], be])
        ge = np.concatenate([bs, [hi]])
        keep = ge > gs
        gs, ge = gs[keep], ge[keep]
        labels = trace._labels(inside, 0.5 * (gs + ge))
        for label in set(labels):
            gap_lists.setdefault(label, []).append((ge - gs)[labels == label])
        for name, iv in intervals.items():
            ss, se = trace.union(iv[:, 0], iv[:, 1], lo, hi)
            both = trace.union(np.concatenate([gs, ss]),
                               np.concatenate([ge, se]), lo, hi)
            # |gaps ∩ spans| = |gaps| + |spans| - |gaps ∪ spans|
            idle_s[name] += (_measure(gs, ge) + _measure(ss, se)
                             - _measure(*both))
    n = len(devices)
    return Spans(window=(lo, hi), devices=n, offset_s=offset,
                 intervals=intervals,
                 idle_s={k: v / n for k, v in idle_s.items()},
                 gaps={k: np.concatenate(v) for k, v in gap_lists.items()})


def load_planes(path: str):
    """The profile at ``path`` (``.xplane.pb``, or gzipped) and its planes;
    keep the first alive as long as the planes are read."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    return data, list(data.planes)


def reduce_file(path: str, window: str = "bench.window",
                offset: float | None = None) -> Spans:
    _data, planes = load_planes(path)
    return reduce_planes(planes, window, offset)
