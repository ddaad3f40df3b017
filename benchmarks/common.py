"""Shared helpers for the benchmark harness.

Every benchmark emits rows through ``emit`` so ``benchmarks.run`` can
aggregate a single CSV:  benchmark,case,metric,value

``write_json`` additionally dumps the emitted rows (plus environment
metadata) to a machine-readable JSON file — the perf-trajectory record
(e.g. ``BENCH_pr4.json``) future PRs diff against instead of prose in
CHANGES.md.
"""
from __future__ import annotations

import json
import platform
import time
from typing import Callable

import jax

from repro.compile_cache import enable_compile_cache
from repro.obs.export import env_meta

# The convex-optimization core targets the paper's 1e-8 duality-gap
# tolerance, which needs f64 (same switch the tests flip in conftest.py).
jax.config.update("jax_enable_x64", True)
enable_compile_cache()

_ROWS: list[tuple[str, str, str, float]] = []


def emit(bench: str, case: str, metric: str, value) -> None:
    _ROWS.append((bench, case, metric, float(value)))
    print(f"{bench},{case},{metric},{value}")


def rows():
    return list(_ROWS)


def write_json(path: str, extra: dict | None = None) -> None:
    """Dump every row emitted so far (plus environment metadata) as JSON.

    Schema: ``{"meta": {...}, "rows": [{benchmark, case, metric, value}]}``
    — flat rows rather than nesting so a diff tool can join on
    (benchmark, case, metric) without knowing any benchmark's shape.
    """
    # Environment metadata comes from the one shared exporter
    # (repro.obs.export.env_meta); the historical key names and the OS
    # platform string are layered on top so existing diff tooling keeps
    # joining on the same fields.
    meta = env_meta()
    meta.update({
        "jax_version": jax.__version__,
        "platform": platform.platform(),
        **(extra or {}),
    })
    payload = {
        "meta": meta,
        "rows": [
            {"benchmark": b, "case": c, "metric": m, "value": v}
            for b, c, m, v in _ROWS
        ],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {len(_ROWS)} rows -> {path}")


def timeit(fn: Callable, *args, warmup: int = 1, repeat: int = 3) -> float:
    """Median wall-clock seconds for ``fn(*args)`` (blocks on jax arrays)."""
    def run():
        out = fn(*args)
        jax.block_until_ready(out)
        return out

    for _ in range(warmup):
        run()
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def header() -> None:
    print("benchmark,case,metric,value")
