"""Measured kernel timing for every registered ``LaunchSpec`` kernel.

The dry-run roofline (:mod:`repro.launch.roofline`) predicts time from HLO
costs without ever running anything; this harness produces the matching
*measured* term.  Discipline, per the accelerator timing guide:

1. jit-warm: call each dispatch wrapper ``warmup`` times and
   ``jax.block_until_ready`` the result, so compile/trace time never
   pollutes a sample;
2. time ``repeat`` calls individually, each fenced by
   ``block_until_ready`` (JAX dispatch is asynchronous — un-fenced
   wall-clock measures the host, not the kernel);
3. report the median (robust) and the min (best-case) and feed the median
   to :func:`repro.launch.roofline.achieved_vs_peak`.

Each timed case mirrors one ``register_kernel_audit`` entry from
:mod:`repro.kernels.ops` — same kernel family, same dispatch wrapper the
solver uses.  ``scale="smoke"`` shrinks the geometry so interpret-mode CPU
(where Pallas executes the grid in Python) stays fast enough for CI;
``scale="paper"`` uses the registered audit shapes and is the setting that
matters on a real accelerator.  On CPU the numbers are an interpret-mode
dispatch story, not a speed story — ``interpret=True`` is stamped into
every row so BENCH readers can tell.

Flops/bytes are hand-written model formulas per kernel (documented inline);
``LaunchSpec.io_bytes`` (unique-bytes lower bound) is the fallback.
"""
from __future__ import annotations

import statistics
import time
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import numpy as np

from ..kernels._util import on_tpu
from ..kernels.cases import kernel_cases
from ..launch.roofline import achieved_vs_peak
from . import metrics as obs_metrics

_M_MEASURED = obs_metrics.REGISTRY.histogram(
    "kernels.measured_wall_s",
    help="Median measured kernel wall-clock per timing-harness case "
         "(jit-warm + block_until_ready)")


class TimingCase(NamedTuple):
    """One timed kernel: geometry per scale + flops/bytes model.

    The arguments come from :func:`repro.kernels.cases.kernel_cases` at
    ``geometry(scale)``, so ``fn(*args)`` is exactly the dispatch wrapper
    the solver calls.  ``model(geometry, itemsize)`` returns the hand
    model ``(flops, bytes)`` of one call.
    """

    audit_name: str
    kernel: str
    geometry: Callable[[str], dict]
    model: Callable[[dict, int], Tuple[float, float]]

    def build(self, scale: str, dtype) -> Tuple[Callable, tuple, float,
                                                 float]:
        geo = self.geometry(scale)
        case = kernel_cases(dtype=dtype, **geo)[self.kernel]
        args = case.make_args(jax.random.PRNGKey(0))
        flops, bts = self.model(geo, np.dtype(dtype).itemsize)
        return case.fn, args, flops, bts


def _corr_geom(scale: str) -> dict:
    # p = G * ng rows of the transposed design
    return (dict(n=256, G=64, ng=8) if scale == "smoke"
            else dict(n=1024, G=512, ng=8))


def _group_geom(scale: str) -> dict:
    return dict(n=1, G=512 if scale == "smoke" else 4096, ng=8)


def _bcd_geom(scale: str, bucket: bool) -> dict:
    if scale == "smoke":
        return dict(B=2 if bucket else 1, G=16, n=128,
                    ng=16 if bucket else 8, n_epochs=2)
    return (dict(B=4, G=256, n=1024, ng=16, n_epochs=3) if bucket
            else dict(B=1, G=64, n=2048, ng=8, n_epochs=2))


def _corr_model(g, s):
    # matvec: 2 flops per (p, n) cell; traffic: design + vector + result
    p, n = g["G"] * g["ng"], g["n"]
    return 2.0 * p * n, s * (p * n + n + p)


def _scores_model(g, s):
    # corr matvec + fused soft-threshold square (~4 flops/row)
    p, n = g["G"] * g["ng"], g["n"]
    return 2.0 * p * n + 4.0 * p, s * (p * n + n + 2 * p)


def _dual_norm_model(g, s, n_iter=64):
    # bisection: ~4 flops per feature per iteration (shrink, square, sum)
    G, ng = g["G"], g["ng"]
    return 4.0 * G * ng * n_iter, s * (G * ng + 3 * G)


def _prox_model(g, s):
    # two-level prox: ~6 flops per feature (shrink + norm + group scale)
    G, ng = g["G"], g["ng"]
    return 6.0 * G * ng, s * (2 * G * ng + 2 * G)


def _bcd_model(g, s):
    # per epoch, group: corr (2·n·ng) + residual rank-1 update (2·n·ng);
    # design streamed once per epoch; state read+written once
    B, G, n, ng, E = g["B"], g["G"], g["n"], g["ng"], g["n_epochs"]
    return (4.0 * E * B * G * n * ng,
            s * (E * G * n * ng + 2 * (B * G * ng + B * n)))


def _bcd_logistic_model(g, s):
    # lsq-epoch work + sigmoid/gradient on the carry (~8 flops per sample)
    flops, bts = _bcd_model(g, s)
    B, G, n, E = g["B"], g["G"], g["n"], g["n_epochs"]
    return flops + 8.0 * E * B * G * n, bts + s * n


#: One timed case per registered kernel-audit family (names match
#: repro.kernels.ops register_kernel_audit entries).
CASES: Tuple[TimingCase, ...] = (
    TimingCase("bcd_epoch/bucket", "bcd_epoch",
               lambda s: _bcd_geom(s, bucket=True), _bcd_model),
    TimingCase("bcd_epoch/paper-ng8", "bcd_epoch",
               lambda s: _bcd_geom(s, bucket=False), _bcd_model),
    TimingCase("bcd_epoch_logistic/bucket", "bcd_epoch_logistic",
               lambda s: _bcd_geom(s, bucket=True), _bcd_logistic_model),
    TimingCase("screening_scores/default", "screening_scores",
               _corr_geom, _scores_model),
    TimingCase("screening_corr/default", "screening_corr",
               _corr_geom, _corr_model),
    TimingCase("dual_norm/paper-ng8", "dual_norm",
               _group_geom, _dual_norm_model),
    TimingCase("sgl_prox/paper-ng8", "sgl_prox", _group_geom, _prox_model),
)


def measure_one(fn: Callable, args: tuple, warmup: int = 2,
                repeat: int = 5,
                clock: Callable[[], float] = time.perf_counter) -> dict:
    """Warm + fenced timing of one callable; median/min over ``repeat``."""
    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(max(1, repeat)):
        t0 = clock()
        jax.block_until_ready(fn(*args))
        samples.append(clock() - t0)
    return {"median_s": statistics.median(samples), "min_s": min(samples),
            "samples": samples}


def measure_kernels(scale: str = "smoke", warmup: int = 2, repeat: int = 5,
                    names: Optional[Tuple[str, ...]] = None,
                    dtype=None) -> Dict[str, dict]:
    """Run the harness over every (or the named) registered kernel case.

    ``dtype`` defaults to f32 on a TPU (Mosaic compiles no 64-bit kernel)
    and f64 elsewhere.

    Returns per-kernel rows ready for the BENCH ``kernels`` section:
    measured wall-clock, model flops/bytes, the audited LaunchSpec's VMEM
    footprint, and the ``achieved_vs_peak`` roofline column.
    """
    from ..analysis.registry import kernel_audits

    audits = kernel_audits()
    if dtype is None:
        dtype = np.float32 if on_tpu() else np.float64
    out: Dict[str, dict] = {}
    for case in CASES:
        if names is not None and case.audit_name not in names:
            continue
        fn, args, flops, bts = case.build(scale, dtype)
        t = measure_one(fn, args, warmup=warmup, repeat=repeat)
        _M_MEASURED.observe(t["median_s"])
        row = {
            "scale": scale,
            "interpret": not on_tpu(),
            "dtype": np.dtype(dtype).name,
            "measured_s": t["median_s"],
            "min_s": t["min_s"],
            "model_flops": flops,
            "model_bytes": bts,
            "achieved": achieved_vs_peak(flops, bts, t["median_s"]),
        }
        builder = audits.get(case.audit_name)
        if builder is not None:
            spec = builder()
            row["vmem_bytes"] = spec.vmem_bytes
            row["audit_io_bytes"] = spec.io_bytes
        out[case.audit_name] = row
    return out
