"""repro.obs — unified observability for the SGL reproduction.

Third leg of the tooling triad next to :mod:`repro.analysis` (static
correctness) and :mod:`repro.faults` (robustness): *measurement*.

Pieces
------
:mod:`repro.obs.metrics`
    Typed metrics registry — ``Counter`` / ``Gauge`` / ``Histogram`` with
    fixed declared names and help text, thread-safe, plus snapshot / diff /
    reset scoping that subsumes the old ``kernels.ops.audit_scope()`` idiom.
    The scattered ad-hoc counters (kernels.ops transpose/retrace/demotion
    globals, ``SGLServer.counters``, ``SessionCache`` hit/miss counts, the
    ckpt quarantine tally) are all backed by it; the legacy surfaces remain
    as back-compat shims.

:mod:`repro.obs.trace`
    Structured tracing: nested spans ``serve.request → serve.coalesce →
    path → lambda → round / epoch_block / read / masks / gather`` with an
    injectable monotonic clock, a bounded ring buffer, JSONL export and
    percentile aggregation.  Span *recording* is sampled; per-site fire
    counters are always exact.  While a JAX profiler session records, the
    same spans are also written to the profiler trace as ``repro.<name>``
    annotations, on the device trace's clock.  Both outputs are OFF by
    default, and then the span sites allocate nothing and take no lock —
    hot solver loops see a global read and one ``is_enabled()`` call
    returning a no-op singleton.

:mod:`repro.obs.timing`
    Measured kernel timing: a jit-warm + ``block_until_ready`` harness
    around every registered ``LaunchSpec`` kernel, feeding
    :func:`repro.launch.roofline.achieved_vs_peak`.

:mod:`repro.obs.export`
    The one percentile implementation and the unified BENCH JSON schema
    (``repro.obs.bench/v1``) shared by ``benchmarks/``.

:mod:`repro.obs.check`
    ``python -m repro.obs --check`` self-audit gate: every declared metric
    documented (OB001), every declared span site fires on a smoke path
    (OB002); analysis-style findings, re-renderable via
    ``reanalyze --obs``.

Enabling
--------
The ring buffer is opt-in per process::

    from repro.obs import trace
    trace.configure(enabled=True)        # or REPRO_OBS=1 in the env
    ... run ...
    trace.TRACER.export_jsonl("spans.jsonl")

The profiler output needs no configuration: any ``jax.profiler`` session
(``start_trace``/``stop_trace``, TensorBoard's capture) turns it on.

Metrics counters are always live (they are just locked ints — the
pre-obs code paths already paid for plain module globals / dict writes).
"""
from __future__ import annotations

import os

from . import metrics, trace  # noqa: F401  (leaf modules)

if os.environ.get("REPRO_OBS", "") not in ("", "0"):  # pragma: no cover
    trace.configure(enabled=True)
