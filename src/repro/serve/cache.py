"""Session/compile cache: repeat tenants never re-trace.

A :class:`repro.core.session.SGLSession` owns every expensive per-problem
artifact — the jit-warm solver programs, the persistent transposed design,
``lam_max``, the gather caches.  :class:`SessionCache` keeps an LRU of
sessions keyed on the problem *value* digest + the config's
:meth:`SolverConfig.cache_token`, so a repeat tenant (or a new tenant with
the same problem) reuses the compiled machinery outright.

Two sub-caches sharpen the miss path:

* **shared transposed design** — ``prepare_transposed(X)`` depends only on
  X, so perturbed-``y`` tenants (new problem digest, same design) adopt
  the cached copy through ``SGLSession(xt_pre=...)`` instead of
  re-materialising the (p, n) layout (``design_hits`` counts these);
* **retrace watch** — the `kernels.ops` retrace audit as the cache's
  correctness check: :meth:`watch_retraces` snapshots the jit-cache sizes
  of every registered traceable around a served request; growth during a
  request that hit the cache with an exact-repeat digest is a retrace
  regression, counted on the cache AND fed to
  :func:`repro.kernels.ops.note_retrace` so ``kernels.ops.audit_scope``
  (and the tests built on it) see it.
"""
from __future__ import annotations

import contextlib
from collections import OrderedDict
from typing import Optional

from ..core.session import SGLSession, SolverConfig
from ..core.sgl import SGLProblem
from ..core.solver import resolve_screen_backend, resolve_solver_backend
from ..kernels import ops as kops
from ..losses import resolve_loss
from ..obs import metrics as obs_metrics
from .types import array_digest, problem_digest

__all__ = ["SessionCache"]

_CACHE_COUNTERS = {
    "hits": "Session-cache hits (jit-warm session reused)",
    "misses": "Session-cache misses (fresh session built)",
    "evictions": "Sessions evicted by the LRU capacity bound",
    "design_hits": "Transposed-design sub-cache hits across tenants",
    "retraces": "Jit-cache growth observed by watch_retraces on a hit",
    "loss_rejects": "Cache hits refused for a mismatched loss (collision)",
}
for _k, _h in _CACHE_COUNTERS.items():
    obs_metrics.declare("serve.cache_" + _k, "counter", _h)


def _counter_attr(key: str):
    """Int-attribute shim over a registry counter (``self.hits += 1`` and
    plain reads keep working while the number lives on the registry)."""

    def _get(self) -> int:
        return self._m[key].value

    def _set(self, v: int) -> None:
        self._m[key]._set(int(v))

    return property(_get, _set, doc=_CACHE_COUNTERS[key])


def _traceable_cache_sizes() -> int:
    """Total jit-cache entries across every registered traceable (the
    same objects the analysis retrace harness watches)."""
    import repro.core.session  # noqa: F401  (registers core traceables)
    import repro.serve.store   # noqa: F401  (registers serve_warm_eval)

    from ..analysis.registry import traceables

    total = 0
    for entry in traceables().values():
        fn = entry["fn"]
        if hasattr(fn, "_cache_size"):
            total += fn._cache_size()
    return total


class SessionCache:
    """LRU of jit-warm :class:`SGLSession` objects, value-keyed.

    ``capacity=0`` disables caching (every lookup is a miss and nothing
    is retained — the shared transposed-design sub-cache is bypassed
    too) — the serving benchmark's fully-cold no-cache baseline.
    """

    def __init__(self, capacity: int = 8, design_capacity: int = 8):
        self.capacity = int(capacity)
        self.design_capacity = int(design_capacity)
        self._sessions: OrderedDict[tuple, SGLSession] = OrderedDict()
        self._designs: OrderedDict[str, object] = OrderedDict()
        # Per-cache registry under the shared declared names; the historic
        # int attributes (hits/misses/...) are properties over it.
        self.metrics = obs_metrics.MetricsRegistry()
        self._m = {k: self.metrics.counter("serve.cache_" + k)
                   for k in _CACHE_COUNTERS}

    hits = _counter_attr("hits")
    misses = _counter_attr("misses")
    evictions = _counter_attr("evictions")
    design_hits = _counter_attr("design_hits")
    retraces = _counter_attr("retraces")
    loss_rejects = _counter_attr("loss_rejects")

    # -- lookups -----------------------------------------------------------

    def key(self, problem: SGLProblem, config: SolverConfig) -> tuple:
        return (problem_digest(problem, config), config.cache_token())

    def get(self, problem: SGLProblem,
            config: SolverConfig) -> tuple[SGLSession, bool]:
        """``(session, hit)`` — builds (and caches) a session on miss."""
        key = self.key(problem, config)
        sess = self._sessions.get(key)
        if sess is not None:
            if repr(sess.loss) != repr(resolve_loss(config.loss)):
                # Defense-in-depth: the key already hashes the loss (via
                # cache_token), so a hit with a mismatched loss means the
                # keying itself regressed — refuse to hand a tenant a
                # session compiled for another data fidelity.
                self.loss_rejects += 1
                raise RuntimeError(
                    f"session-cache key collision across losses: cached "
                    f"session solves {sess.loss.name!r}, request asks "
                    f"for {resolve_loss(config.loss).name!r}"
                )
            self._sessions.move_to_end(key)
            self.hits += 1
            return sess, True
        self.misses += 1
        sess = self._build(problem, config)
        if self.capacity > 0:
            self._sessions[key] = sess
            while len(self._sessions) > self.capacity:
                self._sessions.popitem(last=False)
                self.evictions += 1
        return sess, False

    def _build(self, problem: SGLProblem, config: SolverConfig) -> SGLSession:
        xt_pre = None
        dtype = problem.X.dtype
        needs_xt = (resolve_screen_backend(config.screen_backend, dtype)
                    == "pallas"
                    or resolve_solver_backend(config.solver_backend, dtype)
                    == "pallas")
        # capacity=0 means fully cold: no design reuse either, so the
        # no-cache baseline really rebuilds everything per request.
        if needs_xt and self.capacity > 0 and self.design_capacity > 0:
            dkey = array_digest(problem.X)
            xt_pre = self._designs.get(dkey)
            if xt_pre is not None:
                self._designs.move_to_end(dkey)
                self.design_hits += 1
            else:
                xt_pre = kops.prepare_transposed(problem.X)
                self._designs[dkey] = xt_pre
                while len(self._designs) > self.design_capacity:
                    self._designs.popitem(last=False)
        return SGLSession(problem, config, xt_pre=xt_pre)

    # -- retrace watch (cache correctness check) ---------------------------

    @contextlib.contextmanager
    def watch_retraces(self):
        """Assert-by-measurement that a cached session really is jit-warm.

        Opened by the server around exact-repeat requests served from a
        cache hit: any jit-cache growth across the watched block means the
        "cached" session retraced — counted on ``self.retraces`` and
        reported through :func:`repro.kernels.ops.note_retrace` so
        ``audit_scope`` assertions catch it.
        """
        before = _traceable_cache_sizes()
        try:
            yield
        finally:
            delta = _traceable_cache_sizes() - before
            if delta > 0:
                self.retraces += delta
                kops.note_retrace(delta)

    # -- reporting ---------------------------------------------------------

    def stats(self) -> dict:
        return {
            "sessions": len(self._sessions),
            "capacity": self.capacity,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "design_hits": self.design_hits,
            "retraces": self.retraces,
            "loss_rejects": self.loss_rejects,
        }
