"""repro.obs: metrics registry, tracing spans, scoping, the --check gate.

The contracts defended here, in the order they matter:

* **zero overhead off** — with neither output on (ring buffer, profiler
  session), ``span()`` returns the preallocated NOOP singleton and
  allocates no span and no annotation, and running a full solve with
  either output ON is bit-identical to OFF;
* **the profiler output** — under ``jax.profiler.start_trace`` the solve's
  spans land on the host plane as ``repro.<site>`` events, the same
  number on every identical solve;
* **scope parity** — ``MetricsRegistry.scope`` keeps the exact
  ``kernels.ops.audit_scope()`` semantics (zero on entry, live deltas,
  freeze on exit, outer values restored, nothing propagated);
* **back-compat shims** — ``SGLServer.counters`` still quacks like the
  dict it replaced, ``SessionCache.hits += 1`` still works;
* **exact counts, deterministic time** — span counters are exact under
  sampling and threads; an injected fake clock makes histograms and
  percentiles reproducible to the bit;
* **the gate finds things** — OB001/OB002 findings fire on seeded bad
  fixtures, and the live schema/snapshot pass clean.
"""
import collections
import json
import threading

import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.obs import check as ocheck
from repro.obs import export as oexport
from repro.obs import metrics as om
from repro.obs import trace as ot


# ---------------------------------------------------------------------------
# metrics: declarations, kinds, thread safety
# ---------------------------------------------------------------------------

def test_declare_enforces_names_and_kinds():
    with pytest.raises(ValueError):
        om.declare("NoDots", "counter", "x")
    with pytest.raises(ValueError):
        om.declare("Upper.case", "counter", "x")
    with pytest.raises(ValueError):
        om.declare("ok.name", "exotic", "x")
    om.declare("testobs.decl", "counter", "first help")
    om.declare("testobs.decl", "counter", "redeclare is idempotent")
    assert om.SCHEMA["testobs.decl"].help == "first help"
    with pytest.raises(ValueError):
        om.declare("testobs.decl", "gauge", "kind conflict")


def test_registry_requires_declaration():
    reg = om.MetricsRegistry()
    with pytest.raises(KeyError):
        reg.counter("testobs.never_declared")
    om.declare("testobs.kindmix", "counter", "h")
    with pytest.raises(TypeError):
        reg.gauge("testobs.kindmix")


def test_counter_threadsafe_exact():
    om.declare("testobs.threads", "counter", "h")
    c = om.MetricsRegistry().counter("testobs.threads")

    def worker():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000


def test_histogram_summary_and_percentile_match_numpy():
    om.declare("testobs.hist", "histogram", "h")
    h = om.MetricsRegistry().histogram("testobs.hist")
    vals = np.random.default_rng(3).standard_normal(257).tolist()
    for v in vals:
        h.observe(v)
    assert h.count == len(vals)
    assert h.vmin == min(vals) and h.vmax == max(vals)
    for q in (0.0, 12.5, 50.0, 90.0, 99.0, 100.0):
        assert h.percentile(q) == pytest.approx(np.percentile(vals, q),
                                                abs=1e-12)
    s = h.summary()
    assert s["count"] == len(vals)
    assert s["mean"] == pytest.approx(np.mean(vals))
    assert s["p50"] == pytest.approx(np.percentile(vals, 50))


def test_percentile_edges():
    assert oexport.percentile([], 50) is None
    assert oexport.percentile([7.0], 0) == 7.0
    assert oexport.percentile([7.0], 100) == 7.0
    with pytest.raises(ValueError):
        oexport.percentile([1.0], 101)
    with pytest.raises(ValueError):
        oexport.percentile([1.0], -1)


# ---------------------------------------------------------------------------
# scoping: snapshot/diff/reset and audit_scope parity
# ---------------------------------------------------------------------------

def test_scope_zeroes_restores_freezes():
    om.declare("testobs.scope_a", "counter", "h")
    om.declare("testobs.scope_h", "histogram", "h")
    reg = om.MetricsRegistry()
    a = reg.counter("testobs.scope_a")
    h = reg.histogram("testobs.scope_h")
    a.inc(5)
    h.observe(1.0)
    with reg.scope() as view:
        assert view["testobs.scope_a"] == 0       # zero on entry
        assert view["testobs.scope_h"] == 0
        a.inc(3)
        h.observe(2.0)
        h.observe(4.0)
        assert view["testobs.scope_a"] == 3       # live in-scope deltas
        assert view["testobs.scope_h"] == 2
        assert not view.frozen
    assert view.frozen
    assert view["testobs.scope_a"] == 3           # frozen at exit values
    assert a.value == 5                           # outer value restored
    assert h.count == 1 and h.samples() == (1.0,)
    assert view.as_dict()["testobs.scope_h"] == 2


def test_scope_nested():
    om.declare("testobs.nested", "counter", "h")
    reg = om.MetricsRegistry()
    c = reg.counter("testobs.nested")
    c.inc(10)
    with reg.scope(["testobs.nested"]) as outer:
        c.inc(1)
        with reg.scope(["testobs.nested"]) as inner:
            c.inc(2)
            assert inner["testobs.nested"] == 2
        assert c.value == 1                       # inner restored
        assert outer["testobs.nested"] == 1
    assert c.value == 10


def test_snapshot_diff():
    om.declare("testobs.snap", "counter", "h")
    om.declare("testobs.snap_h", "histogram", "h")
    reg = om.MetricsRegistry()
    c = reg.counter("testobs.snap")
    h = reg.histogram("testobs.snap_h")
    c.inc(2)
    h.observe(0.5)
    snap = reg.snapshot()
    c.inc(3)
    h.observe(0.7)
    d = reg.diff(snap)
    assert d["testobs.snap"] == 3
    assert d["testobs.snap_h"] == 1               # histograms diff on count
    reg.reset(["testobs.snap"])
    assert c.value == 0 and h.count == 2


def test_audit_scope_parity():
    """The migrated kernels.ops.audit_scope keeps its exact contract."""
    base = kops.retrace_count()
    kops.note_retrace(2)
    with kops.audit_scope() as c:
        assert c.retraces == 0                    # zero on entry
        kops.note_retrace(3)
        kops.note_kernel_demotion()
        assert c.retraces == 3                    # live while open
        assert c.kernel_demotions == 1
    assert c.retraces == 3                        # frozen after exit
    assert c.kernel_demotions == 1
    assert kops.retrace_count() == base + 2       # outer value restored
    with kops.audit_scope() as c2:
        assert c2.retraces == 0 and c2.transpose_traces == 0
    assert kops.retrace_count() == base + 2


# ---------------------------------------------------------------------------
# back-compat shims: server counters dict, cache int attributes
# ---------------------------------------------------------------------------

def test_countermap_is_dict_shaped():
    om.declare("testobs.cm_a", "counter", "h")
    om.declare("testobs.cm_b", "counter", "h")
    reg = om.MetricsRegistry()
    m = om.CounterMap(reg, "testobs.", ("cm_a", "cm_b"))
    assert dict(m) == {"cm_a": 0, "cm_b": 0}
    m["cm_a"] += 2
    m["cm_b"] = 7
    assert m["cm_a"] == 2 and len(m) == 2
    assert {**m} == {"cm_a": 2, "cm_b": 7}
    assert reg.counter("testobs.cm_a").value == 2
    m.counter("cm_a").inc()                       # typed escape hatch
    assert m["cm_a"] == 3
    with pytest.raises(TypeError):
        del m["cm_a"]
    with pytest.raises(KeyError):
        m["unknown"]


def test_server_and_cache_shims():
    from repro.serve import ServeConfig, SessionCache, SGLServer

    server = SGLServer(ServeConfig())
    assert server.counters["requests"] == 0
    server.counters["requests"] += 2
    assert dict(server.counters)["requests"] == 2
    assert server.metrics.counter("serve.requests").value == 2
    # distinct servers keep distinct numbers under the shared schema
    other = SGLServer(ServeConfig())
    assert other.counters["requests"] == 0

    cache = SessionCache()
    cache.hits += 1
    cache.retraces += 4
    assert cache.stats()["hits"] == 1
    assert cache.metrics.counter("serve.cache_hits").value == 1
    assert cache.metrics.counter("serve.cache_retraces").value == 4


def test_faults_fired_counter():
    from repro.faults import FaultPlan, FaultSpec, inject
    from repro.faults.inject import fire

    fired = om.REGISTRY.counter("faults.fired")
    base = fired.value
    plan = FaultPlan((FaultSpec("core.round", "nan", hits=(0,)),))
    with inject(plan) as log:
        assert len(fire("core.round")) == 1
        assert fire("core.round") == ()           # hit 1 not scheduled
    assert log.count() == 1
    assert fired.value == base + 1


# ---------------------------------------------------------------------------
# tracing: disabled fast path, fake clock, sampling, threads
# ---------------------------------------------------------------------------

def test_disabled_span_is_noop_and_allocation_free(monkeypatch):
    assert not ot.TRACER.enabled
    annotations = []
    monkeypatch.setattr(ot, "_TraceAnnotation",
                        lambda *a, **k: annotations.append(a))
    before = ot.Span.allocated()
    for _ in range(100):
        with ot.span("round") as sp:
            sp.set("k", 1)
        with ot.span("read").set("what", "gap"):
            pass
    assert ot.span("path") is ot.NOOP
    assert ot.TRACER.span("path") is ot.NOOP
    assert ot.Span.allocated() == before
    assert annotations == []
    assert ot.TRACER.profiler_counts() == {}


def _fake_clock(step=0.25):
    state = {"t": 0.0}

    def clock():
        state["t"] += step
        return state["t"]

    return clock


def test_fake_clock_deterministic_spans():
    tr = ot.Tracer(clock=_fake_clock())
    tr.configure(enabled=True)
    with tr.span("path") as root:
        with tr.span("round") as child:
            pass
    assert root.trace_id == child.trace_id
    assert child.parent_id == root.span_id
    # clock ticks: root enter=0.25, child enter=0.5, child exit=0.75,
    # root exit=1.0 — every duration is exact, no tolerance needed.
    assert child.duration_s == 0.25
    assert root.duration_s == 0.75
    recs = tr.records()
    assert [r["name"] for r in recs] == ["round", "path"]
    p = tr.percentiles("round")
    assert p["p50"] == 0.25 and p["n"] == 1
    assert tr.open_spans() == 0


def test_sampling_thins_records_not_counts():
    tr = ot.Tracer(clock=_fake_clock(), sample_every=2)
    tr.configure(enabled=True)
    for _ in range(4):
        with tr.span("lambda"):
            with tr.span("round"):
                pass
    assert tr.counts() == {"lambda": 4, "round": 4}   # exact
    # roots 1 and 3 sampled; each subtree contributes both spans
    assert len(tr.records("lambda")) == 2
    assert len(tr.records("round")) == 2


def test_span_threads_exact_counts():
    tr = ot.Tracer(clock=_fake_clock(1e-6), buffer=100_000)
    tr.configure(enabled=True)

    def worker():
        for _ in range(200):
            with tr.span("epoch_block"):
                with tr.span("kernel_launch"):
                    pass

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tr.counts() == {"epoch_block": 1600, "kernel_launch": 1600}
    assert tr.open_spans() == 0
    ids = [r["span"] for r in tr.records()]
    assert len(ids) == len(set(ids))                   # unique span ids


def test_export_jsonl(tmp_path):
    tr = ot.Tracer(clock=_fake_clock())
    tr.configure(enabled=True)
    with tr.span("path") as sp:
        sp.set("T", 4)
    out = tmp_path / "spans.jsonl"
    assert tr.export_jsonl(str(out)) == 1
    rec = json.loads(out.read_text().strip())
    assert rec["name"] == "path" and rec["attrs"] == {"T": 4}


# ---------------------------------------------------------------------------
# end-to-end: tracing a real solve is bit-identical and leak-free
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_problem():
    from repro.core import sgl
    from repro.data.synthetic import make_synthetic

    X, y, _, sizes = make_synthetic(n=24, p=64, n_groups=8, gamma1=3,
                                    gamma2=2, seed=5)
    return sgl.make_problem(X, y, sizes, tau=0.3)


def _small_path(problem):
    from repro.core.session import SGLSession, SolverConfig

    return SGLSession(problem, SolverConfig(tol=1e-6, max_epochs=2000)
                      ).solve_path(T=3, delta=1.5)


def _profiled(fn, tmp_path):
    """Run ``fn`` under a profiler session; return its result and the
    ``repro.*`` events of the host plane as (name, metadata) pairs."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    [xplane] = list(tmp_path.glob("**/*.xplane.pb"))
    data = ProfileData.from_file(str(xplane))
    events = [(ev.name, dict(ev.stats))
              for plane in data.planes if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events
              if ev.name.startswith(ot.PROFILER_PREFIX)]
    return out, events


def test_traced_solve_bit_identical(small_problem, tmp_path):
    before = ot.Span.allocated()
    off = _small_path(small_problem)
    assert ot.Span.allocated() == before          # hot path allocated nothing
    ot.configure(enabled=True, sample_every=1)
    ot.TRACER.reset()
    try:
        on = _small_path(small_problem)
        counts = ot.TRACER.counts()
    finally:
        ot.configure(enabled=False)
    np.testing.assert_array_equal(np.asarray(on.betas),
                                  np.asarray(off.betas))
    assert counts["path"] == 1 and counts["lambda"] == 3
    assert counts["round"] > 0 and counts["epoch_block"] > 0
    assert ot.TRACER.open_spans() == 0
    # the profiler output changes nothing either
    profiled, events = _profiled(lambda: _small_path(small_problem),
                                 tmp_path)
    assert events
    np.testing.assert_array_equal(np.asarray(profiled.betas),
                                  np.asarray(off.betas))


def test_profiled_solve_writes_repro_spans_to_the_host_plane(
        small_problem, tmp_path):
    """Under a profiler session the solve's spans are ``repro.<site>``
    events on ``/host:CPU``, with their attributes as metadata; with the
    ring buffer on too, both outputs count every span."""
    ot.configure(enabled=True, sample_every=1)
    ot.TRACER.reset()
    try:
        _, events = _profiled(lambda: _small_path(small_problem), tmp_path)
        counts = ot.TRACER.counts()
        profiled = ot.TRACER.profiler_counts()
    finally:
        ot.configure(enabled=False)
        ot.TRACER.reset()
    names = collections.Counter(name for name, _ in events)
    for site in ("path", "lambda", "round", "epoch_block", "read", "masks"):
        assert names["repro." + site] > 0, site
    assert names["repro.path"] == 1 and names["repro.lambda"] == 3
    assert {k: names["repro." + k] for k in counts} == counts == profiled
    reads = [meta for name, meta in events if name == "repro.read"]
    assert all(meta.get("what") for meta in reads)
    assert {"gap", "masks", "k_done", "result"} <= {m["what"] for m in reads}
    rounds = [meta for name, meta in events if name == "repro.round"]
    assert all("compact" in meta for meta in rounds)


def test_profiled_solves_count_the_same_reads(small_problem, tmp_path):
    reads = []
    for k in range(2):
        _, events = _profiled(lambda: _small_path(small_problem),
                              tmp_path / str(k))
        reads.append(sum(name == "repro.read" for name, _ in events))
    assert reads[0] == reads[1] > 0


def test_serve_worker_traced_under_chaos(small_problem):
    """Spans + counters stay consistent when the serve worker (its own
    thread) dies mid-wave and restarts: no leaked open spans, exact
    request accounting, availability 1.0."""
    from repro.core.session import SolverConfig, lambda_grid
    from repro.core import sgl
    from repro.faults import FaultPlan, FaultSpec, inject
    from repro.serve import PathRequest, ServeConfig, SGLServer

    grid = lambda_grid(float(sgl.lambda_max(small_problem)), T=3, delta=1.5)
    solver = SolverConfig(tol=1e-6, max_epochs=2000)
    plan = FaultPlan((FaultSpec("serve.worker", "kill", hits=(0,)),))
    ot.configure(enabled=True, sample_every=1)
    ot.TRACER.reset()
    try:
        server = SGLServer(ServeConfig(default_solver=solver,
                                       coalesce_window_s=0.05,
                                       retry_backoff_s=0.01)).start()
        try:
            with inject(plan) as log:
                futs = [server.submit(
                    PathRequest(f"chaos-{i}", small_problem, grid))
                    for i in range(3)]
                resps = [f.result(timeout=600) for f in futs]
        finally:
            server.stop()
        counts = ot.TRACER.counts()
    finally:
        ot.configure(enabled=False)
    assert log.count("serve.worker") == 1
    assert server.counters["worker_restarts"] >= 1
    assert len(resps) == 3 and all(r.result is not None for r in resps)
    assert server.counters["responses"] == 3
    assert counts.get("serve.request", 0) >= 1
    assert counts.get("path", 0) >= 1
    assert ot.TRACER.open_spans() == 0
    # queue-wait histogram observed every response
    qw = server.metrics.histogram("serve.queue_wait_s").summary()
    assert qw["count"] == 3


# ---------------------------------------------------------------------------
# the --check gate: findings fire on seeded fixtures, live state is clean
# ---------------------------------------------------------------------------

def test_ob001_fires_on_bad_schema():
    bad = {
        "Bad Name": om.MetricSpec("counter", "ok"),
        "ok.kind": om.MetricSpec("exotic", "ok"),
        "ok.help": om.MetricSpec("counter", "   "),
    }
    fs = ocheck.check_schema(bad)
    assert [f.code for f in fs] == ["OB001"] * 3
    assert all(f.severity == "error" for f in fs)
    locs = {f.location for f in fs}
    assert locs == {"Bad Name", "ok.kind", "ok.help"}


def test_ob001_clean_on_live_schema():
    assert ocheck.check_schema() == []


def test_ob002_fires_on_missing_and_undeclared_sites():
    assert "kernel_launch" not in ot.SPAN_SITES
    assert {"read", "masks", "gather"} <= set(ot.SPAN_SITES)
    full = {site: 1 for site in ot.SPAN_SITES}
    assert ocheck.check_span_coverage(full) == []
    missing = dict(full)
    del missing["round"]
    fs = ocheck.check_span_coverage(missing)
    assert len(fs) == 1 and fs[0].code == "OB002"
    assert fs[0].location == "round" and fs[0].severity == "error"
    fs2 = ocheck.check_span_coverage({**full, "mystery": 2})
    assert len(fs2) == 1 and fs2[0].severity == "warning"
    assert fs2[0].location == "mystery"


# ---------------------------------------------------------------------------
# export: env meta, BENCH merging, markdown rendering
# ---------------------------------------------------------------------------

def test_env_meta_keys():
    meta = oexport.env_meta({"bench": "test"})
    assert {"jax", "backend", "platform", "device_count",
            "x64"} <= set(meta)
    assert meta["bench"] == "test"


def test_merge_bench_order_independent(tmp_path):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    for path, order in ((a, ("kernels", "serve")),
                        (b, ("serve", "kernels"))):
        for section in order:
            oexport.merge_bench(str(path), section, {"v": section},
                                meta_extra={"seed": 1})
    da = json.loads(a.read_text())
    db = json.loads(b.read_text())
    assert da["schema"] == oexport.BENCH_SCHEMA
    assert da["sections"] == db["sections"]
    assert da["sections"]["serve"] == {"v": "serve"}
    # merging replaces a section, keeps the others
    oexport.merge_bench(str(a), "serve", {"v": 2})
    da2 = json.loads(a.read_text())
    assert da2["sections"]["serve"] == {"v": 2}
    assert da2["sections"]["kernels"] == {"v": "kernels"}


def test_render_obs_markdown_smoke():
    from repro.launch.report import render_obs_markdown

    payload = {
        "schema": oexport.BENCH_SCHEMA,
        "meta": {"backend": "cpu"},
        "sections": {
            "kernels": {"scale": "smoke", "kernels": {
                "bcd_epoch/bucket": {
                    "measured_s": 1e-3, "min_s": 9e-4, "interpret": True,
                    "model_flops": 1e6, "model_bytes": 1e5,
                    "achieved": {"frac_peak_compute": 5e-9,
                                 "achieved_vs_model": 1e-5,
                                 "model_bottleneck": "memory"}}}},
            "path": {"shape": {"n": 64}, "base_s": 1.0, "obs_s": 1.01,
                     "overhead_frac": 0.01, "bit_identical": True,
                     "span_counts": {"path": 3},
                     "stages": {"round": {"n": 10, "p50": 1e-4,
                                          "p99": 2e-4, "mean": 1.2e-4}}},
            "serve": {"workload": {"tenants": 10},
                      "latency_s": {"p50": 0.5, "p99": 1.2, "n": 10},
                      "baseline_latency_s": {"p50": 1.5, "p99": 3.0},
                      "requests_per_sec": 4.0,
                      "baseline_requests_per_sec": 1.0,
                      "speedup_rps": 4.0,
                      "stages": {"serve.request": {"n": 5, "p50": 0.4,
                                                   "p99": 1.0,
                                                   "mean": 0.5}},
                      "queue_wait_s": {"p50": 1e-3, "p99": 1e-2,
                                       "count": 10},
                      "counters": {"requests": 10, "failed": 0}},
        },
    }
    md = render_obs_markdown(payload)
    assert "bcd_epoch/bucket" in md and "(interp)" in md
    assert "10 tenants" in md
    assert "`serve.request`" in md
    assert "+1.00%" in md
    assert "'failed'" not in md                    # zero counters dropped


def test_ob002_smoke_fires_every_declared_site():
    assert ocheck.check_span_coverage() == []


def test_obs_check_payload_schema():
    payload = ocheck.run_check(smoke=False)
    assert payload["schema"] == "repro.analysis/v1"
    assert payload["ok"]
    assert payload["passes"]["obs"]["metrics_declared"] >= 20
    assert "serve.request" in payload["passes"]["obs"]["span_sites"]
