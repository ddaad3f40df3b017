"""Tier-1 tests for the static-analysis gate (repro.analysis).

Two obligations, both load-bearing:

1. the repo itself passes every pass clean (the CI gate's contract), and
2. each lint demonstrably FIRES on the committed seeded-violation
   fixtures (tests/analysis_fixtures/ + inline bad specs) — a gate that
   cannot fail is not a gate.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import cert_lint, jaxpr_lints, pallas_audit
from repro.analysis.entrypoints import (
    EntryPointSpec,
    default_entry_specs,
    pairing_findings,
)
from repro.analysis.findings import Finding, summarize, to_payload
from repro.analysis.main import run_checks
from repro.kernels._util import ArraySpec, LaunchSpec
from repro.kernels import ops as kops

FIXTURES = os.path.join(os.path.dirname(__file__), "analysis_fixtures")


def codes(findings, severity="error"):
    return sorted(f.code for f in findings if f.severity == severity)


# ---------------------------------------------------------------------------
# 1. The repo passes clean (the actual gate)
# ---------------------------------------------------------------------------

def test_repo_cert_pass_clean():
    assert codes(cert_lint.run()) == []


def test_repo_pallas_pass_clean():
    assert codes(pallas_audit.run()) == []


def test_repo_full_gate_clean():
    """The complete CI gate — cert + pallas + jaxpr incl. the retrace
    harness — holds on the repository itself."""
    payload = run_checks()
    assert payload["ok"], [f for f in payload["findings"]
                           if f["severity"] == "error"]
    assert set(payload["passes"]) == {"cert", "pallas", "jaxpr"}


def test_traceables_and_templates_pair_exactly():
    assert [str(f) for f in pairing_findings()] == []
    # and an empty template set flags every registered traceable (RG001)
    orphaned = pairing_findings(specs=[])
    assert orphaned and all(f.code == "RG001" for f in orphaned)
    # ... as does a template pointing at nothing
    ghost = EntryPointSpec(name="ghost", traceable="no_such_traceable",
                           build=lambda: None)
    assert any(f.code == "RG001" and "no_such_traceable" in f.message
               for f in pairing_findings(specs=[*default_entry_specs(),
                                                ghost]))


# ---------------------------------------------------------------------------
# 2. Cert lints fire on the seeded fixtures
# ---------------------------------------------------------------------------

def test_cs001_fires_on_forged_and_omitted_safety():
    fs = cert_lint.lint_result_constructions(
        os.path.join(FIXTURES, "bad_src"))
    assert codes(fs) == ["CS001"] * 4
    locs = sorted(f.location for f in fs)
    assert all(loc.startswith("results.py:") for loc in locs)
    msgs = " | ".join(f.message for f in fs)
    assert "safe=True" in msgs            # forged keyword
    assert "positional" in msgs.lower() or "position" in msgs
    assert "omits" in msgs                # omission = silent claim
    assert "certificates_safe" in msgs    # PathResult variant


def test_cs001_allowlist_accepts_rules_library():
    # the same literal inside the allow-file is not a finding
    fs = cert_lint.lint_result_constructions(
        os.path.join(FIXTURES, "bad_src"),
        allow_literal_files=("results.py",))
    # forged literals become allowed; the two *omission* findings remain
    assert codes(fs) == ["CS001"] * 2
    assert all("omits" in f.message for f in fs if f.severity == "error")


def test_cs002_fires_on_core_naming_strong_rule():
    fs = cert_lint.lint_strong_imports(os.path.join(FIXTURES, "bad_src"))
    assert fs and all(f.code == "CS002" for f in fs)
    assert any("core" in f.location for f in fs)


def test_cs003_fires_on_uncovered_safe_rule():
    fs = cert_lint.lint_safety_matrix(
        os.path.join(FIXTURES, "bad_tests"), ["gap", "static", "dynamic"])
    assert codes(fs) == ["CS003"]
    assert "'dynamic'" in fs[0].message


def test_cs003_fires_when_matrix_is_missing(tmp_path):
    fs = cert_lint.lint_safety_matrix(str(tmp_path), ["gap"])
    assert codes(fs) == ["CS003"]


def test_cs004_fires_on_exception_path_results_and_masks():
    fs = cert_lint.lint_exception_paths(os.path.join(FIXTURES, "bad_src"))
    assert codes(fs) == ["CS004"] * 4
    assert all(f.location.startswith(os.path.join("core", "except_result.py"))
               for f in fs)
    msgs = " | ".join(f.message for f in fs)
    assert "RoundResult" in msgs and "PathResult" in msgs
    assert "group_active" in msgs and "feat_active" in msgs
    # the clean handlers (rewind-then-build, star re-wrap) must NOT fire:
    # exactly the four seeded violations, nothing from the clean section
    assert len(fs) == 4


def test_cs004_fixture_stays_cs001_clean():
    """The CS004 fixture threads safety from names, so it must not leak
    into the CS001 counts (which other tests pin exactly)."""
    fs = cert_lint.lint_result_constructions(
        os.path.join(FIXTURES, "bad_src"))
    assert not any("except_result" in f.location for f in fs)


# ---------------------------------------------------------------------------
# 3. Pallas auditor fires on seeded launch geometry
# ---------------------------------------------------------------------------

def _spec1d(out_map, grid=(4,), nblocks=4, carried=(), name="fixture"):
    out = ArraySpec(shape=(nblocks * 8,), block=(8,), index_map=out_map)
    return LaunchSpec(name=name, grid=grid, inputs=(),
                      outputs=(out,), carried=(carried,))


def test_pl001_out_of_bounds_index():
    inp = ArraySpec(shape=(32,), block=(8,), index_map=lambda i: (i + 1,))
    spec = LaunchSpec(name="oob", grid=(4,), inputs=(inp,),
                      outputs=(ArraySpec((32,), (8,), lambda i: (i,)),))
    assert "PL001" in codes(pallas_audit.audit_launch_spec(spec))


def test_pl002_coverage_gap():
    # 8 output blocks, grid only writes the first 4
    out = ArraySpec(shape=(64,), block=(8,), index_map=lambda i: (i,))
    spec = LaunchSpec(name="gap", grid=(4,), inputs=(), outputs=(out,))
    fs = pallas_audit.audit_launch_spec(spec)
    assert "PL002" in codes(fs)


def test_pl003_overlapping_writes():
    fs = pallas_audit.audit_launch_spec(
        _spec1d(lambda i: (i // 2,), name="overlap"))
    assert "PL003" in codes(fs)


def test_pl004_vmem_budget():
    big = ArraySpec(shape=(4 * 2**20,), block=(4 * 2**20,),
                    index_map=lambda i: (0,))   # 32 MiB f64 tile
    out = ArraySpec(shape=(4,), block=(1,), index_map=lambda i: (i,))
    spec = LaunchSpec(name="huge", grid=(4,), inputs=(big,),
                      outputs=(out,), carried=((),))
    fs = pallas_audit.audit_launch_spec(spec)
    assert "PL004" in codes(fs)
    # a roomier budget accepts the same geometry
    fs = pallas_audit.audit_launch_spec(spec, vmem_budget=64 * 2**20)
    assert "PL004" not in codes(fs)


def test_pl005_carried_axis_actually_varies():
    # axis 0 declared carried but the map varies with it
    fs = pallas_audit.audit_launch_spec(
        _spec1d(lambda i: (i,), carried=(0,), name="bad-carry"))
    assert "PL005" in codes(fs)


def test_pl005_undeclared_invariant_axis():
    # output ignores grid axis 1 without declaring it carried
    out = ArraySpec(shape=(16,), block=(8,), index_map=lambda i, j: (i,))
    spec = LaunchSpec(name="undeclared", grid=(2, 3), inputs=(),
                      outputs=(out,), carried=((),))
    fs = pallas_audit.audit_launch_spec(spec)
    assert "PL005" in codes(fs)
    # declaring it carried makes the same geometry clean
    spec = LaunchSpec(name="declared", grid=(2, 3), inputs=(),
                      outputs=(out,), carried=((1,),))
    assert codes(pallas_audit.audit_launch_spec(spec)) == []


def test_pl000_broken_builder_is_a_finding():
    def boom():
        raise RuntimeError("no such config")

    fs = pallas_audit.run(audits={"broken": boom})
    assert codes(fs) == ["PL000"]


def test_pl006_subsampled_grid_is_reported():
    out = ArraySpec(shape=(10**6 * 8,), block=(8,),
                    index_map=lambda i: (i,))
    spec = LaunchSpec(name="big-grid", grid=(10**6,), inputs=(),
                      outputs=(out,), carried=((),))
    fs = pallas_audit.audit_launch_spec(spec, max_points=100)
    assert "PL006" in codes(fs, severity="info")
    assert codes(fs) == []   # corners in bounds; coverage proof skipped


# ---------------------------------------------------------------------------
# 4. Jaxpr lints fire on seeded entry points
# ---------------------------------------------------------------------------

def _spec(fn, *args, name="fixture", **meta):
    return EntryPointSpec(
        name=name, traceable=name,
        build=lambda: (fn, args, {}), **meta)


def test_jx001_dtype_demotion_fires():
    def demote(x):
        return x.astype(jnp.float32) * 2.0

    fs = jaxpr_lints.lint_entry_point(
        _spec(demote, jnp.ones(8, jnp.float64)))
    assert codes(fs) == ["JX001"]
    # the sanctioned min_float_bits=32 posture accepts the same program
    fs = jaxpr_lints.lint_entry_point(
        _spec(demote, jnp.ones(8, jnp.float64), min_float_bits=32))
    assert codes(fs) == []


def test_jx007_literal_minus_f64_scalar_fires():
    from repro.core.precision import one_minus

    fs = jaxpr_lints.lint_entry_point(
        _spec(lambda t: 1.0 - t, jnp.float64(0.2)))
    assert codes(fs) == ["JX007"]
    # the exact spelling, vectors and f32 scalars stay legal
    for fn, x in ((one_minus, jnp.float64(0.2)),
                  (lambda t: 1.0 - t, jnp.ones(4, jnp.float64)),
                  (lambda t: 1.0 - t, jnp.float32(0.2))):
        assert codes(jaxpr_lints.lint_entry_point(_spec(fn, x))) == []
    assert one_minus(jnp.float64(0.2)) == 1.0 - jnp.float64(0.2)


def test_jx002_design_sized_transpose_fires():
    x = jnp.ones((8, 16), jnp.float64)

    fs = jaxpr_lints.lint_entry_point(
        _spec(jnp.transpose, x, design_elements=64))
    assert codes(fs) == ["JX002"]
    # small transposes (below the design size) stay legal
    fs = jaxpr_lints.lint_entry_point(
        _spec(jnp.transpose, x, design_elements=1024))
    assert codes(fs) == []
    # ... and the audited-path exemption is explicit
    fs = jaxpr_lints.lint_entry_point(
        _spec(jnp.transpose, x, design_elements=64,
              allow_design_transpose=True))
    assert codes(fs) == []


def test_jx003_design_sized_gather_fires():
    x = jnp.ones((16, 8), jnp.float64)
    idx = jnp.arange(16)

    def copy_via_take(x, idx):
        return jnp.take(x, idx, axis=0)

    fs = jaxpr_lints.lint_entry_point(
        _spec(copy_via_take, x, idx, design_elements=64))
    assert codes(fs) == ["JX003"]


def test_jx000_broken_template_is_a_finding():
    def bad_build():
        raise RuntimeError("template rotted")

    fs = jaxpr_lints.lint_entry_point(EntryPointSpec(
        name="broken", traceable="broken", build=bad_build))
    assert codes(fs) == ["JX000"]


def test_jx004_weak_type_retrace_fires():
    fn = jax.jit(lambda x, s: x * s)
    calls = {"n": 0}

    def build():
        calls["n"] += 1
        # first build: committed f64 scalar; second: weak-typed python
        # float — dtype-identical to the user, a fresh trace to jax
        s = jnp.float64(0.5) if calls["n"] == 1 else 0.5
        return fn, (jnp.ones(4, jnp.float64), s), {}

    with kops.audit_scope() as audit:
        fs = jaxpr_lints.retrace_harness(EntryPointSpec(
            name="weak-type", traceable="weak-type", build=build))
        assert codes(fs) == ["JX004"]
        assert audit.retraces >= 1   # observed retraces hit the counter


def test_jx004_stable_inputs_do_not_fire():
    fn = jax.jit(lambda x: x * 2.0)
    fs = jaxpr_lints.retrace_harness(_spec(fn, jnp.ones(4, jnp.float64)))
    assert codes(fs) == []


def test_jx005_unhashable_static_argument():
    fn = jax.jit(lambda xs: jnp.zeros(len(xs)), static_argnums=0)
    fs = jaxpr_lints.retrace_harness(_spec(fn, [1, 2, 3]))
    assert codes(fs) == ["JX005"]


def test_iter_eqns_walks_nested_jaxprs():
    def prog(x):
        def body(c, _):
            return jnp.sin(c), None

        y, _ = jax.lax.scan(body, x, None, length=3)
        return jax.jit(jnp.cos)(y)

    closed = jax.make_jaxpr(prog)(jnp.ones(4))
    prims = {e.primitive.name for e in jaxpr_lints.iter_eqns(closed.jaxpr)}
    assert "sin" in prims and "cos" in prims   # scan body + pjit body


# ---------------------------------------------------------------------------
# 5. Payload, renderer, CLI
# ---------------------------------------------------------------------------

def test_payload_shape_and_summary():
    fs = [Finding("cert", "CS001", "bad", severity="error"),
          Finding("pallas", "PL006", "info", severity="info")]
    payload = to_payload(fs, passes={"cert": {}, "pallas": {}})
    assert payload["schema"] == "repro.analysis/v1"
    assert payload["summary"] == {"errors": 1, "warnings": 0, "infos": 1}
    assert not payload["ok"]
    assert summarize([]) == {"errors": 0, "warnings": 0, "infos": 0}


def test_markdown_renderer_roundtrips_payload():
    from repro.launch.report import render_analysis_markdown

    bad = to_payload(
        [Finding("cert", "CS001", "a | pipe", location="x.py:1")],
        passes={"cert": {"findings": 1}})
    md = render_analysis_markdown(bad)
    assert "FAIL" in md and "CS001" in md and "a \\| pipe" in md
    ok = to_payload([], passes={"cert": {"findings": 0}})
    assert "PASS" in render_analysis_markdown(ok)


def test_cli_writes_artifacts_and_exit_code(tmp_path):
    from repro.analysis.__main__ import main

    rpt = tmp_path / "analysis.json"
    md = tmp_path / "analysis.md"
    rc = main(["--check", "--passes", "cert", "pallas",
               "--report", str(rpt), "--md", str(md)])
    assert rc == 0
    assert rpt.exists() and md.exists()
    import json

    payload = json.loads(rpt.read_text())
    assert payload["ok"] and payload["schema"] == "repro.analysis/v1"


# ---------------------------------------------------------------------------
# 6. audit_scope (satellite of this gate: scoped runtime counters)
# ---------------------------------------------------------------------------

def test_audit_scope_counts_and_restores():
    before_t = kops.transpose_trace_count()
    before_r = kops.retrace_count()
    with kops.audit_scope() as audit:
        assert audit.transpose_traces == 0
        kops.note_retrace(2)
        assert audit.retraces == 2
    # frozen after exit; globals restored to the surrounding values
    assert audit.retraces == 2
    kops.note_retrace()
    assert audit.retraces == 2
    assert kops.transpose_trace_count() == before_t
    assert kops.retrace_count() == before_r + 1
    kops.note_retrace(-1)   # keep the module counter as we found it


def test_audit_scope_restores_on_exception():
    t0 = kops.transpose_trace_count()
    with pytest.raises(RuntimeError):
        with kops.audit_scope():
            raise RuntimeError("boom")
    assert kops.transpose_trace_count() == t0


# ---------------------------------------------------------------------------
# 7. f64 posture (repro.core.precision)
# ---------------------------------------------------------------------------

def test_ensure_x64_enforced_by_core_import():
    from repro.core import ensure_x64

    assert ensure_x64() is True
    assert jax.config.read("jax_enable_x64")
    assert jnp.zeros(1).dtype == jnp.float64


def test_ensure_x64_escape_hatch(monkeypatch):
    from repro.core.precision import ensure_x64

    monkeypatch.setenv("REPRO_ALLOW_F32", "1")
    assert ensure_x64() is False   # explicitly waived, no error
