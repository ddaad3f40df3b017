"""Jaxpr lints: dtype demotion, transpose materialisation, retrace hazards.

Every registered entry point (see :mod:`repro.analysis.entrypoints`) is
traced into a jaxpr on a small shape/dtype template and every nested eqn
is walked (``pjit``/``scan``/``while``/``cond`` bodies included):

* **JX001** ``convert_element_type`` narrowing a float below the spec's
  ``min_float_bits`` (default 64) — a certificate value silently leaving
  f64.  The gap/radius/Theorem-1 quantities are *outputs* of these
  programs, so any in-program float narrowing sits on a certificate-
  producing path.
* **JX002** a ``transpose`` on an operand at least as large as the design
  matrix — a (p, n) copy materialised outside the audited
  ``kernels.ops.transposed_design`` (the runtime counter, promoted to a
  static guarantee: the einsum paths lower to ``dot_general`` with no
  transpose, and the Pallas paths consume the persistent pre-transposed
  design).
* **JX003** the same for a design-sized ``gather`` (a full copy smuggled
  through fancy indexing).
* **JX004** jit-cache growth when the entry point is called twice with
  dtype-identical, freshly-built inputs (weak-type literal splits and
  friends).  Observed retraces also bump
  :func:`repro.kernels.ops.note_retrace`, so ``audit_scope`` sees them.
* **JX005** a ``TypeError`` mentioning hashability while dispatching —
  an unhashable value reached ``static_argnums``.
* **JX007** a rank-0 f64 ``sub`` whose first operand is a literal
  (``1.0 - tau``).  A TPU's emulated f64 computes exactly that form to
  f32 precision only (:mod:`repro.core.precision`); certificate code
  writes it as :func:`repro.core.precision.one_minus`.
"""
from __future__ import annotations

from typing import Iterator, List

import jax
import jax.extend.core as jex_core
import numpy as np

from ..kernels import ops as kops
from .findings import Finding

__all__ = ["iter_eqns", "lint_entry_point", "retrace_harness", "run"]


def _as_jaxpr(v):
    if hasattr(v, "eqns"):
        return v
    inner = getattr(v, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    return None


def iter_eqns(jaxpr, skip=()) -> Iterator:
    """All eqns of ``jaxpr`` and every nested sub-jaxpr (pjit bodies, scan/
    while/cond branches, custom-call closures), depth-first.  The bodies
    of primitives named in ``skip`` are not entered."""
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            yield eqn
            if eqn.primitive.name in skip:
                continue
            for val in eqn.params.values():
                vals = val if isinstance(val, (list, tuple)) else (val,)
                for v in vals:
                    sub = _as_jaxpr(v)
                    if sub is not None:
                        stack.append(sub)


def _aval_elems(var) -> int:
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    return int(np.prod(shape)) if len(shape) else 1


def _is_float(dt) -> bool:
    return np.issubdtype(np.dtype(dt), np.floating)


def _literal_minus_f64_scalar(eqn) -> bool:
    lhs, out = eqn.invars[0], eqn.outvars[0].aval
    return (isinstance(lhs, jex_core.Literal) and out.shape == ()
            and np.dtype(out.dtype) == np.float64)


def lint_jaxpr(jaxpr, spec) -> List[Finding]:
    """Walk one traced entry point for dtype/transpose findings."""
    findings: List[Finding] = []
    for eqn in iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim == "convert_element_type":
            new = np.dtype(eqn.params.get("new_dtype"))
            old_aval = getattr(eqn.invars[0], "aval", None)
            old = np.dtype(getattr(old_aval, "dtype", new))
            if (_is_float(new) and _is_float(old)
                    and new.itemsize < old.itemsize
                    and new.itemsize * 8 < spec.min_float_bits):
                findings.append(Finding(
                    pass_name="jaxpr", code="JX001",
                    message=(f"float demoted {old.name} -> {new.name} on a "
                             f"certificate-producing path"),
                    location=spec.name,
                    details={"primitive": prim, "from": old.name,
                             "to": new.name,
                             "min_float_bits": spec.min_float_bits},
                ))
        elif prim == "transpose":
            elems = _aval_elems(eqn.invars[0])
            if (spec.design_elements
                    and elems >= spec.design_elements
                    and not spec.allow_design_transpose):
                findings.append(Finding(
                    pass_name="jaxpr", code="JX002",
                    message=(f"design-sized transpose materialised in the "
                             f"traced program ({elems} elements); (p, n) "
                             f"copies must go through the audited "
                             f"kernels.ops.transposed_design"),
                    location=spec.name,
                    details={"elements": elems,
                             "design_elements": spec.design_elements},
                ))
        elif prim == "gather":
            in_elems = _aval_elems(eqn.invars[0])
            out_elems = _aval_elems(eqn.outvars[0])
            if (spec.design_elements
                    and min(in_elems, out_elems) >= spec.design_elements
                    and not spec.allow_design_transpose):
                findings.append(Finding(
                    pass_name="jaxpr", code="JX003",
                    message=(f"design-sized gather copy in the traced "
                             f"program ({out_elems} elements out)"),
                    location=spec.name,
                    details={"in_elements": in_elems,
                             "out_elements": out_elems,
                             "design_elements": spec.design_elements},
                ))
    # XLA programs only: a Pallas body never runs f64 on a TPU (Mosaic has
    # no 64-bit types), so in f64 it runs interpreted, in IEEE arithmetic.
    for eqn in iter_eqns(jaxpr, skip=("pallas_call",)):
        if eqn.primitive.name == "sub" and _literal_minus_f64_scalar(eqn):
            findings.append(Finding(
                pass_name="jaxpr", code="JX007",
                message=("literal minus a rank-0 f64 value: a TPU's "
                         "emulated f64 rounds it to f32 precision; use "
                         "repro.core.precision.one_minus"),
                location=spec.name,
                details={"literal": float(eqn.invars[0].val)},
            ))
    return findings


def lint_entry_point(spec) -> List[Finding]:
    """Trace ``spec`` on its template and run the jaxpr walks."""
    try:
        fn, args, kwargs = spec.build()
        closed = jax.make_jaxpr(lambda: fn(*args, **kwargs))()
    except Exception as e:  # a broken template IS a gate failure
        return [Finding(
            pass_name="jaxpr", code="JX000",
            message=f"entry point failed to trace: {type(e).__name__}: {e}",
            location=spec.name,
        )]
    return lint_jaxpr(closed.jaxpr, spec)


def retrace_harness(spec) -> List[Finding]:
    """Compile ``spec`` twice with dtype-identical fresh inputs; any jit
    cache growth between the calls is a retrace hazard."""
    findings: List[Finding] = []
    try:
        fn, args, kwargs = spec.build()
        jax.block_until_ready(fn(*args, **kwargs))
        size1 = fn._cache_size() if hasattr(fn, "_cache_size") else None
        fn2, args, kwargs = spec.build()
        jax.block_until_ready(fn2(*args, **kwargs))
        size2 = fn2._cache_size() if hasattr(fn2, "_cache_size") else None
    except (TypeError, ValueError) as e:
        # jax raises TypeError or a ValueError wrapping one, both
        # mentioning hashability, when an unhashable value reaches a
        # static argument
        if "hash" in str(e).lower():
            return [Finding(
                pass_name="jaxpr", code="JX005",
                message=f"unhashable value reached a static argument: {e}",
                location=spec.name,
            )]
        return [Finding(
            pass_name="jaxpr", code="JX000",
            message=(f"entry point failed to execute its template: "
                     f"{type(e).__name__}: {e}"),
            location=spec.name,
        )]
    except Exception as e:
        return [Finding(
            pass_name="jaxpr", code="JX000",
            message=(f"entry point failed to execute its template: "
                     f"{type(e).__name__}: {e}"),
            location=spec.name,
        )]
    if size1 is None or size2 is None:
        findings.append(Finding(
            pass_name="jaxpr", code="JX006", severity="info",
            message="entry point exposes no jit cache; retrace check "
                    "skipped",
            location=spec.name,
        ))
    elif size2 > size1:
        kops.note_retrace(size2 - size1)
        findings.append(Finding(
            pass_name="jaxpr", code="JX004",
            message=(f"retraced on dtype-identical inputs (jit cache grew "
                     f"{size1} -> {size2}); look for weak-type literals or "
                     f"unstable static arguments"),
            location=spec.name,
            details={"cache_before": size1, "cache_after": size2},
        ))
    return findings


def run(specs) -> List[Finding]:
    findings: List[Finding] = []
    for spec in specs:
        findings.extend(lint_entry_point(spec))
        if spec.check_retrace:
            findings.extend(retrace_harness(spec))
    return findings
