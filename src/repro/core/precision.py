"""Explicit f64 posture for every certificate-producing computation.

The GAP safe guarantee (paper Thm 1/2) is only as good as the arithmetic
the certificate is evaluated in: the duality gap, the Eq. 15 dual
scaling, and the sphere radii must be computed in full f64 precision on
the full problem.  JAX defaults to f32 unless ``jax_enable_x64`` is set,
and a silently-f32 "certificate" is the worst kind of bug — numerically
plausible, formally worthless.

:func:`ensure_x64` is called when :mod:`repro.core` is first imported
(before any array can be built by solver code), so every front end — the
test suite, the benchmark drivers, ``python -m repro.analysis`` — gets
the same posture without each having to remember an environment
variable.  The jaxpr lints (JX001, :mod:`repro.analysis.jaxpr_lints`)
then verify statically that no traced program demotes a float below f64.

The ONE sanctioned sub-f64 path is the mesh strategy's low-precision
FISTA solves (``SGLSession`` over a mesh with a non-f64 dtype): those
rounds are never adopted as certificates — the session re-certifies in
f64 before reporting — and the analysis gate documents the exemption via
the ``dist_fista/f32-mesh`` entry spec (``min_float_bits=32``).  Enabling
x64 does not forbid f32 arrays; it only stops f64 requests from being
silently truncated.

x64 on a TPU is not IEEE f64: XLA emulates it with pairs of f32, and
Mosaic (Pallas) has no 64-bit types at all.  The emulation is accurate to
a few hundred f64 ulps in the operations the certificate uses, with one
exception: ``c - x`` for a constant ``c`` and a rank-0 ``x`` comes out
to f32 precision only (on a v5e, ``1.0 - tau`` at tau=0.2 is off by
1.9e-8 relative; ``x - c``, ``c + x`` and negation are exact).  A
``1 - tau`` that loose moves the SGL norm, the dual norm and the
Theorem-1 group threshold by ~1.5e-8 relative, which at the paper's
synthetic size hides duality gaps of up to ~4e-6 under a tol of 1e-8.
Certificate code therefore writes ``1 - x`` as :func:`one_minus`.

Set ``REPRO_ALLOW_F32=1`` to skip enforcement entirely (e.g. profiling
runs on accelerators without f64 support); certificates produced under
that escape hatch are NOT trustworthy and the variable exists so the
choice is loud and greppable.
"""
from __future__ import annotations

import os

__all__ = ["ensure_x64", "one_minus"]


def ensure_x64() -> bool:
    """Enable (and verify) ``jax_enable_x64``; returns True when enforced.

    Raises ``RuntimeError`` if x64 cannot be enabled — e.g. another
    library froze the config after arrays were created — instead of
    letting certificate arithmetic silently truncate to f32.
    """
    if os.environ.get("REPRO_ALLOW_F32") == "1":
        return False
    import jax

    if not jax.config.read("jax_enable_x64"):
        jax.config.update("jax_enable_x64", True)
    if not jax.config.read("jax_enable_x64"):   # pragma: no cover
        raise RuntimeError(
            "repro.core requires jax_enable_x64 for certificate "
            "arithmetic, but it could not be enabled. Set "
            "JAX_ENABLE_X64=1 before importing jax, or export "
            "REPRO_ALLOW_F32=1 to explicitly accept untrustworthy "
            "f32 certificates."
        )
    return True


def one_minus(x):
    """``1 - x``, computed as ``-(x - 1)``.

    The two are bitwise equal under IEEE rounding (up to the sign of a
    zero), but only the second is exact in a TPU's emulated f64 when ``x``
    is rank 0 (see the module docstring).
    """
    return -(x - 1.0)
