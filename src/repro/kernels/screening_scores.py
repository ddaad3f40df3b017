"""Fused correlation + screening-statistics Pallas kernels.

Two variants over the same blocked matvec:

* :func:`screening_scores_pallas` computes, in one pass over the design
  matrix tiles:

      corr = X^T theta                    (p,)   — needed by the feature test
      st2  = S_tau(corr)^2                (p,)   — summed per group by the
                                                   wrapper for the group test

  Used when the screening threshold ``tau`` applies to ``corr`` itself
  (sphere centers, i.e. ``corr = X^T theta_c``): the soft-thresholded
  square never makes an HBM round trip before thresholding, and
  ``screening.screen_with_corr`` consumes ``st2`` directly instead of
  re-thresholding.

* :func:`screening_corr_pallas` is the corr-only variant for the certified
  gap round, where ``corr = X^T resid`` still has to be *rescaled* by the
  (corr-dependent) dual scale before any thresholding — computing st2 there
  would be wasted work that the caller must discard (the pre-PR-2 behavior).

The matvec is blocked (bp x bn) with the K (sample) axis as the innermost
sequential grid dimension; the correlation block accumulates in the output
VMEM tile across K steps (standard Pallas accumulation pattern), and any
finalisation happens on the final K step while the block is still resident.
MXU-friendly when bp, bn are multiples of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._util import ArraySpec, LaunchSpec, block_specs, default_interpret, out_shapes


def _corr_io_specs(p: int, n: int, block_p: int, block_n: int, dtype):
    """Shared (Xt, theta) input + (p, 1) accumulator output geometry of the
    blocked correlation matvec.  The output tile accumulates over the K
    (sample) grid axis — carried axis 1."""
    inputs = (
        ArraySpec((p, n), (block_p, block_n), lambda i, k: (i, k), dtype),
        ArraySpec((n, 1), (block_n, 1), lambda i, k: (k, 0), dtype),
    )
    out = ArraySpec((p, 1), (block_p, 1), lambda i, k: (i, 0), dtype)
    return inputs, out


def screening_scores_launch_spec(p: int, n: int, *, block_p: int = 256,
                                 block_n: int = 128,
                                 dtype="float64") -> LaunchSpec:
    """Auditable launch geometry of :func:`screening_scores_pallas`."""
    inputs, out = _corr_io_specs(p, n, block_p, block_n, dtype)
    return LaunchSpec(
        name="screening_scores",
        grid=(p // block_p, n // block_n),
        inputs=inputs,
        outputs=(out, out),
        carried=((1,), (1,)),
        note="fused corr + S_tau(corr)^2; corr accumulates over K",
    )


def screening_corr_launch_spec(p: int, n: int, *, block_p: int = 256,
                               block_n: int = 128,
                               dtype="float64") -> LaunchSpec:
    """Auditable launch geometry of :func:`screening_corr_pallas`."""
    inputs, out = _corr_io_specs(p, n, block_p, block_n, dtype)
    return LaunchSpec(
        name="screening_corr",
        grid=(p // block_p, n // block_n),
        inputs=inputs,
        outputs=(out,),
        carried=((1,),),
        note="corr-only variant for the certified gap round",
    )


def _matvec(xt, theta):
    """(bp, bn) @ (bn, 1) at full precision.  At the default precision
    Mosaic feeds f32 to the MXU in one bf16 pass: measured on a v5e, the
    corr was off by 2e-3 relative."""
    return jnp.dot(xt, theta, precision=jax.lax.Precision.HIGHEST)


def _screening_kernel(xt_ref, theta_ref, corr_ref, st2_ref, *, tau: float, nk: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        corr_ref[...] = jnp.zeros_like(corr_ref)

    corr_ref[...] += _matvec(xt_ref[...], theta_ref[...])

    @pl.when(k == nk - 1)
    def _finalize():
        c = corr_ref[...]
        st = jnp.maximum(jnp.abs(c) - tau, 0.0)
        st2_ref[...] = st * st


def screening_scores_pallas(
    Xt: jax.Array,       # (p, n) design matrix transposed
    theta: jax.Array,    # (n,)
    tau: float,
    *,
    block_p: int = 256,
    block_n: int = 128,
    interpret: bool | None = None,
):
    if interpret is None:
        interpret = default_interpret()
    p, n = Xt.shape
    assert p % block_p == 0 and n % block_n == 0, (p, n, block_p, block_n)
    nk = n // block_n
    spec = screening_scores_launch_spec(p, n, block_p=block_p,
                                        block_n=block_n, dtype=Xt.dtype)
    corr, st2 = pl.pallas_call(
        functools.partial(_screening_kernel, tau=float(tau), nk=nk),
        grid=spec.grid,
        in_specs=block_specs(spec.inputs),
        out_specs=block_specs(spec.outputs),
        out_shape=out_shapes(spec.outputs),
        interpret=interpret,
    )(Xt, theta[:, None])
    return corr[:, 0], st2[:, 0]


def _corr_kernel(xt_ref, theta_ref, corr_ref, *, nk: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        corr_ref[...] = jnp.zeros_like(corr_ref)

    corr_ref[...] += _matvec(xt_ref[...], theta_ref[...])


def screening_corr_pallas(
    Xt: jax.Array,       # (p, n) design matrix transposed
    theta: jax.Array,    # (n,)
    *,
    block_p: int = 256,
    block_n: int = 128,
    interpret: bool | None = None,
):
    """Corr-only variant: blocked corr = Xt @ theta without the st2 output.

    The certified gap round rescales corr by the dual scale before
    thresholding, so the fused kernel's S_tau(corr)^2 half is dead weight
    there — this variant skips both its compute and its (p,) HBM write.
    """
    if interpret is None:
        interpret = default_interpret()
    p, n = Xt.shape
    assert p % block_p == 0 and n % block_n == 0, (p, n, block_p, block_n)
    nk = n // block_n
    spec = screening_corr_launch_spec(p, n, block_p=block_p,
                                      block_n=block_n, dtype=Xt.dtype)
    corr = pl.pallas_call(
        functools.partial(_corr_kernel, nk=nk),
        grid=spec.grid,
        in_specs=block_specs(spec.inputs),
        out_specs=block_specs(spec.outputs)[0],
        out_shape=out_shapes(spec.outputs)[0],
        interpret=interpret,
    )(Xt, theta[:, None])
    return corr[:, 0]
