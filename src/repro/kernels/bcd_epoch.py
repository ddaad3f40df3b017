"""Fused Pallas BCD *epoch* mega-kernel: whole blocks of cyclic BCD passes in
ONE kernel launch, with the residual carried in VMEM.

Why a mega-kernel
-----------------
The solver's hot loop (Algorithm 2) is cyclic block coordinate descent over
the compacted active groups: per group a tiny (n x ng) correlation, the fused
two-level prox, and a rank-one residual update.  As a ``jax.lax.scan`` over
groups (:func:`repro.core.solver.bcd_epochs`) every step is far too small to
feed the MXU, and the carried (n,) residual makes an HBM round trip between
steps — on the synthetic paper config the path engine runs ~150k of these
epochs, so the per-step dispatch/round-trip overhead dominates wall clock
even after screening has shrunk the math itself.  This kernel runs
``n_epochs`` full cyclic passes inside one ``pallas_call``:

* the (n,) **residual** and the whole (Gb, ng) **coefficient block** live in
  VMEM for the entire launch (output blocks whose index map ignores the
  epoch/group-tile grid axes stay resident — the standard accumulation
  pattern — and are flushed to HBM once per lambda);
* the compacted (Gb, n, ng) **design** is streamed tile-by-tile by the
  group-tile grid axis (``block_g`` groups per tile), so VMEM holds one
  design tile + the carried state, never the full buffer;
* the two-level prox (the ``sgl_prox`` math) is fused into each group
  update — no coefficient ever leaves VMEM between the gradient step and
  the group soft-threshold.

Grid layout: ``(B, n_epochs, Gb // block_g)`` with the group-tile axis
innermost, then epochs, then the **lambda batch** B outermost.  The leading
batch axis lets consecutive lambda-path points whose certified active sets
coincide share ONE launch (and one streaming pass over the design per epoch):
each lambda carries its own beta / residual / feature mask / threshold, while
the design tiles and Lipschitz constants are batch-invariant.

VMEM residency budget (per grid step, f64): the design tile
``block_g * n * ng * 8`` bytes dominates; the carried state adds
``(Gb * ng + n) * 8`` bytes (+ the same again for the warm-start inputs) and
the per-tile scalars are noise.  With the default ``block_g = 8`` a bucket
of Gb = 256 groups of ng = 16 features at n = 1024 samples costs ~1.0 MB
tile + ~0.1 MB state — comfortably inside a ~16 MB VMEM even double-buffered.
Buckets whose *tile* does not fit should lower ``block_g`` (the wrapper in
:mod:`repro.kernels.ops` exposes it); the carried state only becomes a
concern past Gb * ng ~ 10^5 active features, where the compacted buffer
itself would no longer be "compact".

Numerics: each group update is line-for-line the math of
:func:`repro.core.solver.bcd_epochs` (same operations, same order, same
guards), so interpret-mode f64 results are bit-identical to the
``lax.scan`` reference — asserted by ``tests/test_bcd_kernel.py``.  Masked
and bucket-padded groups ride along with ``Lg <= 0`` and a zero feature
mask: their coefficients are left untouched and their residual delta is an
exact zero, so duplicate-alias ``take`` slots are inert.  (The reference
does not visit the slots past the chunk holding the last live group; this
kernel steps through them as those exact no-ops, so the results agree.)

On CPU this executes with ``interpret=True`` (bit-parity reference mode); on
TPU the same code lowers to Mosaic.  TPU tiling note: ``ng`` rides the lane
axis and ``n`` the sublane axis of the streamed tile — pad to (8, 128)
multiples for aligned layouts (the interpret-mode wrapper intentionally does
NOT pad, so CPU parity tests see the exact reference shapes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._util import (
    ArraySpec,
    LaunchSpec,
    block_specs,
    default_interpret,
    fori_loop_i32,
    out_shapes,
)


def bcd_epoch_launch_spec(
    B: int,
    Gb: int,
    n: int,
    ng: int,
    n_epochs: int,
    *,
    block_g: int = 8,
    dtype="float64",
) -> LaunchSpec:
    """Auditable launch geometry of :func:`bcd_epoch_pallas`.

    Both outputs are VMEM-resident across the epoch (axis 1) and group-tile
    (axis 2) grid axes — the carried-state pattern the module docstring
    describes — hence ``carried=((1, 2), (1, 2))``.
    """
    return LaunchSpec(
        name="bcd_epoch",
        grid=(B, n_epochs, Gb // block_g),
        inputs=(
            ArraySpec((Gb, n, ng), (block_g, n, ng),
                      lambda b, e, g: (g, 0, 0), dtype),        # design tile
            ArraySpec((Gb, 1), (block_g, 1),
                      lambda b, e, g: (g, 0), dtype),           # Lg
            ArraySpec((Gb, 1), (block_g, 1),
                      lambda b, e, g: (g, 0), dtype),           # w
            ArraySpec((B, Gb, ng), (1, block_g, ng),
                      lambda b, e, g: (b, g, 0), dtype),        # feat mask
            ArraySpec((B, 1), (1, 1),
                      lambda b, e, g: (b, 0), dtype),           # lam
            ArraySpec((1, 1), (1, 1),
                      lambda b, e, g: (0, 0), dtype),           # tau
            ArraySpec((B, Gb, ng), (1, Gb, ng),
                      lambda b, e, g: (b, 0, 0), dtype),        # beta0
            ArraySpec((B, n), (1, n),
                      lambda b, e, g: (b, 0), dtype),           # resid0
        ),
        outputs=(
            ArraySpec((B, Gb, ng), (1, Gb, ng),
                      lambda b, e, g: (b, 0, 0), dtype),        # beta
            ArraySpec((B, n), (1, n),
                      lambda b, e, g: (b, 0), dtype),           # resid
        ),
        carried=((1, 2), (1, 2)),
        note="fused BCD epoch mega-kernel; VMEM-carried beta/resid",
    )


def _bcd_epoch_kernel(
    xt_ref,       # (block_g, n, ng) design tile (streamed by g)
    lg_ref,       # (block_g, 1)     block Lipschitz constants (<= 0: inert)
    w_ref,        # (block_g, 1)     group weights
    fm_ref,       # (1, block_g, ng) per-lambda float feature mask tile
    lam_ref,      # (1, 1)           this lambda
    tau_ref,      # (1, 1)           SGL mixing parameter
    beta0_ref,    # (1, Gb, ng)      warm-start coefficients
    resid0_ref,   # (1, n)           warm-start residual
    beta_ref,     # (1, Gb, ng)      OUT, VMEM-resident across (e, g)
    resid_ref,    # (1, n)           OUT, VMEM-resident across (e, g)
    *,
    block_g: int,
):
    e = pl.program_id(1)
    g = pl.program_id(2)

    @pl.when((e == 0) & (g == 0))
    def _init():
        # First step of this lambda: adopt the warm start.  From here on the
        # carried state never leaves VMEM until the batch index changes.
        beta_ref[...] = beta0_ref[...]
        resid_ref[...] = resid0_ref[...]

    lam_ = lam_ref[0, 0]
    tau = tau_ref[0, 0]
    base = g * block_g
    resid = resid_ref[0, :]

    def group_update(i, resid):
        # Line-for-line the update of repro.core.solver.bcd_epochs
        # (bit-parity contract — see the module docstring).
        Xg = xt_ref[i]                                   # (n, ng)
        L = lg_ref[i, 0]
        lv = (L > 0).astype(resid.dtype)
        safe_L = jnp.where(L > 0, L, 1.0)
        step = lam_ / safe_L
        t1 = tau * step
        t2 = (1.0 - tau) * w_ref[i, 0] * step
        m = fm_ref[0, i]                                 # (ng,)
        bg = beta_ref[0, base + i]                       # (ng,)
        grad_step = (Xg.T @ resid) / safe_L
        z = (bg + grad_step) * m
        z = jnp.sign(z) * jnp.maximum(jnp.abs(z) - t1, 0.0)
        nrm = jnp.linalg.norm(z)
        z = jnp.maximum(1.0 - t2 / jnp.maximum(nrm, 1e-30), 0.0) * z
        new_bg = jnp.where(lv > 0, z, bg)
        beta_ref[0, base + i] = new_bg
        return resid + Xg @ (bg - new_bg)

    resid = fori_loop_i32(block_g, group_update, resid)
    resid_ref[0, :] = resid


def bcd_epoch_pallas(
    Xt: jax.Array,        # (Gb, n, ng) compacted group-major design
    Lg: jax.Array,        # (Gb,)  block Lipschitz constants (* gmask)
    w: jax.Array,         # (Gb,)  group weights
    fmask: jax.Array,     # (B, Gb, ng) float feature masks (0 = inert)
    lam_b: jax.Array,     # (B,)   per-lambda regularisation
    tau: jax.Array,       # ()     SGL mixing parameter
    beta: jax.Array,      # (B, Gb, ng) warm-start coefficients
    resid: jax.Array,     # (B, n) warm-start residuals
    n_epochs: int,
    *,
    block_g: int = 8,
    interpret: bool | None = None,
):
    """Run ``n_epochs`` cyclic BCD passes for B lambdas in ONE launch.

    Returns ``(beta, resid)`` of the same shapes.  ``Gb`` must be a multiple
    of ``block_g`` (the :mod:`repro.kernels.ops` wrapper pads).
    """
    if interpret is None:
        interpret = default_interpret()
    B, Gb, ng = beta.shape
    n = Xt.shape[1]
    assert Xt.shape == (Gb, n, ng), (Xt.shape, beta.shape)
    assert Gb % block_g == 0, (Gb, block_g)
    spec = bcd_epoch_launch_spec(B, Gb, n, ng, n_epochs, block_g=block_g,
                                 dtype=beta.dtype)
    return pl.pallas_call(
        functools.partial(_bcd_epoch_kernel, block_g=block_g),
        grid=spec.grid,
        in_specs=block_specs(spec.inputs),
        out_specs=block_specs(spec.outputs),
        out_shape=out_shapes(spec.outputs),
        interpret=interpret,
    )(
        Xt,
        Lg[:, None],
        w[:, None],
        fmask,
        lam_b[:, None],
        jnp.reshape(tau, (1, 1)),
        beta,
        resid,
    )


# ----------------------------------------------------------------------------
# Logistic variant: the VMEM carry is the linear predictor z = X beta
# ----------------------------------------------------------------------------

def bcd_epoch_logistic_launch_spec(
    B: int,
    Gb: int,
    n: int,
    ng: int,
    n_epochs: int,
    *,
    block_g: int = 8,
    dtype="float64",
) -> LaunchSpec:
    """Auditable launch geometry of :func:`bcd_epoch_logistic_pallas`.

    Same grid/streaming layout as :func:`bcd_epoch_launch_spec`, with the
    carried (n,) state being the linear predictor instead of the lsq
    residual, plus the batch-invariant (n,) response ``y`` as one extra
    streamed-once input (its index map ignores the whole grid).
    """
    return LaunchSpec(
        name="bcd_epoch_logistic",
        grid=(B, n_epochs, Gb // block_g),
        inputs=(
            ArraySpec((Gb, n, ng), (block_g, n, ng),
                      lambda b, e, g: (g, 0, 0), dtype),        # design tile
            ArraySpec((Gb, 1), (block_g, 1),
                      lambda b, e, g: (g, 0), dtype),           # Lg
            ArraySpec((Gb, 1), (block_g, 1),
                      lambda b, e, g: (g, 0), dtype),           # w
            ArraySpec((B, Gb, ng), (1, block_g, ng),
                      lambda b, e, g: (b, g, 0), dtype),        # feat mask
            ArraySpec((B, 1), (1, 1),
                      lambda b, e, g: (b, 0), dtype),           # lam
            ArraySpec((1, 1), (1, 1),
                      lambda b, e, g: (0, 0), dtype),           # tau
            ArraySpec((1, n), (1, n),
                      lambda b, e, g: (0, 0), dtype),           # y (labels)
            ArraySpec((B, Gb, ng), (1, Gb, ng),
                      lambda b, e, g: (b, 0, 0), dtype),        # beta0
            ArraySpec((B, n), (1, n),
                      lambda b, e, g: (b, 0), dtype),           # z0
        ),
        outputs=(
            ArraySpec((B, Gb, ng), (1, Gb, ng),
                      lambda b, e, g: (b, 0, 0), dtype),        # beta
            ArraySpec((B, n), (1, n),
                      lambda b, e, g: (b, 0), dtype),           # z
        ),
        carried=((1, 2), (1, 2)),
        note="logistic BCD mega-kernel; VMEM-carried beta/linear predictor",
    )


def _bcd_epoch_logistic_kernel(
    xt_ref,       # (block_g, n, ng) design tile (streamed by g)
    lg_ref,       # (block_g, 1)     block spectral norms ||X_g||_2^2
    w_ref,        # (block_g, 1)     group weights
    fm_ref,       # (1, block_g, ng) per-lambda float feature mask tile
    lam_ref,      # (1, 1)           this lambda
    tau_ref,      # (1, 1)           SGL mixing parameter
    y_ref,        # (1, n)           {0,1} labels (batch-invariant)
    beta0_ref,    # (1, Gb, ng)      warm-start coefficients
    z0_ref,       # (1, n)           warm-start linear predictor X beta
    beta_ref,     # (1, Gb, ng)      OUT, VMEM-resident across (e, g)
    z_ref,        # (1, n)           OUT, VMEM-resident across (e, g)
    *,
    block_g: int,
):
    e = pl.program_id(1)
    g = pl.program_id(2)

    @pl.when((e == 0) & (g == 0))
    def _init():
        beta_ref[...] = beta0_ref[...]
        z_ref[...] = z0_ref[...]

    lam_ = lam_ref[0, 0]
    tau = tau_ref[0, 0]
    y = y_ref[0, :]
    base = g * block_g
    z = z_ref[0, :]

    def group_update(i, z):
        # Line-for-line the update of repro.core.solver.bcd_epochs_loss
        # for LogisticLoss (bit-parity contract, tests/test_losses.py):
        # majorized step with block bound nu*Lg = Lg/4, fresh gradient
        # rho = y - sigmoid(z) per group, rank-one predictor update.
        Xg = xt_ref[i]                                   # (n, ng)
        L = lg_ref[i, 0]
        lv = (L > 0).astype(z.dtype)
        Lmaj = 0.25 * L                                  # nu * Lg
        safe_L = jnp.where(L > 0, Lmaj, 1.0)
        step = lam_ / safe_L
        t1 = tau * step
        t2 = (1.0 - tau) * w_ref[i, 0] * step
        m = fm_ref[0, i]                                 # (ng,)
        bg = beta_ref[0, base + i]                       # (ng,)
        rho = y - jax.nn.sigmoid(z)                      # (n,)
        grad_step = (Xg.T @ rho) / safe_L
        u = (bg + grad_step) * m
        u = jnp.sign(u) * jnp.maximum(jnp.abs(u) - t1, 0.0)
        nrm = jnp.linalg.norm(u)
        u = jnp.maximum(1.0 - t2 / jnp.maximum(nrm, 1e-30), 0.0) * u
        new_bg = jnp.where(lv > 0, u, bg)
        beta_ref[0, base + i] = new_bg
        return z + Xg @ (new_bg - bg)

    z = fori_loop_i32(block_g, group_update, z)
    z_ref[0, :] = z


def bcd_epoch_logistic_pallas(
    Xt: jax.Array,        # (Gb, n, ng) compacted group-major design
    Lg: jax.Array,        # (Gb,)  block spectral norms (* gmask)
    w: jax.Array,         # (Gb,)  group weights
    fmask: jax.Array,     # (B, Gb, ng) float feature masks (0 = inert)
    lam_b: jax.Array,     # (B,)   per-lambda regularisation
    tau: jax.Array,       # ()     SGL mixing parameter
    y: jax.Array,         # (n,)   {0,1} labels
    beta: jax.Array,      # (B, Gb, ng) warm-start coefficients
    z: jax.Array,         # (B, n) warm-start linear predictors
    n_epochs: int,
    *,
    block_g: int = 8,
    interpret: bool | None = None,
):
    """Logistic twin of :func:`bcd_epoch_pallas`: ``n_epochs`` majorized
    cyclic BCD passes for B lambdas in one launch, carrying the linear
    predictor in VMEM.  Returns ``(beta, z)``."""
    if interpret is None:
        interpret = default_interpret()
    B, Gb, ng = beta.shape
    n = Xt.shape[1]
    assert Xt.shape == (Gb, n, ng), (Xt.shape, beta.shape)
    assert Gb % block_g == 0, (Gb, block_g)
    spec = bcd_epoch_logistic_launch_spec(
        B, Gb, n, ng, n_epochs, block_g=block_g, dtype=beta.dtype)
    return pl.pallas_call(
        functools.partial(_bcd_epoch_logistic_kernel, block_g=block_g),
        grid=spec.grid,
        in_specs=block_specs(spec.inputs),
        out_specs=block_specs(spec.outputs),
        out_shape=out_shapes(spec.outputs),
        interpret=interpret,
    )(
        Xt,
        Lg[:, None],
        w[:, None],
        fmask,
        lam_b[:, None],
        jnp.reshape(tau, (1, 1)),
        y[None, :],
        beta,
        z,
    )
