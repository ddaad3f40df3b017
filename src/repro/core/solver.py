"""ISTA-BC (block coordinate descent) with dynamic safe screening — Algorithm 2.

Faithful reproduction of the paper's solver:

* cyclic block coordinate descent over *active* groups, block Lipschitz
  steps  L_g = ||X_g||_2^2, two-level prox (soft-threshold then group
  soft-threshold),
* duality gap computed every ``f_ce`` passes (paper: f_ce = 10), giving the
  dual feasible point via residual rescaling (Eq. 15) and the GAP safe
  sphere (Thm 2), from which groups/features are screened (Thm 1),
* alternative spheres (static / dynamic / DST3 / none / unsafe strong) for
  the paper's comparison experiments (Fig. 2/3) — pluggable
  :mod:`repro.rules` strategy objects sharing the one round skeleton
  (:func:`_screen_round`), which owns everything rule-independent and asks
  a rule only for its sphere.

TPU/XLA adaptation (see DESIGN.md §3): screened variables are removed by
**gathering the surviving groups into a dense buffer padded to power-of-two
buckets**, so the inner jitted BCD epochs only touch active data; XLA
recompiles at most log2(G) times and the compile cache is shared across the
lambda path.  Screening certificates are permanent (safe), so active sets
shrink monotonically.

Compacted certified rounds: the paper keeps the gap/screening round's
correlation X^T theta on the *full* problem every f_ce passes, which stays
O(n p) even when 99% of groups hold a permanent certificate — exactly the
cost the rule exists to kill.  Since certificates are permanent, screened
groups never need exact correlations again; they re-enter only through the
dual scaling Omega^D(X^T resid) (Eq. 15).  :func:`_screen_round_compact`
therefore runs the whole round — residual, correlation, dual norm, gap,
Theorem-1 tests — on the gathered (n, p_active) buffer and *bounds* the
screened groups' dual-norm terms from the last full round's cached
reference (``SolveCaches.resid_ref`` / ``ref_terms``; bound proof in
:mod:`repro.core.screening`).  When the bound stays below
max(lambda, active-term max) the compact round is EXACT; otherwise the
driver falls back to the full :func:`_screen_round` (which also refreshes
the reference).  The driver additionally forces a full round every
``full_round_every`` rounds and always re-confirms convergence with a full
round, so every *reported* gap/certificate is full-problem exact.

Fused BCD epochs: the inner epochs themselves dispatch on
``SolverConfig.solver_backend`` (resolved by :func:`resolve_solver_backend`,
the same auto/xla/pallas policy as the screening backend) — ``"pallas"``
replaces the per-group ``lax.scan`` of :func:`bcd_epochs` with the
:mod:`repro.kernels.bcd_epoch` mega-kernel, which runs whole epoch blocks in
ONE launch with the residual carried in VMEM and a lambda-batch grid axis
(consecutive path points with coinciding certified active sets solve
together; see :meth:`repro.core.session.SGLSession.solve_path`).  The
``lax.scan`` path stays as the XLA fallback and the bit-parity reference:
interpret-mode f64 results of the fused kernel are bit-identical to it.
(The *epoch math* parity is structural; the Pallas reduced-gap correlation
used between blocks accumulates per n-tile, so the early-exit heuristic
can differ from the einsum in the last ulp — end-to-end path equality
therefore additionally requires that no reduced gap lands within ~1e-13
relative of ``tol``, which the CI smoke config pins deterministically.)

This module holds the jitted machinery (``bcd_epochs``, ``_inner_rounds``,
``_screen_round``, ``_gather_static``) plus the round/caches primitives; the
outer drivers live on :class:`repro.core.session.SGLSession` and the
module-level :func:`solve` is a thin deprecated wrapper delegating there.

Path-engine hooks (used by :meth:`repro.core.session.SGLSession.solve_path`):

* :func:`screen_round` is the public resumable-round API — one certified
  gap + Theorem-1 screening round, returned as a :class:`RoundResult`.
  The path engine calls it at a new ``lambda_t`` with the previous
  lambda's ``beta`` (the paper's *sequential* rule) and hands the result
  to the solve as ``first_round`` so the round is not recomputed.
* the hot correlation ``X^T resid`` and the SGL dual norm inside the round
  are routed through the Pallas kernels (:mod:`repro.kernels.ops`) when
  ``screen_backend`` resolves to ``"pallas"`` (the default on TPU).
* :class:`SolveCaches` carries the compacted gather buffers *across* calls:
  a path engine passes one instance for the whole lambda path, so
  consecutive lambdas whose certified active set is unchanged skip the
  (n x p_active) re-gather and share the jit cache.
* ``check_every`` controls the granularity of the reduced-gap early-exit
  inside the jitted inner loop; the path engine uses 1 (check after every
  BCD pass) so warm-started lambdas stop after exactly the epochs they
  need instead of a full ``f_ce`` block.
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import screening as scr
from . import sgl
from .sgl import SGLProblem
from ..kernels import _util as kernel_util
from ..kernels import ops as kops
from ..losses import Loss, resolve_loss
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..rules import RuleState, ScreeningRule, resolve_rule

_M_GATHERS = obs_metrics.REGISTRY.counter(
    "solver.gathers",
    help="Compacted gather-buffer rebuilds (certified active set shrank) "
         "across all SolveCaches instances in the process")

__all__ = [
    "SolveResult",
    "SolveCaches",
    "RoundResult",
    "solve",
    "bcd_epochs",
    "bcd_epochs_loss",
    "screen_round",
    "resolve_backend",
    "resolve_screen_backend",
    "resolve_solver_backend",
    "check_rule_loss",
]


class RoundResult(NamedTuple):
    """One certified gap + Theorem-1 screening round (GAP-sphere certificate).

    Replaces the bare ``(gap, theta, group_active, feat_active)`` 4-tuple the
    round family used to hand around by positional index; being a tuple
    subclass, positional unpacking still works (slice ``[:4]`` for the
    legacy quartet).  ``theta`` is None on the distributed strategy (the
    dual point stays sharded on the mesh).  ``compact`` marks a round
    evaluated on the compacted active buffer (exact, but the driver always
    confirms convergence with a full round before reporting — see
    :meth:`repro.core.session.SGLSession.solve`).
    """

    gap: jax.Array                   # certified duality gap at (beta, lam)
    theta: Optional[jax.Array]       # (n,) dual feasible point (Eq. 15)
    group_active: jax.Array          # (G,) bool — False = certified zero
    feat_active: jax.Array           # (G, ng) bool — False = certified zero
    compact: bool = False            # round ran on the compacted buffer
    safe: bool = True                # masks are certificates; False for
                                     #   rounds produced by an unsafe rule
                                     #   (repro.rules ScreeningRule.is_safe
                                     #   False) — heuristic discards, never
                                     #   reported as zero-certificates


class SolveResult(NamedTuple):
    beta: jax.Array            # (G, ng) grouped coefficients
    theta: jax.Array           # (n,) dual feasible point
    gap: jax.Array             # final duality gap
    n_epochs: int              # BCD passes performed
    group_active: np.ndarray   # (G,) final active mask
    feat_active: np.ndarray    # (G, ng) final active mask
    gap_history: list
    active_history: list       # [(epoch, n_groups_active, n_feats_active)]
    degraded: Optional[str] = None  # budget-trip reason ("deadline" |
                                    #   "epoch_budget"); gap stays the
                                    #   honest last-certified value


class SolveCaches:
    """Mutable cross-call caches for :func:`solve`.

    Holds the compacted gather buffers keyed on the certified active-group
    set.  Within one ``solve`` the active set only shrinks, so the gather is
    redone a handful of times; across a lambda path the previous lambda's
    active set is usually a subset of the next one's *certified* set, and on
    dense grids it is frequently identical — passing one ``SolveCaches`` down
    the whole path (see :func:`repro.core.path.solve_path`) skips those
    re-gathers entirely and keeps XLA's compile cache warm (same power-of-two
    bucket shapes).

    Also carries the compact-round reference state: the residual and the
    per-group dual-norm terms of the last *full* certified round
    (``resid_ref`` / ``ref_terms``, refreshed by
    :meth:`repro.core.session.SGLSession._certified_round`), which let
    :func:`_screen_round_compact` bound the screened groups' dual-norm
    contribution without touching their columns, plus (Pallas backend) the
    active-row slice of the persistent transposed design keyed on the same
    active-set bytes as the gather.

    Entries are keyed on problem identity + active-set bytes, so sharing an
    instance across problems degrades to a miss instead of serving stale
    buffers; one instance per lambda path is the intended use.
    """

    __slots__ = ("gather_key", "gather_val", "n_gathers", "_problem",
                 "xt_rows_key", "xt_rows_val", "resid_ref", "ref_terms")

    def __init__(self) -> None:
        self.gather_key: Optional[bytes] = None
        self.gather_val = None
        self.n_gathers: int = 0
        self._problem: Optional[SGLProblem] = None
        self.xt_rows_key: Optional[bytes] = None
        self.xt_rows_val = None
        self.resid_ref: Optional[jax.Array] = None
        self.ref_terms: Optional[jax.Array] = None

    def _sync_problem(self, problem: SGLProblem) -> None:
        if problem is not self._problem:
            # A different problem with a byte-identical mask must be a cache
            # MISS, not silently-served stale buffers; reference residuals
            # of another problem are meaningless here.
            self._problem = problem
            self.gather_key = None
            self.xt_rows_key = None
            self.resid_ref = None
            self.ref_terms = None

    def gather(self, problem: SGLProblem, group_active: np.ndarray):
        self._sync_problem(problem)
        key = group_active.tobytes()
        if key != self.gather_key:
            with obs_trace.span("gather"):
                self.gather_val = _gather_static(problem, group_active)
            self.gather_key = key
            self.n_gathers += 1
            _M_GATHERS.inc()
        return self.gather_val

    def gather_xt_rows(self, problem: SGLProblem, group_active: np.ndarray,
                       xt_pre: jax.Array):
        """Active-row slice of the persistent transposed design (Pallas
        compact rounds), keyed on the same active-set bytes as ``gather``
        — a row *gather*, never an on-the-fly transpose."""
        self._sync_problem(problem)
        key = group_active.tobytes()
        if key != self.xt_rows_key:
            _, take, *_ = self.gather(problem, group_active)
            with obs_trace.span("gather"):
                self.xt_rows_val = kops.gather_transposed_rows(
                    xt_pre, take, problem.ng
                )
            self.xt_rows_key = key
        return self.xt_rows_val

    def set_refs(self, problem: SGLProblem, resid: jax.Array,
                 terms: jax.Array) -> None:
        """Adopt a full round's residual + per-group dual-norm terms as the
        compact-round reference point."""
        self._sync_problem(problem)
        self.resid_ref = resid
        self.ref_terms = terms


# ----------------------------------------------------------------------------
# Inner jitted BCD epochs over a compacted active buffer
# ----------------------------------------------------------------------------

#: Slots per iteration of the live-bounded group loop.  Every compacted
#: bucket is a power of two >= 8, so a chunk never runs past the buffer;
#: it matches ``block_g`` of :mod:`repro.kernels.bcd_epoch`.
_GROUP_CHUNK = 8
#: Unroll of the scan inside a chunk.  On a v5e, f64 group steps took
#: 39.3 us rolled and 36.0 us unrolled by 2; unrolled by 8 (36.5 us) the
#: epoch programs had up to 2.6x the code and took 8 s longer to load
#: from the compile cache.
_CHUNK_UNROLL = 2


def _chunk(Gb: int) -> int:
    """Chunk size for a buffer of ``Gb`` slots: ``_GROUP_CHUNK`` on every
    compacted bucket, a divisor of ``Gb`` on the uncompacted design."""
    return np.gcd(Gb, _GROUP_CHUNK).item()


def _live_slots(Lg: jax.Array) -> jax.Array:
    """Slots a pass visits: up to the end of the chunk holding the last
    live slot (``Lg > 0``), as a device int32."""
    Gb = Lg.shape[0]
    C = _chunk(Gb)
    last = jnp.max(jnp.where(Lg > 0, jnp.arange(1, Gb + 1, dtype=jnp.int32),
                             0))
    return (last + C - 1) // C * C


def _live_group_pass(group_update, carry, beta, Xt, per_slot, n_slots):
    """One cyclic pass of ``group_update`` over slots ``[0, n_slots)``.

    ``n_slots`` comes from :func:`_live_slots`.  The loop runs chunk by
    chunk: a ``dynamic_slice`` of each per-slot input, the unchanged
    ``group_update`` in a ``lax.scan``, and the new rows written back into
    ``beta``.  The same arithmetic in the same order as a scan
    over every slot: a dead slot leaves its row and the carry exactly as
    they are, so the dead slots past the last live chunk are not visited.
    """
    C = _chunk(Xt.shape[0])

    def chunk(c, state):
        carry, beta = state
        start = c * C

        def rows(a):
            return jax.lax.dynamic_slice_in_dim(a, start, C)

        carry, new_rows = jax.lax.scan(
            group_update, carry,
            (rows(Xt), rows(beta), *map(rows, per_slot)),
            unroll=_CHUNK_UNROLL,
        )
        return carry, jax.lax.dynamic_update_slice_in_dim(
            beta, new_rows, start, 0)

    return jax.lax.fori_loop(0, n_slots // C, chunk, (carry, beta))


@functools.partial(jax.jit, static_argnames=("n_epochs",), donate_argnums=(4, 5))
def bcd_epochs(
    Xt: jax.Array,         # (Gb, n, ng) compacted design (group-major)
    Lg: jax.Array,         # (Gb,)
    w: jax.Array,          # (Gb,)
    feat_mask: jax.Array,  # (Gb, ng) float mask (0 also encodes screened feats)
    beta: jax.Array,       # (Gb, ng)
    resid: jax.Array,      # (n,)
    tau: jax.Array,
    lam_: jax.Array,
    n_epochs: int,
):
    """Run ``n_epochs`` cyclic BCD passes, carrying the residual.

    Update for group g (paper Section 6):
        z      = beta_g + X_g^T resid / L_g            (gradient step)
        z      = S_{tau lam / L_g}(z)                  (feature prox)
        beta_g = S^gp_{(1-tau) w_g lam / L_g}(z)       (group prox)
        resid += X_g (beta_g_old - beta_g_new)
    Inactive (padded / screened) groups have feat_mask == 0 and Lg <= 0:
    their updates are masked out, and slots past the chunk holding the
    last live group are not visited (:func:`_live_group_pass`).
    """
    live = (Lg > 0).astype(beta.dtype)                # (Gb,)
    n_slots = _live_slots(Lg)
    safe_L = jnp.where(Lg > 0, Lg, 1.0)
    step = lam_ / safe_L                              # alpha_g = lam / L_g
    thr1 = tau * step                                 # (Gb,)
    thr2 = one_minus(tau) * w * step                  # (Gb,)

    def group_update(resid, inputs):
        Xg, bg, L, t1, t2, m, lv = inputs
        grad_step = (Xg.T @ resid) / L                # (ng,)
        z = (bg + grad_step) * m
        z = jnp.sign(z) * jnp.maximum(jnp.abs(z) - t1, 0.0)
        nrm = jnp.linalg.norm(z)
        z = jnp.maximum(one_minus(t2 / jnp.maximum(nrm, 1e-30)), 0.0) * z
        new_bg = jnp.where(lv > 0, z, bg)
        resid = resid + Xg @ (bg - new_bg)
        return resid, new_bg

    def epoch(carry, _):
        beta, resid = carry
        resid, beta = _live_group_pass(
            group_update, resid, beta, Xt,
            (safe_L, thr1, thr2, feat_mask, live), n_slots,
        )
        return (beta, resid), None

    (beta, resid), _ = jax.lax.scan(epoch, (beta, resid), None, length=n_epochs)
    return beta, resid


@functools.partial(jax.jit, static_argnames=("loss", "n_epochs"),
                   donate_argnums=(4, 5))
def bcd_epochs_loss(
    Xt: jax.Array,         # (Gb, n, ng) compacted design (group-major)
    Lg: jax.Array,         # (Gb,)
    w: jax.Array,          # (Gb,)
    feat_mask: jax.Array,  # (Gb, ng) float mask
    beta: jax.Array,       # (Gb, ng)
    z: jax.Array,          # (n,) linear predictor X beta (the loss carry)
    tau: jax.Array,
    lam_: jax.Array,
    y: jax.Array,          # (n,) response (the loss gradient needs it)
    loss: Loss,
    n_epochs: int,
):
    """Loss-generic twin of :func:`bcd_epochs`: majorized BCD carrying the
    linear predictor ``z = X beta`` instead of the lsq residual.

    Per group (majorize-minimize; arXiv 1611.05780 §4):
        rho    = -grad F(z) = loss.neg_grad(y, z)     (fresh each group)
        z_g    = beta_g + X_g^T rho / (nu L_g)        (gradient step)
        beta_g = two-level prox at step lam / (nu L_g)
        z     += X_g (beta_g_new - beta_g_old)
    ``nu L_g`` upper-bounds the block Hessian ``X_g^T diag(f'') X_g``
    (per-sample curvature <= nu), so every epoch decreases the primal.
    For ``loss="lsq"`` (nu=1, rho = y - z) this is algebraically the
    :func:`bcd_epochs` update — but the carry differs (z vs resid), so the
    lsq solver keeps the original function; this one serves non-quadratic
    losses and the parity tests.
    """
    live = (Lg > 0).astype(beta.dtype)                # (Gb,)
    n_slots = _live_slots(Lg)
    Lmaj = loss.nu * Lg                               # block majorization
    safe_L = jnp.where(Lg > 0, Lmaj, 1.0)
    step = lam_ / safe_L
    thr1 = tau * step                                 # (Gb,)
    thr2 = one_minus(tau) * w * step                  # (Gb,)

    def group_update(z, inputs):
        Xg, bg, L, t1, t2, m, lv = inputs
        rho = loss.neg_grad(y, z)                     # (n,)
        grad_step = (Xg.T @ rho) / L                  # (ng,)
        u = (bg + grad_step) * m
        u = jnp.sign(u) * jnp.maximum(jnp.abs(u) - t1, 0.0)
        nrm = jnp.linalg.norm(u)
        u = jnp.maximum(one_minus(t2 / jnp.maximum(nrm, 1e-30)), 0.0) * u
        new_bg = jnp.where(lv > 0, u, bg)
        z = z + Xg @ (new_bg - bg)
        return z, new_bg

    def epoch(carry, _):
        beta, z = carry
        z, beta = _live_group_pass(
            group_update, z, beta, Xt,
            (safe_L, thr1, thr2, feat_mask, live), n_slots,
        )
        return (beta, z), None

    (beta, z), _ = jax.lax.scan(epoch, (beta, z), None, length=n_epochs)
    return beta, z


# ----------------------------------------------------------------------------
# Certified gap + screening round (resumable-round API)
# ----------------------------------------------------------------------------

def resolve_backend(backend: str, dtype, *, what: str = "backend") -> str:
    """Shared backend resolution for every Pallas/XLA dispatch knob.

    ``"auto"`` picks the Pallas kernels only where they compile: on TPU and
    for a problem ``dtype`` of at most 32 bits (Mosaic has no 64-bit
    types).  Elsewhere it picks plain XLA — on CPU Pallas would only run
    interpreted, and an f64 problem on TPU runs XLA's emulated f64.
    ``"xla"``/``"pallas"`` force; forcing ``"pallas"`` on an f64 problem
    on TPU raises, since no kernel would compile.  ``what`` only labels
    the error message (``screen backend`` / ``solver backend``).
    """
    if backend not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown {what}: {backend!r}")
    compiles = np.dtype(dtype).itemsize <= 4
    if backend == "auto":
        return "pallas" if kernel_util.on_tpu() and compiles else "xla"
    if backend == "pallas" and kernel_util.on_tpu() and not compiles:
        raise ValueError(
            f"{what}='pallas' cannot run a {np.dtype(dtype).name} problem "
            "on TPU: Mosaic compiles no 64-bit kernel (use 'auto' or 'xla')"
        )
    return backend


def resolve_screen_backend(backend: str, dtype) -> str:
    """Resolve the screening correlation/dual-norm backend."""
    return resolve_backend(backend, dtype, what="screen backend")


def resolve_solver_backend(backend: str, dtype) -> str:
    """Resolve the BCD-epoch solver backend (``SolverConfig.solver_backend``):
    ``"pallas"`` runs the inner epochs through the fused
    :mod:`repro.kernels.bcd_epoch` mega-kernel, ``"xla"`` keeps the
    ``lax.scan`` reference (the bit-parity fallback)."""
    return resolve_backend(backend, dtype, what="solver backend")


def _design_2d(problem: SGLProblem) -> jax.Array:
    """The grouped design as one (n, G*ng) matrix, reshaped inside the
    calling program.  XLA emulates f64 dots on the TPU, and a contraction
    over the two axes (G, ng) of the 3-D design costs it several times the
    same contraction over one axis of this matrix, at the same precision."""
    n, G, ng = problem.X.shape
    return problem.X.reshape(n, G * ng)


def _corr_grouped(problem: SGLProblem, v: jax.Array, backend: str,
                  xt_pre: Optional[jax.Array]) -> jax.Array:
    """Backend-routed grouped correlation X^T v — the shared skeleton's one
    correlation primitive.  ``"pallas"`` runs the corr-only Pallas matvec
    over the persistent transposed design (on-the-fly transposes are
    audit-counted); ``"xla"`` one rank-2 product ``v @ X2``."""
    if backend == "pallas":
        return kops.screening_corr_grouped(problem.X, v, xt_pre=xt_pre)
    return (v @ _design_2d(problem)).reshape(problem.G, problem.ng)


def check_rule_loss(rule: ScreeningRule, loss: Loss) -> None:
    """Fail fast on a rule x loss pairing the rule's sphere cannot prove.

    Mirrors the rule x mesh gate in :class:`repro.core.session.SGLSession`:
    rules whose geometry is least-squares-specific declare
    ``supported_losses=("lsq",)`` and any other loss is rejected at
    construction time, never silently screened unsafely.
    """
    if rule.supported_losses is not None and (
            loss.name not in rule.supported_losses):
        raise ValueError(
            f"rule={rule.name!r} supports losses "
            f"{list(rule.supported_losses)}, not loss={loss.name!r} "
            f"(its sphere is built from the quadratic dual's y/lambda "
            f"geometry); use the GAP family for non-lsq losses"
        )


@functools.partial(jax.jit, static_argnames=("rule", "backend", "loss"))
def _screen_round(problem: SGLProblem, beta: jax.Array, lam_: jax.Array,
                  lam_max: jax.Array, rule: ScreeningRule,
                  backend: str = "xla",
                  xt_pre: Optional[jax.Array] = None,
                  loss: Optional[Loss] = None):
    """One fused FULL gap + screening round (single XLA program) — the
    shared sphere-test SKELETON every :class:`repro.rules.ScreeningRule`
    plugs into.

    The eager version of this round cost ~50 small dispatches; fusing it is
    what makes screening overhead negligible per round (see EXPERIMENTS.md
    §Perf, solver iteration 1).  The skeleton owns everything
    rule-independent — the residual, the Eq. 15 dual scaling, the duality
    gap, the Theorem-1 tests, and the Pallas corr/dual-norm kernel routing
    (fed from the persistent transposed design, so the transpose audit
    covers every rule) — and asks the rule only for its sphere via
    ``rule.center_and_radius`` (a hashable static argument: equal rule
    instances share one compiled program).  A rule that cannot supply
    ``X^T center`` for free gets it from the SAME backend-routed
    correlation primitive, so e.g. the dynamic sphere's second correlation
    also runs on the Pallas kernel on TPU.

    Returns ``(RoundResult, resid, terms)`` where ``resid``/``terms`` (the
    residual and the per-group dual-norm terms) are the reference state the
    compacted round (:func:`_screen_round_compact`) bounds screened groups
    from — the session stores them on :class:`SolveCaches` after every full
    round.  For rules that do not screen dynamically the masks are
    all-true; rounds from unsafe rules come back flagged ``safe=False``.

    The round passes over the design exactly twice, each time as a rank-2
    product on ``X.reshape(n, G*ng)`` (:func:`_design_2d`): ``X beta``,
    and ``X^T resid`` (plus a dynamic rule's ``X^T center``).  The lsq gap
    is taken from the round's own residual.

    ``backend="pallas"`` computes the hot X^T resid correlation through the
    corr-only Pallas matvec kernel and the SGL dual norm through the Pallas
    bisection kernel (kernels.ops); ``"xla"`` uses plain products.
    ``xt_pre`` is the persistent (p, n) transposed design from
    :func:`repro.kernels.ops.prepare_transposed` — without it every
    Pallas-backed round materialises a fresh transposed copy of X.

    ``loss`` (static): a :class:`repro.losses.Loss`, or None for the
    historical squared loss.  The skeleton generalizes by swapping the
    residual for ``rho = -grad F(X beta)`` (Eq. 15 is otherwise verbatim)
    and the gap for the loss's primal/dual pair.  The sphere test sees the
    loss only through ``RuleState.nu``.
    """
    lsq = loss is None or loss.name == "lsq"
    z = _design_2d(problem) @ beta.reshape(-1)
    if lsq:
        resid = problem.y - z
    else:
        resid = loss.neg_grad(problem.y, z)   # generalized residual rho
    corr = _corr_grouped(problem, resid, backend, xt_pre)
    if backend == "pallas":
        terms = kops.sgl_dual_norm_terms_fused(corr, problem.tau, problem.w)
    else:
        terms = sgl.sgl_dual_norm_terms(corr, problem.tau, problem.w)
    dual_norm = jnp.max(terms)
    scale = jnp.maximum(lam_, dual_norm)
    theta = resid / scale
    penalty = lam_ * sgl.sgl_norm(beta, problem.tau, problem.w)
    if lsq:
        # sgl.primal from the round's own residual: its grouped X beta
        # would be a third pass over the design.
        gap = (0.5 * jnp.sum(resid * resid) + penalty
               - sgl.dual(problem, theta, lam_))
    else:
        gap = (loss.value(problem.y, z) + penalty
               - loss.dual_obj(problem.y, theta, lam_))

    if rule.is_dynamic:
        state = RuleState(
            problem=problem, beta=beta, resid=resid, corr=corr, scale=scale,
            theta=theta, gap=gap, lam=lam_, lam_max=lam_max,
            nu=1.0 if lsq else float(loss.nu),
        )
        center, radius, corr_c = rule.center_and_radius(state)
        if corr_c is None:
            corr_c = _corr_grouped(problem, center, backend, xt_pre)
        res = scr.screen_with_corr(
            problem, scr.Sphere(center, radius), corr_c
        )
    else:  # "none" / "static" — no dynamic screening, gap-only round
        res = scr.ScreenResult(
            jnp.ones((problem.G,), bool),
            jnp.asarray(problem.feat_mask),
            scr.Sphere(theta, jnp.inf),
        )
    round_res = RoundResult(gap, theta, res.group_active, res.feat_active,
                            safe=rule.is_safe)
    return round_res, resid, terms


@functools.partial(jax.jit, static_argnames=("backend",))
def _screen_round_compact(
    problem: SGLProblem,
    Xt: jax.Array,            # (Gb, n, ng) gathered active design
    take: jax.Array,          # (Gb,) group indices (padded slots alias 0)
    gmask: jax.Array,         # (Gb,) float, 0 on padded slots
    beta: jax.Array,          # (G, ng) full coefficients (0 off the buffer)
    feat_active: jax.Array,   # (G, ng) bool current mask
    group_active: jax.Array,  # (G,) bool current mask
    ref_terms: jax.Array,     # (G,) dual-norm terms at resid_ref
    resid_ref: jax.Array,     # (n,) residual of the last full round
    lam_: jax.Array,
    backend: str = "xla",
    xt_rows: Optional[jax.Array] = None,
):
    """Certified gap + Theorem-1 round on the compacted active buffer.

    O(n * p_active) instead of O(n * p): the residual, the correlation, the
    dual norm, the gap, and the Theorem-1 tests all touch only the gathered
    groups.  Screened groups enter solely through the dual scaling
    (Eq. 15), where their eps-norm terms are *bounded* from the cached
    reference (:func:`repro.core.screening.screened_dual_bound`):

        term_g(resid) <= ref_terms_g + rate_g * ||resid - resid_ref||.

    ``valid`` is True iff that bound stays <= max(lambda, active-term max),
    in which case the full dual norm provably equals the active-term max
    and every returned quantity is EXACT (bit-level identical up to einsum
    reduction order) — not an approximation.  On ``valid=False`` the caller
    must discard the result and fall back to :func:`_screen_round`.

    Returns ``(gap, theta, group_keep, feat_keep, valid)`` with full-size
    (G,) / (G, ng) masks; groups outside the buffer come back False (they
    hold a permanent certificate and the caller's masks are intersected
    monotonically).

    ``backend="pallas"`` routes the correlation through the corr-only
    kernel over ``xt_rows`` (the active-row slice of the persistent
    transposed design, :func:`repro.kernels.ops.gather_transposed_rows`)
    and the per-group dual terms through the bisection kernel.
    """
    dtype = Xt.dtype
    tau = problem.tau
    Gb, ng = Xt.shape[0], Xt.shape[2]

    fmask_sub = (jnp.take(feat_active, take, axis=0).astype(dtype)
                 * gmask[:, None])
    bsub = jnp.take(beta, take, axis=0) * fmask_sub
    resid = problem.y - jnp.einsum("gnk,gk->n", Xt, bsub)
    shift = jnp.linalg.norm(resid - resid_ref)

    if backend == "pallas":
        corr = kops.screening_corr(xt_rows, resid)[: Gb * ng]
        corr = corr.reshape(Gb, ng)
    else:
        corr = jnp.einsum("gnk,n->gk", Xt, resid)
    corr = corr * gmask[:, None]          # padded slots alias group 0

    w_sub = jnp.take(problem.w, take)
    if backend == "pallas":
        terms_sub = kops.sgl_dual_norm_terms_fused(corr, tau, w_sub)
    else:
        terms_sub = sgl.sgl_dual_norm_terms(corr, tau, w_sub)
    gact_sub = jnp.take(group_active, take) & (gmask > 0)
    dual_active = jnp.max(jnp.where(gact_sub, terms_sub, 0.0))
    scale = jnp.maximum(lam_, dual_active)

    real_grp = jnp.any(problem.feat_mask, axis=-1)
    screened = real_grp & ~group_active
    bound = scr.screened_dual_bound(
        ref_terms, scr.screened_group_rate(problem), shift, screened
    )
    valid = bound <= scale

    theta = resid / scale
    # sgl.primal on the buffer: beta is exactly zero off it, so the
    # residual and the SGL norm restricted to the gathered groups ARE the
    # full primal; the dual is O(n) and reused verbatim.
    primal = (0.5 * jnp.sum(resid * resid)
              + lam_ * sgl.sgl_norm(bsub, tau, w_sub))
    gap = primal - sgl.dual(problem, theta, lam_)

    # Theorem-1 tests on the buffer: the SAME shared formulas as the full
    # round (screening.theorem1_tests), on the gathered slices.
    r = jnp.sqrt(2.0 * jnp.maximum(gap, 0.0)) / lam_
    corr_s = corr / scale
    fm_real_sub = (jnp.take(problem.feat_mask, take, axis=0)
                   & (gmask[:, None] > 0))
    xg = jnp.take(problem.Xnorm_grp, take)
    xc = jnp.take(problem.Xnorm_col, take, axis=0)
    g_keep_sub, f_keep_sub = scr.theorem1_tests(
        corr_s, r, xg, xc, w_sub, fm_real_sub, tau
    )
    g_keep_sub = g_keep_sub & gact_sub
    f_keep_sub = f_keep_sub & g_keep_sub[:, None] & fm_real_sub

    # Scatter back to full-size masks; padded slots carry False and .add
    # with int values keeps duplicate (aliased) indices harmless.
    G = problem.feat_mask.shape[0]
    g_keep = jnp.zeros((G,), jnp.int32).at[take].add(
        g_keep_sub.astype(jnp.int32)) > 0
    f_keep = jnp.zeros(problem.feat_mask.shape, jnp.int32).at[take].add(
        f_keep_sub.astype(jnp.int32)) > 0
    return gap, theta, g_keep, f_keep, valid


def screen_round(
    problem: SGLProblem,
    beta: jax.Array,
    lam_: float,
    lam_max: float = 0.0,
    rule="gap",
    backend: str = "auto",
    xt_pre: Optional[jax.Array] = None,
    loss="lsq",
) -> RoundResult:
    """Public resumable-round API: one certified gap + screening round.

    Returns a :class:`RoundResult` — a GAP-sphere certificate valid at
    ``lam_``.  Calling this at a *new* lambda with the *previous* lambda's
    ``beta`` is exactly the paper's sequential screening rule; the result
    can be fed to :func:`solve` as ``first_round`` so the solve starts on
    the reduced problem with zero duplicated work.

    ``rule``: a registered rule name or a :class:`repro.rules.ScreeningRule`
    object; unknown names fail fast here with the registered list (they
    used to fall silently into the no-screening branch of the round).
    ``rule="dst3"`` needs the true ``lam_max`` (its sphere divides by it).
    ``xt_pre``: persistent transposed design (Pallas backend only) — see
    :meth:`repro.core.session.SGLSession.screen`, which supplies it
    automatically.
    ``loss``: a registered :mod:`repro.losses` name or ``Loss`` object
    (default ``"lsq"``); rule x loss pairings the rule cannot prove fail
    fast here (``supported_losses``).
    """
    rule = resolve_rule(rule)
    loss = resolve_loss(loss)
    if loss.multi_output:
        raise ValueError(
            f"loss={loss.name!r} is multi-output (matrix-valued beta); "
            "the round skeleton supports single-output losses — use the "
            "repro.core.sgl.multitask_* helpers"
        )
    check_rule_loss(rule, loss)
    if rule.pre_screens:
        # Checked BEFORE needs_lam_max: this refusal is terminal, so a
        # static-rule caller must not first be told to pass lambda_max.
        # The static screen is applied once inside solve(), not per round;
        # _screen_round would return all-true masks that LOOK like a valid
        # certificate while screening nothing.
        raise ValueError(
            f"rule={rule.name!r} has no per-round certificate; use "
            "screening.static_sphere + screening.screen, or solve()"
        )
    if rule.needs_lam_max and not lam_max > 0.0:
        raise ValueError(
            f"rule={rule.name!r} requires lam_max > 0 (pass lambda_max)"
        )
    dtype = problem.X.dtype
    res, _resid, _terms = _screen_round(
        problem,
        jnp.asarray(beta, dtype),
        jnp.asarray(lam_, dtype),
        jnp.asarray(lam_max, dtype),
        rule,
        resolve_screen_backend(backend, dtype),
        xt_pre,
        loss=None if loss.name == "lsq" else loss,
    )
    return res


def _bucket(n: int, minimum: int = 8) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


@functools.partial(jax.jit,
                   static_argnames=("block_epochs", "max_blocks", "backend"))
def _inner_rounds(Xt, Lg, w, y, beta, feat_active, take, gmask, tau, lam_,
                  tol, block_epochs, max_blocks, backend="xla",
                  xt_rows=None):
    """Up to ``max_blocks`` blocks of ``block_epochs`` BCD epochs in ONE
    jitted call.

    Between blocks the *reduced-problem* duality gap (dual norm over the
    compacted buffer only) is checked for early exit.  This gap is exact
    for the reduced problem but may under-estimate the full certified gap,
    so it is used ONLY as a work heuristic — the caller always recomputes
    the full-problem gap (paper Eq. 15/Thm 2) before stopping or screening.
    Amortises the full X^T rho correlation and the host sync over
    ~max_blocks x block_epochs epochs instead of one block (see
    EXPERIMENTS.md §Perf).  The path engine runs with ``block_epochs=1`` so
    a warm-started lambda stops after exactly the passes it needs.

    ``backend="pallas"`` runs each epoch block through the fused
    :mod:`repro.kernels.bcd_epoch` mega-kernel (one launch per block,
    residual carried in VMEM) instead of the ``lax.scan`` over groups, and
    routes the between-block reduced-gap correlation through the Pallas
    corr kernel over ``xt_rows`` (the active-row slice of the persistent
    transposed design from
    :func:`repro.kernels.ops.gather_transposed_rows`) — previously the gap
    check always paid the XLA einsum even on TPU, and with
    ``block_epochs=1`` it runs after every single pass.

    ``take`` may contain padded slots aliasing group 0; the scatter uses a
    masked *delta* with .add so duplicate indices contribute zero and the
    real group-0 row is preserved.

    Returns ``(beta, k, gap, steps)``: ``k`` blocks ran, and ``steps``
    group steps in all (device int32s) — every slot of the buffer per
    epoch on the Pallas backend, the slots up to the last live chunk on
    XLA (:func:`_live_group_pass`).
    """
    dtype = beta.dtype
    Gb, ng = Xt.shape[0], Xt.shape[2]
    fmask = (jnp.take(feat_active, take, axis=0).astype(dtype)
             * gmask[:, None])
    bsub0 = jnp.take(beta, take, axis=0) * fmask
    slots = Gb if backend == "pallas" else _live_slots(Lg * gmask)
    resid0 = y - jnp.einsum("gnk,gk->n", Xt, bsub0)
    y2half = 0.5 * jnp.sum(y * y)

    def reduced_gap(bsub, resid):
        if backend == "pallas" and xt_rows is not None:
            corr = kops.screening_corr(xt_rows, resid)[: Gb * ng]
            corr = corr.reshape(Gb, ng) * fmask
        else:
            corr = jnp.einsum("gnk,n->gk", Xt, resid) * fmask
        dn = sgl.sgl_dual_norm(corr, tau, w)
        theta = resid / jnp.maximum(lam_, dn)
        primal = (0.5 * jnp.sum(resid * resid)
                  + lam_ * sgl.sgl_norm(bsub, tau, w))
        diff = theta - y / lam_
        dual = y2half - 0.5 * lam_ * lam_ * jnp.sum(diff * diff)
        return primal - dual

    def cond(c):
        bsub, resid, k, gap = c
        return (k < max_blocks) & (gap > tol)

    def body(c):
        bsub, resid, k, gap = c
        if backend == "pallas":
            bsub_b, resid_b = kops.bcd_epochs_fused(
                Xt, Lg * gmask, w, fmask[None], bsub[None], resid[None],
                tau, jnp.reshape(lam_, (1,)), block_epochs
            )
            bsub, resid = bsub_b[0], resid_b[0]
        else:
            bsub, resid = bcd_epochs(
                Xt, Lg * gmask, w, fmask, bsub, resid, tau, lam_,
                block_epochs
            )
        return bsub, resid, k + 1, reduced_gap(bsub, resid)

    bsub, resid, k, gap = jax.lax.while_loop(
        cond, body, (bsub0, resid0, jnp.zeros((), jnp.int32),
                     jnp.asarray(jnp.inf, dtype))
    )
    delta = (bsub - bsub0) * fmask
    return beta.at[take].add(delta), k, gap, k * block_epochs * slots


@functools.partial(
    jax.jit,
    static_argnames=("loss", "block_epochs", "max_blocks", "backend"))
def _inner_rounds_loss(Xt, Lg, w, y, beta, feat_active, take, gmask, tau,
                       lam_, tol, loss, block_epochs, max_blocks,
                       backend="xla", xt_rows=None):
    """Loss-generic twin of :func:`_inner_rounds`: blocked majorized BCD
    epochs + reduced-gap early exit for any single-output loss.

    The carry is the linear predictor ``z = X beta`` (the loss-defined
    state that replaces the lsq residual); between blocks the reduced gap
    is built from ``rho = -grad F(z)`` through the same Eq. 15 scaling and
    the loss's conjugate dual.  Exact for the reduced problem, heuristic
    for the full one — the caller always re-certifies with a full
    :func:`_screen_round` before stopping or screening, same contract as
    the lsq path.

    ``backend="pallas"`` with ``loss="logistic"`` routes each epoch block
    through the fused :func:`repro.kernels.ops.bcd_epochs_logistic_fused`
    mega-kernel (z carried in VMEM) and the reduced-gap correlation
    through the Pallas corr kernel; other losses fall back to the
    ``lax.scan`` epochs, which are the bit-parity reference either way.
    Returns ``(beta, k, gap, steps)`` as :func:`_inner_rounds` does.
    """
    dtype = beta.dtype
    Gb, ng = Xt.shape[0], Xt.shape[2]
    fmask = (jnp.take(feat_active, take, axis=0).astype(dtype)
             * gmask[:, None])
    bsub0 = jnp.take(beta, take, axis=0) * fmask
    fused = backend == "pallas" and loss.name == "logistic"
    slots = Gb if fused else _live_slots(Lg * gmask)
    # beta is exactly zero off the buffer, so this IS the full predictor.
    z0 = jnp.einsum("gnk,gk->n", Xt, bsub0)

    def reduced_gap(bsub, z):
        rho = loss.neg_grad(y, z)
        if backend == "pallas" and xt_rows is not None:
            corr = kops.screening_corr(xt_rows, rho)[: Gb * ng]
            corr = corr.reshape(Gb, ng) * fmask
        else:
            corr = jnp.einsum("gnk,n->gk", Xt, rho) * fmask
        dn = sgl.sgl_dual_norm(corr, tau, w)
        theta = rho / jnp.maximum(lam_, dn)
        primal = loss.value(y, z) + lam_ * sgl.sgl_norm(bsub, tau, w)
        return primal - loss.dual_obj(y, theta, lam_)

    def cond(c):
        bsub, z, k, gap = c
        return (k < max_blocks) & (gap > tol)

    def body(c):
        bsub, z, k, gap = c
        if fused:
            bsub_b, z_b = kops.bcd_epochs_logistic_fused(
                Xt, Lg * gmask, w, fmask[None], bsub[None], z[None],
                y, tau, jnp.reshape(lam_, (1,)), block_epochs
            )
            bsub, z = bsub_b[0], z_b[0]
        else:
            bsub, z = bcd_epochs_loss(
                Xt, Lg * gmask, w, fmask, bsub, z, tau, lam_, y,
                loss, block_epochs
            )
        return bsub, z, k + 1, reduced_gap(bsub, z)

    bsub, z, k, gap = jax.lax.while_loop(
        cond, body, (bsub0, z0, jnp.zeros((), jnp.int32),
                     jnp.asarray(jnp.inf, dtype))
    )
    delta = (bsub - bsub0) * fmask
    return beta.at[take].add(delta), k, gap, k * block_epochs * slots


def _gather_static(problem: SGLProblem, group_active):
    """Gather the active groups' design slices into a power-of-two padded
    buffer.  Depends only on the active-group set, so :class:`SolveCaches`
    caches the result between rounds — and between lambdas on a path — (the
    (n x p_active) copy of X is the expensive part); per-round masks are
    applied by the caller.

    Masked/padded groups are *not* zeroed in Xt: ``bcd_epochs`` masks their
    updates (feat_mask, live) so their columns never contribute, and does
    not visit the padded slots past the chunk holding the last active
    group (the active groups come first).
    """
    idx = np.nonzero(np.asarray(group_active))[0]
    Gb = _bucket(max(len(idx), 1))
    pad = Gb - len(idx)
    take = np.concatenate([idx, np.zeros(pad, np.int64)])
    gmask = np.concatenate([np.ones(len(idx)), np.zeros(pad)])

    take_j = jnp.asarray(take)
    Xt = jnp.transpose(jnp.take(problem.X, take_j, axis=1), (1, 0, 2))
    Lg = jnp.take(problem.Lg, take_j)
    w = jnp.take(problem.w, take_j)
    gmask_j = jnp.asarray(gmask, problem.X.dtype)
    return idx, take_j, Xt, Lg, w, gmask_j


# ----------------------------------------------------------------------------
# Outer driver
# ----------------------------------------------------------------------------

def solve(
    problem: SGLProblem,
    lam_: float,
    beta0: Optional[jax.Array] = None,
    tol: float = 1e-8,
    max_epochs: int = 10_000,
    f_ce: int = 10,
    rule="gap",
    lam_max: Optional[float] = None,
    compact: bool = True,
    inner_rounds: int = 5,
    check_every: Optional[int] = None,
    first_round: Optional[tuple] = None,
    caches: Optional[SolveCaches] = None,
    screen_backend: str = "auto",
    solver_backend: str = "auto",
) -> SolveResult:
    """Solve one SGL instance at regularisation ``lam_``.

    .. deprecated::
        Thin wrapper over the session API — loose kwargs map onto
        :class:`repro.core.session.SolverConfig` fields of the same names
        and the solve delegates to
        :meth:`repro.core.session.SGLSession.solve`.  Prefer::

            session = SGLSession(problem, SolverConfig(tol=1e-8))
            res = session.solve(lam_)

        A session additionally keeps a persistent transposed design for the
        Pallas-backed rounds and carries the gather cache across calls.

    ``rule``: a registered :mod:`repro.rules` name ({"gap", "static",
    "dynamic", "dst3", "none", "strong"}) or a
    :class:`repro.rules.ScreeningRule` object.
    ``tol`` is the duality-gap stopping threshold (paper uses 1e-8).
    ``inner_rounds``: how many f_ce-epoch blocks run inside one jitted
    call between certified (full-problem) gap/screening rounds; the inner
    early-exit uses the reduced-problem gap, so safety is unaffected.
    ``check_every``: epochs between reduced-gap early-exit checks inside
    the jitted inner loop (default ``f_ce``, i.e. one check per block; the
    path engine passes 1).  With ``compact=False`` the solver runs plain
    ``f_ce``-epoch blocks and ``inner_rounds``/``check_every`` are ignored.
    ``first_round``: a :class:`RoundResult` from :func:`screen_round`
    evaluated at (``beta0``, ``lam_``), consumed as the first certified
    round.  ``caches``: a :class:`SolveCaches` shared across calls.
    """
    if isinstance(check_every, str):
        raise ValueError(
            "check_every must be an int or None for solve(); "
            "'auto' scheduling exists only on solve_path()"
        )
    from .session import SGLSession, SolverConfig

    warnings.warn(
        "repro.core.solve() is deprecated; use "
        "SGLSession(problem, SolverConfig(...)).solve(lam_)",
        DeprecationWarning, stacklevel=2,
    )
    cfg = SolverConfig(
        tol=tol, max_epochs=max_epochs, f_ce=f_ce, rule=rule,
        compact=compact, inner_rounds=inner_rounds, check_every=check_every,
        screen_backend=screen_backend, solver_backend=solver_backend,
    )
    session = SGLSession(problem, cfg, caches=caches)
    return session.solve(
        lam_, beta0=beta0, first_round=first_round, lam_max=lam_max
    )


# ----------------------------------------------------------------------------
# Static-analysis hooks: expose the jitted entry points to the jaxpr lints
# (repro.analysis.registry is a leaf import — no cycle).  Each name pairs
# with a shape template in repro.analysis.entrypoints.
# ----------------------------------------------------------------------------

from ..analysis.registry import register_traceable  # noqa: E402
from .precision import one_minus

register_traceable("screen_round", _screen_round,
                   module=__name__, kind="jit")
register_traceable("screen_round_compact", _screen_round_compact,
                   module=__name__, kind="jit")
register_traceable("inner_rounds", _inner_rounds,
                   module=__name__, kind="jit")
register_traceable("bcd_epochs", bcd_epochs,
                   module=__name__, kind="jit")
register_traceable("inner_rounds_loss", _inner_rounds_loss,
                   module=__name__, kind="jit")
register_traceable("bcd_epochs_loss", bcd_epochs_loss,
                   module=__name__, kind="jit")
