"""Unified solver-session API: one front-end for single-lambda, path, and
distributed solves.

The paper's speed story is one algorithm — certified GAP rounds (Thm 2) +
Theorem-1 screening wrapped around an inner solver — and the journal
follow-up (Ndiaye et al. 2017) frames the rule as penalty- and
solver-agnostic.  :class:`SGLSession` is that framing in code: it owns the
problem, the resolved screening backend, a **persistent transposed design**
for the Pallas correlation kernels, and the cross-call gather caches, and
exposes the whole algorithm family through three methods:

* :meth:`SGLSession.screen` — one certified gap + Theorem-1 round
  (:class:`repro.core.solver.RoundResult`), the resumable-round primitive;
* :meth:`SGLSession.solve` — one regularisation level, warm-startable and
  certificate-seedable;
* :meth:`SGLSession.solve_path` — the sequential-screening lambda-path
  engine (paper Section 7.1).

Strategies
----------
``SGLSession(problem)`` runs the single-device ISTA-BC solver
(Algorithm 2, :mod:`repro.core.solver`).  ``SGLSession(problem,
mesh=mesh)`` swaps in the distributed FISTA strategy
(:mod:`repro.distributed.solver_dist`) behind the *same* methods: the
sequential rule threads :class:`RoundResult` certificates and warm starts
through the shard_map kernels, and consecutive path points whose certified
active sets coincide are solved in ONE batched-lambda FISTA run
(``fista_batch`` — arithmetic intensity scales with the batch).

Screening-rule strategies
-------------------------
``SolverConfig.rule`` is a pluggable :mod:`repro.rules` strategy: a
:class:`repro.rules.ScreeningRule` object (or a registered name — the
legacy-string shim, resolved at session construction so unknown names
fail fast with the registered list).  The certified round is a shared
sphere-test skeleton (:func:`repro.core.solver._screen_round`) that asks
the rule only for its sphere; safety metadata gates everything else —
``supports_sequential`` decides whether the path engine runs pre-solve
rounds, ``supports_compact`` gates the compacted rounds, ``pre_screens``
routes the static rule's one up-front screen, and ``is_safe=False``
(unsafe heuristics like ``StrongSequentialRule``) flags every round
(``RoundResult.safe``) and path (``PathResult.certificates_safe``) so
heuristic discards are never reported as zero-certificates.

Persistent transposed design
----------------------------
On the Pallas backend the certified round's hot correlation ``X^T resid``
needs the feature-major (p, n) layout; before this session existed, every
round materialised a fresh transposed copy of X (ROADMAP perf item).  The
session builds it once (:func:`repro.kernels.ops.prepare_transposed`) and
feeds it to every round of every solve of the whole path; the elimination
is *measured* (``kernels.ops.transpose_trace_count`` moves iff a round
traced an on-the-fly transpose) and surfaced per path as
``PathResult.n_rounds`` / ``n_transpose_copies`` for the benchmarks.

Compacted certified rounds
--------------------------
The certified round itself used to stay O(n p) per round no matter how many
groups held a permanent certificate.  With ``SolverConfig.compact_rounds``
(default True, ``rule="gap"`` + compacted buffers only) the driver runs
most rounds through :func:`repro.core.solver._screen_round_compact` on the
gathered (n, p_active) buffer: screened groups re-enter the round only via
the dual scaling (Eq. 15), and their per-group eps-norm terms are bounded
from the last full round's cached reference by

    term_g(resid) <= term_g(resid_ref) + ||X_g||_2/scale_g * ||resid - resid_ref||

(proof in :mod:`repro.core.screening`).  Fallback policy — a FULL round
runs instead whenever (1) the bound crosses max(lambda, active-term max),
i.e. the residual drifted too far from the reference (the full round
refreshes it), (2) ``full_round_every`` compact rounds ran since the last
full one, or (3) a compact round's gap reaches ``tol``: convergence is
always re-confirmed on the full problem, so the *reported* gap and
certificate of every solve (and of every lambda on a path) are
full-problem exact even though compact rounds are themselves exact when
their bound holds.  ``PathResult.n_compact_rounds`` / ``n_full_rounds`` /
``round_flops`` audit the split next to the transpose audit.

Fused BCD epochs and batched lambdas
------------------------------------
``SolverConfig.solver_backend`` (``"auto"``/``"xla"``/``"pallas"``, same
resolution policy as the screening backend) picks the inner-epoch engine on
the single-device strategy: ``"pallas"`` dispatches whole epoch blocks as
ONE fused :mod:`repro.kernels.bcd_epoch` launch — residual carried in VMEM
across the group loop, design streamed tile-by-tile — instead of the
``lax.scan`` over groups (kept as the XLA fallback and bit-parity
reference).  The kernel's lambda-batch grid axis also brings the
batched-lambda path optimisation to the single-device solver: consecutive
path points whose sequential certificates agree on the active groups solve
in one run (:meth:`SGLSession._solve_batch_bcd`), mirroring the mesh
strategy's ``fista_batch``.  Audited as
``PathResult.n_fused_epoch_launches`` / ``batched_lambdas`` (session
counters ``fused_epoch_launches`` / ``batched_lambdas``).

Migration from the legacy front-ends
------------------------------------
``solve(...)`` / ``solve_path(...)`` loose kwargs became
:class:`SolverConfig` fields with the same names and defaults (``tol``,
``max_epochs``, ``f_ce``, ``rule``, ``compact``, ``inner_rounds``,
``check_every``, ``screen_backend``, ``solver_backend``,
``warm_gap_factor``); per-call state
(``lam_``, ``beta0``, ``first_round``, ``lambdas``) stays on the method.
``solve_distributed(mesh, X, y, w, ...)`` raw arrays became
``SGLSession(problem_from_grouped(X, y, tau, w), mesh=mesh)``.  The legacy
functions survive as thin deprecated wrappers delegating here.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Union

import numpy as np
import jax
import jax.numpy as jnp

from . import screening as scr
from . import sgl
from .sgl import SGLProblem
from .solver import (
    RoundResult,
    SolveCaches,
    SolveResult,
    _bucket,
    _inner_rounds,
    _inner_rounds_loss,
    _screen_round,
    _screen_round_compact,
    bcd_epochs,
    bcd_epochs_loss,
    check_rule_loss,
    resolve_screen_backend,
    resolve_solver_backend,
)
from ..faults.errors import KernelLaunchError, NumericsError
from ..faults.inject import fire as _fire_fault
from ..kernels import ops as kops
from ..losses import Loss, resolve_loss
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..rules import ScreeningRule, resolve_rule


_M_GROUP_STEPS = obs_metrics.REGISTRY.counter(
    "solver.epoch_group_steps",
    help="Group steps run by the compacted epoch blocks (_inner_rounds)")
_M_GROUP_SLOTS = obs_metrics.REGISTRY.counter(
    "solver.epoch_group_slots",
    help="Buffer slots spanned by those epochs (Gb x epochs); the share "
         "not stepped is bucket padding the XLA scan skipped")


def _read(what: str):
    """A ``read`` span around one blocking device->host read."""
    return obs_trace.span("read").set("what", what)


__all__ = [
    "SolverConfig",
    "SGLSession",
    "PathResult",
    "lambda_grid",
]

_UNSET = object()


class _SolverConfigFields(NamedTuple):
    tol: float = 1e-8              # duality-gap stopping threshold
    max_epochs: int = 10_000       # BCD epochs (FISTA steps on a mesh)
    f_ce: int = 10                 # epochs between certified rounds
    rule: Union[str, ScreeningRule] = "gap"
                                   # screening strategy: a repro.rules
                                   #   ScreeningRule object, or a registered
                                   #   name (gap | static | dynamic | dst3 |
                                   #   none | strong) resolved through the
                                   #   registry (legacy-string shim; unknown
                                   #   names fail fast at session init with
                                   #   the registered list)
    compact: bool = True           # gather active groups into dense buffers
    inner_rounds: int = 5          # f_ce-blocks per jitted inner call
    check_every: Union[int, None, str] = "auto"  # reduced-gap exit cadence
    screen_backend: str = "auto"   # auto | xla | pallas
    warm_gap_factor: float = 1e3   # warm-lambda threshold for "auto"
    compact_rounds: bool = True    # run certified rounds on the compacted
                                   #   active buffer when provably exact
                                   #   (rule="gap" + compact buffers only);
                                   #   False restores full rounds everywhere
    full_round_every: int = 10     # certified rounds between forced full
                                   #   rounds (reference refresh); <= 0
                                   #   disables compact rounds outright
    solver_backend: str = "auto"   # auto | xla | pallas — backend for the
                                   #   inner BCD epochs: "pallas" fuses
                                   #   whole epoch blocks into ONE kernel
                                   #   launch (kernels/bcd_epoch.py, VMEM-
                                   #   resident residual, batched-lambda
                                   #   grid); "xla" keeps the lax.scan
                                   #   reference.  Single-device strategy
                                   #   only (the mesh strategy's FISTA
                                   #   kernels have their own dispatch).
    loss: Union[str, Loss] = "lsq"
                                   # data-fidelity strategy: a repro.losses
                                   #   Loss object or a registered name
                                   #   (lsq | logistic | ...), resolved
                                   #   through the registry at construction
                                   #   so unknown names fail fast with the
                                   #   registered list.  "lsq" is the
                                   #   paper's squared loss and keeps every
                                   #   historical code path bit-identical;
                                   #   other losses run full certified
                                   #   rounds (no compact rounds, no
                                   #   batched lambdas, no mesh strategy).


class SolverConfig(_SolverConfigFields):
    """Frozen bundle of every solver knob (formerly 13 loose kwargs).

    Field names match the legacy ``solve``/``solve_path`` keyword arguments
    one-to-one; anything not listed here (``lam_``, ``beta0``,
    ``first_round``, ``lambdas``, ``sequential``) is per-call state and
    lives on the session methods instead.

    Backend knobs are validated at *construction*: an unknown
    ``screen_backend``/``solver_backend`` raises here with the valid
    choices, instead of surfacing as a jit-time ``ValueError`` deep inside
    the first certified round (typos used to cost a full problem build +
    trace before failing).
    """

    __slots__ = ()

    _BACKENDS = ("auto", "xla", "pallas")

    def __new__(cls, *args, **kwargs):
        self = super().__new__(cls, *args, **kwargs)
        for knob in ("screen_backend", "solver_backend"):
            val = getattr(self, knob)
            if val not in cls._BACKENDS:
                raise ValueError(
                    f"unknown {knob.replace('_', ' ')}: {val!r} "
                    f"(choose one of {'|'.join(cls._BACKENDS)})"
                )
        # Loss names are validated here too (same fail-fast contract as the
        # backend knobs): resolve_loss raises with the registered list on
        # an unknown name, instead of deep inside the first round.
        resolve_loss(self.loss)
        return self

    def cache_token(self) -> tuple:
        """Hashable identity of every compile-relevant solver knob.

        The serving layer (:mod:`repro.serve`) keys its session/compile
        cache on this token together with the problem digest: two configs
        with equal tokens drive identical jitted programs, so a cached
        session can serve either without retracing.  ``rule`` is resolved
        through the :mod:`repro.rules` registry and keyed by its ``repr``
        (rules are frozen dataclasses, so the repr carries every
        parameter) — a registered name and the equivalent rule object
        produce the same token.
        """
        d = self._asdict()
        d["rule"] = repr(resolve_rule(d["rule"]))
        # Same treatment for the loss strategy: losses are frozen
        # dataclasses, so the repr is a stable parameter-carrying identity
        # — tenants solving different data fidelities can NEVER share a
        # cached session, path, or warm-start hint.
        d["loss"] = repr(resolve_loss(d["loss"]))
        return tuple(sorted(d.items()))


def lambda_grid(lam_max: float, T: int = 100, delta: float = 3.0) -> np.ndarray:
    """lambda_t = lambda_max * 10^(-delta t / (T-1)), t = 0..T-1 (paper §7.1)."""
    t = np.arange(T)
    return lam_max * 10.0 ** (-delta * t / max(T - 1, 1))


class PathResult(NamedTuple):
    """Dense path outputs; leading axis is the lambda grid (length T)."""

    lambdas: np.ndarray            # (T,)
    betas: np.ndarray              # (T, G, ng) coefficients
    gaps: np.ndarray               # (T,) final certified duality gaps
    epochs: np.ndarray             # (T,) int, BCD passes / FISTA steps
    group_active_frac: np.ndarray  # (T,)
    feat_active_frac: np.ndarray   # (T,)
    group_active: np.ndarray       # (T, G) bool, certified active masks
                                   #   (solver-final intersected with the
                                   #   sequential certificate).  False is a
                                   #   certificate of zero at the optimum,
                                   #   NOT a support indicator of betas[t]:
                                   #   a lambda converged on its sequential
                                   #   round keeps beta un-zeroed there.
    feat_active: np.ndarray        # (T, G, ng) bool, same semantics
    seq_screened: np.ndarray       # (T,) int, groups the sequential round
                                   #   certified inactive before any epoch
    dyn_screened: np.ndarray       # (T,) int, further groups screened out
                                   #   during the solve (dynamic rule)
    n_gathers: int                 # design re-gathers across the whole path
    results: list                  # per-lambda SolveResult (keep_results)
    n_rounds: int = 0              # certified rounds dispatched on the path
    n_transpose_copies: int = 0    # rounds that executed a jitted program
                                   #   which materialises an on-the-fly
                                   #   (p, n) transposed copy of X, measured
                                   #   via kernels.ops.transpose_trace_count
                                   #   — 0 when the session's persistent
                                   #   transposed design reached every
                                   #   Pallas round (and trivially 0 on the
                                   #   XLA backend, where no copy is ever at
                                   #   stake)
    n_compact_rounds: int = 0      # certified rounds run on the compacted
                                   #   active buffer (O(n p_active))
    n_full_rounds: int = 0         # certified rounds run on the full
                                   #   problem (every converged round, the
                                   #   sequential rounds, the forced
                                   #   full_round_every refreshes, and any
                                   #   bound-crossing fallbacks)
    round_flops: float = 0.0       # estimated FLOPs spent in certified
                                   #   rounds (~4*n*p_buffer per round,
                                   #   incl. discarded fallback attempts);
                                   #   full-round-only engines spend
                                   #   (n_compact+n_full) * 4*n*p
    n_fused_epoch_launches: int = 0  # epoch blocks dispatched as ONE fused
                                   #   Pallas launch (solver_backend=
                                   #   "pallas"); the lax.scan path would
                                   #   have paid O(G) scan steps per block.
                                   #   0 on the XLA solver backend and on
                                   #   the mesh strategy.
    batched_lambdas: int = 0       # path points solved through a
                                   #   batched-lambda run: the fused BCD
                                   #   kernel's lambda-batch grid axis on
                                   #   the single-device strategy, the
                                   #   fista_batch kernel on the mesh —
                                   #   consecutive lambdas whose sequential
                                   #   certificates agreed on the active
                                   #   groups.  0 when no batching engaged.
    n_group_steps: int = 0         # group steps run by the compacted
                                   #   epoch blocks (_inner_rounds); 0
                                   #   where epochs run elsewhere
                                   #   (compact=False, a batched-lambda
                                   #   run, the mesh strategy)
    n_group_slots: int = 0         # buffer slots those epochs spanned
                                   #   (Gb x epochs); the XLA scan skips
                                   #   the padding past the last live
                                   #   chunk, so steps <= slots
    rule_name: str = "gap"         # registered name of the screening rule
                                   #   that produced this path
    certificates_safe: bool = True # the group/feat_active masks are safe
                                   #   zero-certificates (ScreeningRule.
                                   #   is_safe).  False for unsafe rules
                                   #   (e.g. "strong"): the masks then only
                                   #   record what the heuristic discarded
                                   #   — they certify NOTHING, and Fig. 3
                                   #   style comparisons must treat them as
                                   #   potentially erroneous.
    degraded: str = ""             # "" = full path; "deadline" |
                                   #   "epoch_budget" = a SolveBudget
                                   #   tripped and the arrays hold only the
                                   #   prefix of lambdas actually solved —
                                   #   every entry still carries its honest
                                   #   certified full-problem gap.


@functools.partial(jax.jit, static_argnames=("backend",))
def _batch_reduced_gaps(Xt, fmask_b, bsub, resid, w, y, tau, lam_b,
                        backend="xla", xt_rows=None):
    """Per-lambda reduced-problem duality gaps on a shared batch buffer.

    The jitted batched twin of ``_inner_rounds``' early-exit heuristic —
    one correlation + vmapped norms per epoch block instead of per-lambda
    eager dispatches.  Work scheduling only; never reported (convergence
    is always confirmed by a full certified round).

    ``backend="pallas"`` routes the correlation through the batch-vmapped
    corr-only Pallas kernel (:func:`repro.kernels.ops.
    screening_corr_batched`) over ``xt_rows`` — the active-row slice of
    the persistent transposed design shared with the compact rounds —
    instead of the XLA einsum (previously the batched driver always paid
    the einsum even on TPU; PR 4 leftover).
    """
    if backend == "pallas" and xt_rows is not None:
        B = resid.shape[0]
        Gb, ng = Xt.shape[0], Xt.shape[2]
        corr = kops.screening_corr_batched(xt_rows, resid)[:, : Gb * ng]
        corr = corr.reshape(B, Gb, ng) * fmask_b
    else:
        corr = jnp.einsum("gnk,bn->bgk", Xt, resid) * fmask_b
    dn = jax.vmap(sgl.sgl_dual_norm, in_axes=(0, None, None))(corr, tau, w)
    theta = resid / jnp.maximum(lam_b, dn)[:, None]
    primal = (0.5 * jnp.sum(resid * resid, axis=1)
              + lam_b * jax.vmap(sgl.sgl_norm,
                                 in_axes=(0, None, None))(bsub, tau, w))
    diff = theta - y[None] / lam_b[:, None]
    dual = (0.5 * jnp.sum(y * y)
            - 0.5 * lam_b * lam_b * jnp.sum(diff * diff, axis=1))
    return primal - dual


def _global_lipschitz(problem: SGLProblem, n_iter: int = 150) -> float:
    """||X||_2^2 *estimate* via power iteration, +5% margin.

    NOT a certified upper bound — the Rayleigh quotient converges to the
    top eigenvalue from below, and a spectrum with a near-tied second
    singular value can leave the estimate a few percent short.  The FISTA
    drivers therefore back any auto-estimated constant with a divergence
    safeguard (gap growing while the active set is unchanged => double L
    and restart momentum), so an under-estimate costs speed, never
    correctness.  Callers with the exact constant should pass ``L=``.
    """
    X, mask = problem.X, problem.feat_mask
    dtype = X.dtype
    v0 = jnp.where(mask, 1.0, 0.0).astype(dtype)
    v0 = v0 * (1.0 + 1e-3 * jnp.arange(X.shape[2], dtype=dtype)[None, :])
    v0 = v0 / jnp.maximum(jnp.linalg.norm(v0), 1e-30)

    def body(_, v):
        u = jnp.einsum("ngk,gk->n", X, v)
        w = jnp.einsum("ngk,n->gk", X, u)
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)

    v = jax.lax.fori_loop(0, n_iter, body, v0)
    u = jnp.einsum("ngk,gk->n", X, v)
    return float(jnp.sum(u * u)) * 1.05


def _fire_epoch_launch_fault() -> None:
    """Chaos hook for the fused epoch-kernel dispatch sites."""
    for s in _fire_fault("kernels.epochs"):
        if s.kind == "raise":
            raise KernelLaunchError("injected epoch-kernel launch failure")


class SGLSession:
    """Stateful front-end over one SGL problem (see module docstring).

    Parameters
    ----------
    problem : SGLProblem
    config : SolverConfig, optional
    mesh : jax.sharding.Mesh, optional
        When given, the distributed FISTA strategy replaces the local
        ISTA-BC solver behind the same ``screen``/``solve``/``solve_path``
        methods.
    multi_pod : bool
        Mesh has the leading "pod" axis (distributed strategy only).
    L : float, optional
        Global Lipschitz constant ||X||_2^2 for FISTA; estimated by power
        iteration when omitted (distributed strategy only).
    caches : SolveCaches, optional
        Pre-existing gather caches to adopt (the legacy ``solve`` wrapper
        passes its ``caches=`` argument through here).
    xt_pre : jax.Array, optional
        A pre-built persistent transposed design to adopt instead of
        building one lazily — the serving layer shares ONE
        :func:`repro.kernels.ops.prepare_transposed` copy across every
        session over the same design (perturbed-y tenants).  Must have
        exactly the padded (p_pad, n_pad) layout ``prepare_transposed``
        produces for this problem's shape; validated at construction.
    """

    def __init__(
        self,
        problem: SGLProblem,
        config: Optional[SolverConfig] = None,
        *,
        mesh=None,
        multi_pod: bool = False,
        L: Optional[float] = None,
        caches: Optional[SolveCaches] = None,
        xt_pre: Optional[jax.Array] = None,
    ) -> None:
        self.problem = problem
        self.config = config if config is not None else SolverConfig()
        self.caches = caches if caches is not None else SolveCaches()
        # Screening strategy: SolverConfig.rule may be a ScreeningRule
        # object or a legacy string name — resolved through the
        # repro.rules registry here so an unknown name fails at session
        # construction (with the registered list), never inside a round.
        self.rule = resolve_rule(self.config.rule)
        # Data-fidelity strategy, resolved and gated eagerly (same policy):
        # an unsupported rule x loss pairing fails at construction with the
        # rule's declared support list, never as a silently-unsafe screen.
        self.loss = resolve_loss(self.config.loss)
        if self.loss.multi_output:
            raise ValueError(
                f"loss={self.loss.name!r} is multi-output; SGLSession "
                "solves single-output problems — use the "
                "repro.core.sgl.multitask_* helpers for the multi-task "
                "screening math"
            )
        check_rule_loss(self.rule, self.loss)
        self.backend = resolve_screen_backend(self.config.screen_backend,
                                              problem.X.dtype)
        # Inner-epoch backend (single-device BCD strategy): "pallas" runs
        # whole epoch blocks through the fused kernels/bcd_epoch.py launch,
        # "xla" keeps the lax.scan reference.  Resolved eagerly so an
        # invalid knob fails at session construction, like screen_backend.
        self.solver_backend = resolve_solver_backend(
            self.config.solver_backend, problem.X.dtype
        )
        self.mesh = mesh
        # Auditable round accounting: every certified round dispatched
        # through this session.  Whether any of those rounds had to build a
        # per-call (p, n) transposed copy of X is *measured*, not assumed:
        # kernels.ops.transpose_trace_count() moves iff a jitted round
        # actually traced an on-the-fly transpose, and solve_path converts
        # its delta into PathResult.n_transpose_copies.
        self.rounds = 0
        # Compact-round audit: rounds run on the compacted active buffer vs
        # the full problem, attempts discarded because the screened-group
        # bound crossed the active max, and the estimated FLOPs actually
        # spent in rounds (~4 n p_buffer each, fallback attempts included).
        self.compact_rounds = 0
        self.full_rounds = 0
        self.compact_fallbacks = 0
        self.round_flops = 0.0
        self._rounds_since_full = 0
        # Lambdas solved through a batched-lambda run: the fused BCD
        # kernel's lambda-batch grid axis (single-device Pallas strategy)
        # or the fista_batch kernel (mesh strategy) — path points whose
        # sequential certificates agreed on the active groups.
        self.batched_lambdas = 0
        # Epoch blocks dispatched as ONE fused Pallas launch instead of an
        # O(G) lax.scan (solver_backend="pallas" only).
        self.fused_epoch_launches = 0
        # Group steps the compacted epoch blocks ran, and the buffer slots
        # they spanned (Gb per epoch): the XLA scan skips the slots past
        # the last live chunk.
        self.epoch_group_steps = 0
        self.epoch_group_slots = 0
        # Fault-tolerance accounting + per-request budget (repro.faults):
        # certified rounds discarded for a non-finite gap (the solve loop
        # rewinds and re-runs them), pallas→reference kernel demotions
        # after failed launches, and the optional SolveBudget the serving
        # layer attaches for the duration of one request.
        self.nonfinite_rounds = 0
        self.kernel_demotions = 0
        self.budget = None
        if xt_pre is not None:
            p = problem.G * problem.ng
            bp, bn = kops._corr_blocks(p, problem.n)
            expect = (p + (-p) % bp, problem.n + (-problem.n) % bn)
            if tuple(xt_pre.shape) != expect:
                raise ValueError(
                    f"adopted xt_pre has shape {tuple(xt_pre.shape)}; "
                    f"prepare_transposed would produce {expect} for this "
                    f"problem ((n, p) = ({problem.n}, {p}))"
                )
        self._xt_pre: Optional[jax.Array] = xt_pre
        self._lam_max: Optional[float] = None
        if mesh is not None and self.rule.name != "gap":
            # The sharded screen kernel computes GAP-sphere certificates
            # only; accepting another rule here would silently hand back
            # gap-rule results under a different name.
            raise ValueError(
                "the distributed strategy implements rule='gap' only; "
                f"got rule={self.rule.name!r}"
            )
        if mesh is not None and self.loss.name != "lsq":
            # The shard_map FISTA/screen kernels hard-code the squared-loss
            # residual and dual; accepting another loss here would silently
            # solve the wrong problem on the mesh.
            raise ValueError(
                "the distributed strategy implements loss='lsq' only; "
                f"got loss={self.loss.name!r}"
            )
        self._dist = _DistStrategy(self, mesh, multi_pod=multi_pod, L=L) \
            if mesh is not None else None

    # -- lazily-built shared state -----------------------------------------

    @property
    def lam_max(self) -> float:
        """lambda_max = Omega^D(X^T rho_0), computed once per session
        (rho_0 = -grad F(0): y for the squared loss, y - 1/2 logistic)."""
        if self._lam_max is None:
            if self.loss.name == "lsq":
                self._lam_max = float(sgl.lambda_max(self.problem))
            else:
                self._lam_max = float(
                    sgl.lambda_max_loss(self.problem, self.loss)
                )
        return self._lam_max

    @property
    def xt_pre(self) -> Optional[jax.Array]:
        """Persistent transposed design for the Pallas correlation kernel
        (None when neither the screening rounds nor the inner reduced-gap
        checks run on Pallas — plain XLA einsums handle layout natively).
        The Pallas *solver* backend needs it too: ``_inner_rounds`` feeds
        its between-block gap correlation from the active-row slice."""
        if self.backend != "pallas" and self.solver_backend != "pallas":
            return None
        if self._xt_pre is None:
            self._xt_pre = kops.prepare_transposed(self.problem.X)
        return self._xt_pre

    def _certified_round(self, beta, lam_j, lam_max_j, rule,
                         caches: Optional[SolveCaches] = None) -> RoundResult:
        """One FULL certified round; refreshes the compact-round reference
        (residual + per-group dual-norm terms) on ``caches`` — but only
        when the round's gap is finite.  A corrupted round must never
        install its residual as the compact-round reference: the previous
        full round's reference stays cached, and it remains a valid bound
        anchor for later compact rounds.

        Fault sites: ``core.round`` (numeric corruption of this round's
        outputs, stalls), ``kernels.screen`` (Pallas launch failure — the
        session demotes itself to the XLA reference backend, retries the
        round once, and counts the demotion; pallas/XLA bit-parity keeps
        the retried round's outputs identical).
        """
        caches = self.caches if caches is None else caches
        problem = self.problem
        specs = _fire_fault("core.round")   # stall kinds sleep in fire()
        self.rounds += 1
        self.full_rounds += 1
        self._rounds_since_full = 0
        self.round_flops += 4.0 * problem.n * problem.G * problem.ng
        # loss=None for lsq keeps the legacy jit cache key (shared with
        # every pre-loss call site); non-lsq rounds screen from the
        # generalized residual rho = -grad F(X beta).
        loss_arg = None if self.loss.name == "lsq" else self.loss
        with obs_trace.span("round") as _sp:
            _sp.set("compact", False)
            try:
                for s in _fire_fault("kernels.screen"):
                    if s.kind == "raise":
                        raise KernelLaunchError(
                            "injected screening-kernel launch failure"
                        )
                res, resid, terms = _screen_round(
                    problem, beta, lam_j, lam_max_j, rule, self.backend,
                    self.xt_pre, loss=loss_arg,
                )
            except KernelLaunchError:
                if self.backend != "pallas":
                    raise
                # Failed Pallas launch: demote the session to the XLA
                # reference path and retry ONCE.  Bit-parity between the
                # backends keeps the retried round's outputs identical; the
                # demotion is counted so a degraded node stays visible in the
                # fused-launch audit.  Only a launch failure demotes: a
                # compiler refusal surfaces instead of running XLA under a
                # Pallas label.
                self.backend = "xla"
                self.kernel_demotions += 1
                kops.note_kernel_demotion()
                res, resid, terms = _screen_round(
                    problem, beta, lam_j, lam_max_j, rule, "xla", None,
                    loss=loss_arg,
                )
        for s in specs:
            if s.kind in ("nan", "inf"):
                bad = float("nan") if s.kind == "nan" else float("inf")
                field = s.field or "theta"
                if field == "resid":
                    resid = resid * bad
                elif field == "corr":
                    terms = terms * bad
                else:
                    res = res._replace(theta=res.theta * bad)
                # Real corruption in resid/corr/theta propagates into the
                # gap through the same dataflow; mirror that so the gap
                # stays the universal corruption detector.
                res = res._replace(gap=res.gap * bad)
        with _read("gap"):
            finite = np.isfinite(float(res.gap))
        if finite:
            caches.set_refs(problem, resid, terms)
        else:
            self.nonfinite_rounds += 1
        return res

    def _demote_solver_backend(self) -> None:
        """A fused epoch-kernel launch failed: fall back to the lax.scan
        reference path for the rest of the session.  Bit-parity between
        the paths keeps results identical; the demotion is counted so the
        degraded throughput stays visible in the fused-launch audit."""
        self.solver_backend = "xla"
        self.kernel_demotions += 1
        kops.note_kernel_demotion()

    def _compact_round(self, beta, lam_j, group_active, feat_active,
                       caches: SolveCaches) -> Optional[RoundResult]:
        """Certified round on the compacted active buffer, or None.

        Returns None — the caller must fall back to a full round — when no
        reference state is cached yet or when the screened-group dual-norm
        bound crossed max(lambda, active max) (the residual drifted too far
        from the last full round's reference; the fallback refreshes it).
        A non-None result is *exact* (see
        :func:`repro.core.solver._screen_round_compact`).
        """
        if caches.resid_ref is None or caches.ref_terms is None:
            return None
        problem = self.problem
        _, take, Xt, _, _, gmask = caches.gather(problem, group_active)
        xt_rows = None
        if self.backend == "pallas":
            xt_rows = caches.gather_xt_rows(problem, group_active,
                                            self.xt_pre)
        dtype = problem.X.dtype
        with obs_trace.span("round") as _sp:
            _sp.set("compact", True)
            gap, theta, g_keep, f_keep, valid = _screen_round_compact(
                problem, Xt, take, gmask,
                jnp.asarray(beta, dtype),
                jnp.asarray(feat_active),
                jnp.asarray(group_active),
                caches.ref_terms, caches.resid_ref, lam_j,
                self.backend, xt_rows,
            )
        # Attempt cost is spent either way (honest FLOP accounting).
        self.round_flops += 4.0 * problem.n * Xt.shape[0] * problem.ng
        with _read("valid"):
            valid = bool(valid)
        if not valid:
            self.compact_fallbacks += 1
            return None
        self.rounds += 1
        self.compact_rounds += 1
        self._rounds_since_full += 1
        # Compact rounds only run under the (safe) gap rule — see
        # supports_compact — but thread the metadata rather than claim it.
        return RoundResult(gap, theta, g_keep, f_keep, compact=True,
                           safe=self.rule.is_safe)

    # -- the three front-end methods ---------------------------------------

    def screen(self, lam_: float, beta=None,
               rule: Union[str, ScreeningRule, None] = None) -> RoundResult:
        """One certified gap + Theorem-1 screening round at ``lam_``.

        Called at a *new* lambda with the *previous* lambda's ``beta`` this
        is the paper's sequential rule; feed the result to :meth:`solve` as
        ``first_round``.  ``beta`` defaults to zeros (the cold start).
        ``rule``: per-call override — a :class:`repro.rules.ScreeningRule`
        or a registered name (unknown names fail fast with the registered
        list).  Rounds from an unsafe rule come back flagged
        ``safe=False``: heuristic discards, never zero-certificates.
        """
        rule = self.rule if rule is None else resolve_rule(rule)
        if rule is not self.rule:
            # Per-call overrides get the same rule x loss gate as the
            # session rule did at construction.
            check_rule_loss(rule, self.loss)
        problem = self.problem
        dtype = problem.X.dtype
        if beta is None:
            beta = jnp.zeros((problem.G, problem.ng), dtype)
        if self._dist is not None:
            if rule.name != "gap":
                raise ValueError(
                    "the distributed strategy implements rule='gap' only; "
                    f"got rule={rule.name!r}"
                )
            return self._dist.screen(lam_, beta)
        if rule.pre_screens:
            raise ValueError(
                f"rule={rule.name!r} has no per-round certificate; use "
                "screening.static_sphere + screening.screen, or solve()"
            )
        return self._certified_round(
            jnp.asarray(beta, dtype),
            jnp.asarray(lam_, dtype),
            jnp.asarray(self.lam_max, dtype),
            rule,
        )

    def solve(
        self,
        lam_: float,
        beta0=None,
        *,
        first_round: Optional[RoundResult] = None,
        lam_max: Optional[float] = None,
        check_every=_UNSET,
        caches: Optional[SolveCaches] = None,
    ) -> SolveResult:
        """Solve one SGL instance at regularisation ``lam_``.

        All solver knobs come from ``self.config``; per-call state:

        * ``beta0`` — warm start (required alongside ``first_round``);
        * ``first_round`` — a :class:`RoundResult` evaluated at
          (``beta0``, ``lam_``), consumed instead of recomputing round 1;
        * ``lam_max`` — the true lambda_max when already known (path);
        * ``check_every`` — per-call override of the config cadence
          ("auto" resolves from the ``first_round`` warm gap here);
        * ``caches`` — per-call gather-cache override (the naive path mode
          uses a throwaway instance; default is the session cache).
        """
        if self._dist is not None:
            return self._dist.solve(lam_, beta0=beta0,
                                    first_round=first_round)
        cfg = self.config
        problem = self.problem
        rule = self.rule
        tol, max_epochs, f_ce = cfg.tol, cfg.max_epochs, cfg.f_ce
        if first_round is not None and rule.pre_screens:
            # The pre-solve screen re-masks (and zeroes parts of) beta0
            # before the loop, so an injected certificate evaluated at the
            # original beta0 would no longer certify the beta actually
            # being solved.
            raise ValueError(
                "first_round certifies beta0 as passed; it cannot be "
                f"combined with rule={rule.name!r}"
            )
        if first_round is not None and beta0 is None:
            # Without beta0 the solve starts from zeros, which the injected
            # certificate was (almost certainly) not evaluated at — if its
            # gap were <= tol the zeros would be returned as "converged".
            raise ValueError(
                "first_round requires the beta0 it was evaluated at"
            )
        if first_round is not None and not isinstance(first_round,
                                                      RoundResult):
            first_round = RoundResult(*first_round)
        if (first_round is not None and rule.is_safe
                and not bool(first_round.safe)):
            # An unsafe rule's round carries heuristic discards; adopting
            # them here would apply them monotonically and report them
            # under this session's safe rule as zero-certificates —
            # exactly what the safe=False flag exists to prevent.  (An
            # unsafe-rule session injecting its own flagged rounds is
            # fine: its results are flagged certificates_safe=False.)
            raise ValueError(
                "first_round was produced by an unsafe rule (safe=False); "
                f"refusing to adopt its masks under safe rule "
                f"{rule.name!r}"
            )
        caches = self.caches if caches is None else caches

        ce = cfg.check_every if check_every is _UNSET else check_every
        if isinstance(ce, str):
            if ce != "auto":
                raise ValueError(f"unknown check_every: {ce!r}")
            # Warmness read off the injected certificate: a lambda whose
            # warm-start gap is already near tol stops within a handful of
            # passes, so per-epoch early-exit checks beat the f_ce floor.
            warm = (first_round is not None
                    and float(first_round.gap) <= cfg.warm_gap_factor * tol)
            ce = 1 if warm else None

        G, ng = problem.G, problem.ng
        dtype = problem.X.dtype
        beta = (jnp.zeros((G, ng), dtype) if beta0 is None
                else jnp.asarray(beta0, dtype))
        lam_j = jnp.asarray(lam_, dtype)
        check = f_ce if ce is None else max(1, int(ce))
        # Never exceed the certified-round cadence, and keep degenerate
        # inputs (f_ce or inner_rounds <= 0) from collapsing the block size.
        check = max(1, min(check, f_ce * cfg.inner_rounds))
        max_blocks = max(1, (f_ce * cfg.inner_rounds) // check)

        if lam_max is None:
            lam_max = self.lam_max           # session-cached; the legacy
                                             # stateless solve() recomputed
                                             # this O(n p) dual norm per call

        with _read("problem"):
            group_active = np.array(jnp.any(problem.feat_mask, axis=-1))
            feat_active = np.array(problem.feat_mask)

        # Pre-screening rules (static sphere) screen once, up front —
        # through the same backend-routed Theorem-1 tests as every round,
        # so the static rule's one correlation also runs on the Pallas
        # kernel (fed from the persistent transposed design) on TPU.
        if rule.pre_screens:
            pre = rule.pre_solve_sphere(
                problem, lam_j, jnp.asarray(lam_max, dtype)
            )
            res = scr.screen(problem, scr.Sphere(*pre),
                             backend=self.backend, xt_pre=self.xt_pre)
            group_active &= np.asarray(res.group_active)
            feat_active &= np.asarray(res.feat_active)
            beta = beta * jnp.asarray(feat_active, dtype)

        gap_history: list = []
        active_history: list = []
        epochs_done = 0
        lsq = self.loss.name == "lsq"
        # Placeholder dual point (overwritten by the first certified
        # round); lam_max is always known here (cached on the session).
        # Generic losses scale rho_0 = -grad F(0) the same way (feasible
        # at beta=0 by the lam_max definition).
        if lsq:
            theta = problem.y / max(float(lam_), float(lam_max))
        else:
            theta = (self.loss.lam_max_rho(problem.y)
                     / max(float(lam_), float(lam_max)))
        gap = jnp.inf
        round_res = first_round
        lam_max_j = jnp.asarray(lam_max, dtype)
        with _read("problem"):
            n_real_groups = int(np.asarray(
                jnp.any(problem.feat_mask, axis=-1)).sum())
        # Non-compact branch state, hoisted out of the round loop: ONE
        # transposed design for the whole solve and a carried residual —
        # the loop used to re-materialise a fresh (G, n, ng) copy of X and
        # recompute the full residual einsum every certified round.
        # Generic losses carry the linear predictor z = X beta instead
        # (the majorized-BCD state; rho = -grad F(z) is derived per group).
        Xt_full = None
        resid_nc = None
        z_nc = None
        # Fault-tolerance state: consecutive non-finite certified rounds
        # (cap 3 -> typed NumericsError), the best finite iterate to
        # rewind to when beta itself is corrupted, and the budget-trip
        # reason (threads into SolveResult.degraded).
        nonfinite_run = 0
        best_gap: Optional[float] = None
        best_beta = None
        degraded: Optional[str] = None

        while epochs_done < max_epochs:
            # ---- fused gap + screening round (paper does this every f_ce
            # passes on the full problem; here it runs on the compacted
            # active buffer whenever the screened-group bound proves that
            # exact — see _compact_round).  The first round may be injected
            # by the path engine (sequential screening). ----
            if round_res is None:
                # A compact round only pays when the gathered buffer is
                # smaller than the problem: with power-of-two buckets a
                # barely-screened active set rounds up PAST the real group
                # count (e.g. 130/200 active -> bucket 256), where the
                # "compacted" buffer would cost more than the full round it
                # replaces — those rounds go full directly.
                n_act = int(group_active.sum())
                # Compact rounds are lsq-only: the screened-group bound is
                # proved against the quadratic dual's reference residual
                # (repro.core.screening) — generic losses run every round
                # full-problem.
                if (lsq and rule.supports_compact and cfg.compact
                        and cfg.compact_rounds
                        and self._rounds_since_full < cfg.full_round_every
                        and 0 < n_act
                        and _bucket(n_act) < n_real_groups):
                    round_res = self._compact_round(
                        beta, lam_j, group_active, feat_active, caches
                    )
                if round_res is None:
                    round_res = self._certified_round(
                        beta, lam_j, lam_max_j, rule, caches=caches
                    )
                    if not cfg.compact and lsq:
                        # The full round just recomputed y - X beta exactly
                        # (stored as the compact-round reference): adopt it
                        # so the carried residual's incremental drift is
                        # reset every full round, matching the pre-hoist
                        # per-round recomputation.  Copied because
                        # bcd_epochs donates its residual buffer, which
                        # would otherwise invalidate the cached reference.
                        # Gated on round finiteness: a corrupted round left
                        # the PREVIOUS full round's reference cached, which
                        # no longer equals y - X beta for the current beta.
                        if np.isfinite(float(round_res.gap)):
                            resid_nc = caches.resid_ref.copy()
                    elif not cfg.compact:
                        # Generic losses: the full round's reference is
                        # rho, not z — drop the carried predictor so it is
                        # recomputed from beta (same drift-reset cadence).
                        z_nc = None
            if bool(round_res.compact):
                # The REPORTED gap/certificate must always be full-problem
                # exact: re-confirm an (exact, but buffer-computed)
                # compact-round convergence with a full round before
                # stopping.  If the full gap disagrees (> tol), the loop
                # simply continues from the full round.
                with _read("gap"):
                    compact_done = float(round_res.gap) <= tol
                if compact_done:
                    round_res = self._certified_round(
                        beta, lam_j, lam_max_j, rule, caches=caches
                    )
            gap_r, theta_r = round_res.gap, round_res.theta
            g_act, f_act = round_res.group_active, round_res.feat_active
            round_res = None
            gap_history.append((epochs_done, float(gap_r)))

            if not np.isfinite(float(gap_r)):
                # Corrupted round: NEVER adopt its masks/theta (an all-False
                # NaN-comparison mask would erase the active set and the
                # "certificate" would be garbage).  If beta itself is still
                # finite the corruption was round-local — keep beta and
                # simply re-run the round (jit determinism makes the re-run
                # bit-identical to the fault-free round).  If beta is
                # corrupted, rewind to the best finite certified iterate
                # and drop the incremental carries so they are recomputed.
                nonfinite_run += 1
                if nonfinite_run >= 3:
                    raise NumericsError(
                        f"{nonfinite_run} consecutive non-finite certified "
                        f"rounds at lambda={float(lam_):.3e}; rewind could "
                        "not recover a finite trajectory"
                    )
                with _read("finite"):
                    beta_finite = bool(jnp.all(jnp.isfinite(beta)))
                if not beta_finite:
                    beta = (best_beta if best_beta is not None
                            else jnp.zeros((G, ng), dtype))
                    resid_nc = None
                    z_nc = None
                continue
            nonfinite_run = 0
            if best_gap is None or float(gap_r) < best_gap:
                best_gap = float(gap_r)
                best_beta = beta
            gap, theta = gap_r, theta_r

            if float(gap) <= tol:
                # Do NOT apply this round's masks: at convergence the
                # rounded gap can under-estimate the true gap (to exactly 0
                # in f32), so its sphere radius is not reliable, and zeroing
                # beta here would invalidate the gap just reported.  The
                # returned active sets reflect the last screen applied.
                break

            if self.budget is not None:
                reason = self.budget.exceeded()
                if reason is not None:
                    # Budget tripped at a certified boundary: return the
                    # prefix actually certified — gap/theta above are the
                    # honest full-problem values for the current beta.
                    degraded = reason
                    break

            if rule.is_dynamic:
                with obs_trace.span("masks"):
                    n_g0 = int(group_active.sum())
                    n_f0 = int(feat_active.sum())
                    with _read("masks"):
                        group_active &= np.asarray(g_act)
                        feat_active &= np.asarray(f_act)
                    feat_active &= group_active[:, None]
                    masks_changed = (int(group_active.sum()) != n_g0
                                     or int(feat_active.sum()) != n_f0)
                    beta_masked = beta * jnp.asarray(feat_active, dtype)
                    if resid_nc is not None and masks_changed:
                        # Keep the carried residual consistent with the
                        # newly zeroed coefficients (masks shrink
                        # monotonically, so an unchanged mask leaves beta —
                        # and resid — as-is).
                        if Xt_full is None:
                            Xt_full = jnp.transpose(problem.X, (1, 0, 2))
                        resid_nc = resid_nc + jnp.einsum(
                            "gnk,gk->n", Xt_full, beta - beta_masked
                        )
                    if z_nc is not None and masks_changed:
                        # Same consistency rule for the generic-loss
                        # predictor carry: z = X beta shrinks by
                        # X (beta - beta_masked).
                        if Xt_full is None:
                            Xt_full = jnp.transpose(problem.X, (1, 0, 2))
                        z_nc = z_nc - jnp.einsum(
                            "gnk,gk->n", Xt_full, beta - beta_masked
                        )
                    beta = beta_masked

            active_history.append(
                (epochs_done, int(group_active.sum()),
                 int(feat_active.sum()))
            )

            # ---- up to max_blocks x check BCD epochs in one jitted call --
            epochs_before = epochs_done
            if cfg.compact:
                idx, take, Xt, Lg, w, gmask = caches.gather(
                    problem, group_active
                )
                xt_rows = None
                if self.solver_backend == "pallas":
                    # Active-row slice of the persistent transposed design,
                    # feeding the Pallas reduced-gap correlation between
                    # epoch blocks (keyed on the same active-set bytes as
                    # the gather — a row gather, never a transpose).
                    xt_rows = caches.gather_xt_rows(
                        problem, group_active, self.xt_pre
                    )
                def _epochs_compact(backend, rows):
                    if backend == "pallas":
                        _fire_epoch_launch_fault()
                    if lsq:
                        return _inner_rounds(
                            Xt, Lg, w, problem.y, beta,
                            jnp.asarray(feat_active),
                            take, gmask, problem.tau, lam_j,
                            jnp.asarray(tol, dtype), check, max_blocks,
                            backend, rows
                        )
                    return _inner_rounds_loss(
                        Xt, Lg, w, problem.y, beta,
                        jnp.asarray(feat_active),
                        take, gmask, problem.tau, lam_j,
                        jnp.asarray(tol, dtype), self.loss, check,
                        max_blocks, backend, rows
                    )

                with obs_trace.span("epoch_block"):
                    try:
                        beta, k_done, _, steps = _epochs_compact(
                            self.solver_backend, xt_rows
                        )
                    except KernelLaunchError:
                        if self.solver_backend != "pallas":
                            raise
                        self._demote_solver_backend()
                        beta, k_done, _, steps = _epochs_compact(
                            "xla", None)
                with _read("k_done"):
                    k_done, steps = (int(v) for v in
                                     jax.device_get((k_done, steps)))
                epochs_done += check * k_done
                self.epoch_group_steps += steps
                self.epoch_group_slots += Xt.shape[0] * check * k_done
                _M_GROUP_STEPS.inc(steps)
                _M_GROUP_SLOTS.inc(Xt.shape[0] * check * k_done)
                if self.solver_backend == "pallas" and (
                        lsq or self.loss.name == "logistic"):
                    # Each inner block ran as ONE fused kernel launch
                    # (k_done of them) instead of O(G) scan steps.  Other
                    # generic losses fall back to the lax.scan epochs
                    # inside _inner_rounds_loss — no fused launch to count.
                    self.fused_epoch_launches += k_done
            else:
                if Xt_full is None:
                    Xt_full = jnp.transpose(problem.X, (1, 0, 2))
                fmask = jnp.asarray(feat_active, dtype)
                Lg = problem.Lg * jnp.asarray(group_active, dtype)
                if lsq:
                    if resid_nc is None:
                        resid_nc = problem.y - jnp.einsum(
                            "gnk,gk->n", Xt_full, beta
                        )
                    if self.solver_backend == "pallas":
                        with obs_trace.span("epoch_block"):
                            try:
                                _fire_epoch_launch_fault()
                                beta_b, resid_b = kops.bcd_epochs_fused(
                                    Xt_full, Lg, problem.w, fmask[None],
                                    beta[None], resid_nc[None],
                                    problem.tau,
                                    jnp.reshape(lam_j, (1,)), f_ce
                                )
                                beta, resid_nc = beta_b[0], resid_b[0]
                                self.fused_epoch_launches += 1
                            except KernelLaunchError:
                                self._demote_solver_backend()
                                beta, resid_nc = bcd_epochs(
                                    Xt_full, Lg, problem.w, fmask, beta,
                                    resid_nc, problem.tau, lam_j, f_ce
                                )
                    else:
                        with obs_trace.span("epoch_block"):
                            beta, resid_nc = bcd_epochs(
                                Xt_full, Lg, problem.w, fmask, beta,
                                resid_nc, problem.tau, lam_j, f_ce
                            )
                else:
                    if z_nc is None:
                        z_nc = jnp.einsum("gnk,gk->n", Xt_full, beta)
                    if (self.solver_backend == "pallas"
                            and self.loss.name == "logistic"):
                        with obs_trace.span("epoch_block"):
                            try:
                                _fire_epoch_launch_fault()
                                beta_b, z_b = kops.bcd_epochs_logistic_fused(
                                    Xt_full, Lg, problem.w,
                                    fmask[None], beta[None],
                                    z_nc[None], problem.y,
                                    problem.tau,
                                    jnp.reshape(lam_j, (1,)), f_ce
                                )
                                beta, z_nc = beta_b[0], z_b[0]
                                self.fused_epoch_launches += 1
                            except KernelLaunchError:
                                self._demote_solver_backend()
                                beta, z_nc = bcd_epochs_loss(
                                    Xt_full, Lg, problem.w, fmask, beta,
                                    z_nc, problem.tau, lam_j, problem.y,
                                    self.loss, f_ce
                                )
                    else:
                        with obs_trace.span("epoch_block"):
                            beta, z_nc = bcd_epochs_loss(
                                Xt_full, Lg, problem.w, fmask, beta, z_nc,
                                problem.tau, lam_j, problem.y, self.loss,
                                f_ce
                            )
                epochs_done += f_ce

            if self.budget is not None:
                self.budget.note_epochs(epochs_done - epochs_before)
            # Chaos hook: corrupt the iterate AFTER an epoch block — the
            # next certified round sees the non-finite beta through the
            # real dataflow (its gap goes non-finite) and rewinds.
            for s in _fire_fault("core.epochs"):
                if s.kind in ("nan", "inf"):
                    beta = beta * (float("nan") if s.kind == "nan"
                                   else float("inf"))

        return SolveResult(
            beta=beta,
            theta=theta,
            gap=gap,
            n_epochs=epochs_done,
            group_active=group_active,
            feat_active=feat_active,
            gap_history=gap_history,
            active_history=active_history,
            degraded=degraded,
        )

    def _solve_batch_bcd(self, lams, beta0, certs, caches: SolveCaches):
        """Solve B consecutive path points in ONE fused-kernel run
        (single-device mirror of :meth:`_DistStrategy._solve_batch`).

        All B lambdas warm-start from the same previous-lambda ``beta0``
        and share one gathered design buffer over the UNION of their
        certified active-group sets (the batching precondition keeps that
        union inside one gather bucket); each carries its own
        coefficients, residual, feature mask, and threshold down the fused
        kernel's lambda-batch grid axis, so every epoch block is ONE launch
        and one streaming pass over the design for all B lambdas — groups
        a given lambda screened ride along with a zero mask, exactly like
        bucket padding.  Every
        ``f_ce`` epochs (every epoch when all certificates are warm) each
        unconverged lambda gets its own certified round — per-lambda
        dynamic screening inside the batch, expressed through the
        per-lambda feature masks (the shared buffer never re-gathers
        mid-run).  Converged lambdas are snapshotted; their rows keep
        iterating under a frozen mask until the batch drains (wasted but
        harmless work — same policy as the mesh ``_solve_batch``).

        Round cadence (mirrors the per-lambda driver's round economy):
        each epoch block is followed only by the cheap reduced-problem gap
        heuristic on the batch buffer (O(n p_active) per lambda, exactly
        ``_inner_rounds``' early-exit test; on the Pallas backend it runs
        through the batch-vmapped corr kernel over the persistent
        transposed design's active rows).  A certified round runs for a
        lambda only when its reduced gap crosses ``tol`` (the convergence
        confirmation, ALWAYS full-problem) or when ``f_ce * inner_rounds``
        epochs have passed since its last round (the dynamic-screening
        cadence — the same worst-case spacing as one per-lambda
        ``_inner_rounds`` call).  Cadence rounds run COMPACT on the shared
        union buffer whenever the screened-group bound proves them exact
        (:meth:`_compact_round` with the batch union as the active set, so
        the gather key coincides with the batch buffer), with the usual
        full-round fallback on bound crossings and ``full_round_every``
        refreshes — previously the batched driver always paid full rounds
        (PR 4 leftover).  A confirmation that FAILS (reduced gap under
        ``tol`` but full gap above — the reduced gap under-estimates once
        screened mass dominates) backs that lambda off for ``f_ce`` epochs
        so a saturating straggler cannot degrade to one full round per
        epoch.

        Trade-off vs the per-lambda sequential driver: every batched
        lambda warm-starts from the *batch-entry* beta instead of its
        predecessor's solution, so cold batches spend somewhat more epochs
        (and a lambda near the ``max_epochs`` budget can saturate where
        the warmer sequential start would just converge — the reported
        gap stays honest either way).  Batching pays off on the warm
        plateau stretches where certificates coincide because little is
        changing lambda-to-lambda.

        Returns per-lambda :class:`SolveResult`\\ s with the same reporting
        semantics as :meth:`solve` (masks reflect the last screen applied;
        a converging round's masks are never adopted).
        """
        cfg = self.config
        problem = self.problem
        dtype = problem.X.dtype
        tol, f_ce = cfg.tol, cfg.f_ce
        B = len(lams)
        self.batched_lambdas += B
        G, ng = problem.G, problem.ng
        y = problem.y
        lam_max_j = jnp.asarray(self.lam_max, dtype)
        real_grp = np.asarray(jnp.any(problem.feat_mask, axis=-1))
        base_g = real_grp & np.logical_or.reduce(
            [np.asarray(c.group_active) for c in certs]
        )
        fm_full = np.asarray(problem.feat_mask)

        g_act = [real_grp & np.asarray(certs[b].group_active)
                 for b in range(B)]
        f_act = [fm_full & np.asarray(c.feat_active)
                 & np.asarray(c.group_active)[:, None] for c in certs]
        gap_b = [float(c.gap) for c in certs]
        done = np.array([g <= tol for g in gap_b])
        gap_hist = [[(0, gap_b[b])] for b in range(B)]
        epochs_b = np.zeros(B, np.int64)
        beta0_j = jnp.asarray(beta0, dtype)
        # Lambdas converged on their sequential certificate report the
        # pre-screen state, exactly like solve(): beta untouched, masks =
        # the initial active sets (the path recorder intersects the
        # REPORTED masks with the certificate afterwards).
        final_beta = [beta0_j if done[b] else None for b in range(B)]
        final_g = [real_grp.copy() if done[b] else None for b in range(B)]
        final_f = [fm_full.copy() if done[b] else None for b in range(B)]
        final_theta = [certs[b].theta for b in range(B)]

        degraded_b = [None] * B

        def results():
            return [
                SolveResult(
                    beta=final_beta[b],
                    theta=final_theta[b],
                    gap=gap_hist[b][-1][1],
                    n_epochs=int(epochs_b[b]),
                    group_active=final_g[b],
                    feat_active=final_f[b],
                    gap_history=gap_hist[b],
                    active_history=[],
                    degraded=degraded_b[b],
                )
                for b in range(B)
            ]

        if done.all():
            return results()

        idx, take, Xt, Lg, w, gmask = caches.gather(problem, base_g)
        take_np = np.asarray(take)
        Lg_eff = Lg * gmask
        lam_b = jnp.asarray(np.asarray(lams), dtype)
        n_real_groups = int(real_grp.sum())
        n_base_act = int(base_g.sum())
        # Active-row slice of the persistent transposed design: feeds the
        # batch-vmapped Pallas corr kernel in _batch_reduced_gaps (keyed on
        # the SAME active-set bytes as the shared gather buffer, so it is
        # built at most once per batch).
        xt_rows = None
        if self.solver_backend == "pallas" and self.xt_pre is not None:
            xt_rows = caches.gather_xt_rows(problem, base_g, self.xt_pre)

        def gather_masks():
            return (jnp.asarray(np.stack(f_act)[:, take_np], dtype)
                    * gmask[None, :, None])

        fm_b = gather_masks()
        bsub = jnp.stack([
            jnp.take(beta0_j * jnp.asarray(f_act[b], dtype), take, axis=0)
            for b in range(B)
        ]) * fm_b
        resid = y[None] - jnp.einsum("gnk,bgk->bn", Xt, bsub)
        # All-warm batches (every certificate gap already near tol) check
        # after every epoch; otherwise the cheap f_ce-block cadence.
        warm = all(g <= cfg.warm_gap_factor * tol for g in gap_b)
        block = 1 if warm else f_ce
        cadence = f_ce * max(1, cfg.inner_rounds)
        last_round_b = np.zeros(B)     # sequential certificates count as
        hold_b = np.zeros(B)           # round 0; holds gate re-confirms

        step = 0
        while not done.all() and step < cfg.max_epochs:
            if self.budget is not None:
                reason = self.budget.exceeded()
                if reason is not None:
                    for b in range(B):
                        if not done[b]:
                            degraded_b[b] = reason
                    break
            # The batched-lambda driver has no reference twin (the lax.scan
            # path is per-lambda): a failed fused launch surfaces as the
            # KernelLaunchError it raised instead of a silent retry.
            _fire_epoch_launch_fault()
            with obs_trace.span("epoch_block"):
                bsub, resid = kops.bcd_epochs_fused(
                    Xt, Lg_eff, w, fm_b, bsub, resid, problem.tau,
                    lam_b, block
                )
            self.fused_epoch_launches += 1
            step += block
            if self.budget is not None:
                self.budget.note_epochs(block * B)
            red = _batch_reduced_gaps(
                Xt, fm_b, bsub, resid, w, y, problem.tau, lam_b,
                backend=self.solver_backend, xt_rows=xt_rows,
            )
            with _read("reduced_gaps"):
                red = np.asarray(red)
            changed = False
            for b in range(B):
                if done[b]:
                    continue
                crossed = red[b] <= tol and step >= hold_b[b]
                due = (step - last_round_b[b] >= cadence
                       or step >= cfg.max_epochs)
                if not (crossed or due):
                    # Neither due for screening nor plausibly converged:
                    # keep iterating round-free (the cheap heuristic is
                    # the only per-block cost, as in _inner_rounds).
                    continue
                # Padded take slots alias group 0 but carry zero masks, so
                # their (zero) rows scatter harmlessly.
                beta_full = jnp.zeros((G, ng), dtype).at[take].add(
                    bsub[b] * fm_b[b]
                )
                last_round_b[b] = step
                rres = None
                if (not crossed and cfg.compact and cfg.compact_rounds
                        and self.rule.supports_compact
                        and self._rounds_since_full < cfg.full_round_every
                        and 0 < n_base_act
                        and _bucket(n_base_act) < n_real_groups):
                    # Cadence rounds (dynamic screening inside the batch)
                    # run compact on the SHARED base buffer: the round's
                    # group_active is the batch UNION active set, so the
                    # gather key coincides with the batch buffer (no
                    # re-gather) and the union-but-screened-for-b groups
                    # contribute their EXACT terms to the dual max while
                    # only the off-buffer groups are bounded from the
                    # reference — still exact when the bound holds.  The
                    # caller's per-lambda masks intersect monotonically,
                    # so union-level keep bits cannot resurrect anything
                    # lambda b already screened.  Convergence is NEVER
                    # adopted from a compact round: a crossed reduced gap
                    # (and a compact gap at tol, below) re-confirms with a
                    # FULL round, keeping every reported gap full-problem
                    # exact — the same policy as the per-lambda driver.
                    rres = self._compact_round(
                        beta_full, lam_b[b], base_g, f_act[b], caches
                    )
                    if rres is not None:
                        with _read("gap"):
                            compact_done = float(rres.gap) <= tol
                        if compact_done:
                            rres = None    # full-round confirmation below
                if rres is None:
                    rres = self._certified_round(
                        beta_full, lam_b[b], lam_max_j, self.rule,
                        caches=caches
                    )
                gap_hist[b].append((step, float(rres.gap)))
                if not np.isfinite(float(rres.gap)):
                    # Corrupted round: adopt NOTHING (theta, masks,
                    # convergence).  The batch buffer state is untouched
                    # by rounds, so the next cadence round simply re-runs
                    # from healthy state.
                    continue
                final_theta[b] = rres.theta
                if float(rres.gap) <= tol:
                    # Converging round's masks are NOT adopted (same
                    # reporter contract as solve()).
                    done[b] = True
                    epochs_b[b] = step
                    final_beta[b] = beta_full
                    final_g[b] = g_act[b]
                    final_f[b] = f_act[b]
                    continue
                if crossed:
                    # Failed confirmation: the reduced gap sits under tol
                    # while the full gap does not — back off f_ce epochs
                    # before re-confirming this lambda.
                    hold_b[b] = step + f_ce
                n_g0, n_f0 = g_act[b].sum(), f_act[b].sum()
                with _read("masks"):
                    g_act[b] &= np.asarray(rres.group_active)
                    f_act[b] &= np.asarray(rres.feat_active)
                f_act[b] &= g_act[b][:, None]
                if g_act[b].sum() != n_g0 or f_act[b].sum() != n_f0:
                    changed = True
            if changed:
                # Some lambda screened further: re-mask its coefficients
                # and refresh the affected residuals (the buffer itself
                # stays at the shared base active set).
                fm_b = gather_masks()
                bsub = bsub * fm_b
                resid = y[None] - jnp.einsum("gnk,bgk->bn", Xt, bsub)

        for b in range(B):
            if not done[b]:        # max_epochs stragglers
                epochs_b[b] = step
                final_beta[b] = jnp.zeros((G, ng), dtype).at[take].add(
                    bsub[b] * fm_b[b]
                )
                final_g[b] = g_act[b]
                final_f[b] = f_act[b]
        return results()

    def solve_path(
        self,
        lambdas: Optional[Sequence[float]] = None,
        *,
        T: int = 100,
        delta: float = 3.0,
        sequential: bool = True,
        keep_results: bool = False,
        batch_lambdas: int = 4,
        beta0=None,
        prev_epochs: Optional[int] = None,
    ) -> PathResult:
        """Solve the whole lambda path with sequential + dynamic screening.

        ``beta0``/``prev_epochs`` resume a path mid-grid: ``beta0`` warm-
        starts the first lambda (default zeros — the cold start at
        lambda_max), and ``prev_epochs`` is the epoch count of the lambda
        solved immediately before this grid began, feeding the
        ``check_every="auto"`` warmness predictor and the batched-lambda
        gate exactly as ``epochs[t-1]`` would inside one grid.  With both
        threaded, a path chopped into consecutive sub-grids on one session
        is bit-identical to the one-shot run (``batch_lambdas=1``; batch
        probes never cross a sub-grid boundary, so batching may regroup).
        The serving layer's resumable paths are built on this.

        Engine behavior (see the module docstring of
        :mod:`repro.core.path` for the algorithmic background): a certified
        :meth:`screen` round at each new lambda from the previous primal
        point *before* any epoch, one gather cache carried down the grid,
        and ``check_every="auto"`` scheduling from the sequential gap.
        ``sequential=False`` reproduces the legacy naive loop (fresh caches
        and no pre-solve screening per lambda).

        Up to ``batch_lambdas`` *consecutive* path points whose sequential
        certificates agree on the active groups are solved in one
        batched-lambda run: the ``fista_batch`` kernel on the distributed
        strategy, and — with ``solver_backend="pallas"`` (f64, GAP rule) —
        the fused BCD epoch kernel's lambda-batch grid axis on the
        single-device strategy (:meth:`_solve_batch_bcd`).
        ``PathResult.batched_lambdas`` audits both.
        """
        if self._dist is not None:
            return self._dist.solve_path(
                lambdas=lambdas, T=T, delta=delta, sequential=sequential,
                keep_results=keep_results, batch_lambdas=batch_lambdas,
                beta0=beta0,
            )
        with obs_trace.span("path") as _sp:
            _sp.set("T", T)
            return self._solve_path_impl(
                lambdas, T=T, delta=delta, sequential=sequential,
                keep_results=keep_results, batch_lambdas=batch_lambdas,
                beta0=beta0, prev_epochs=prev_epochs,
            )

    def _solve_path_impl(self, lambdas, *, T, delta, sequential,
                         keep_results, batch_lambdas, beta0,
                         prev_epochs) -> PathResult:
        cfg = self.config
        problem = self.problem
        rule = self.rule
        lam_max = self.lam_max
        if lambdas is None:
            lambdas = lambda_grid(lam_max, T=T, delta=delta)
        lambdas = np.asarray(lambdas, float)
        T_ = len(lambdas)

        G, ng = problem.G, problem.ng
        dtype = problem.X.dtype
        with _read("problem"):
            n_feat = int(np.asarray(problem.feat_mask).sum())
            n_groups = int(np.asarray(
                jnp.any(problem.feat_mask, axis=-1)).sum())
        rounds0 = self.rounds
        compact0 = self.compact_rounds
        full0 = self.full_rounds
        flops0 = self.round_flops
        fused0 = self.fused_epoch_launches
        steps0 = self.epoch_group_steps
        slots0 = self.epoch_group_slots
        batched0 = self.batched_lambdas
        traces0 = kops.transpose_trace_count()

        # One cache for the whole path: the gather (and its jit cache)
        # survives across lambdas whose certified active set is unchanged.
        # The naive mode gets a fresh cache per lambda (seed behavior) but
        # still totals its gather count for the benchmark comparison.
        caches = self.caches if sequential else None
        n_gathers_total = 0

        beta = (jnp.zeros((G, ng), dtype) if beta0 is None
                else jnp.asarray(beta0, dtype))
        betas = np.zeros((T_, G, ng), np.dtype(dtype))   # no up-cast
        gaps = np.zeros(T_, float)
        epochs = np.zeros(T_, np.int64)
        gfrac = np.zeros(T_, float)
        ffrac = np.zeros(T_, float)
        g_act = np.zeros((T_, G), bool)
        f_act = np.zeros((T_, G, ng), bool)
        seq_scr = np.zeros(T_, np.int64)
        dyn_scr = np.zeros(T_, np.int64)
        results: list = []

        screening_rule = rule.is_dynamic

        def record(t, res, first_round, n_seq_active):
            """Per-lambda bookkeeping shared by the per-lambda and the
            batched-lambda drivers (mutates the dense path arrays)."""
            with _read("result"):
                betas[t] = np.asarray(res.beta)
                gaps[t] = float(res.gap)
                g_act[t] = np.asarray(res.group_active)
                f_act[t] = np.asarray(res.feat_active)
            epochs[t] = res.n_epochs
            if first_round is not None and screening_rule:
                if np.dtype(dtype).itemsize >= 8:
                    # Report the sequential certificate even when solve
                    # converged on that very round without applying it (beta
                    # is untouched — only the REPORTED masks reflect the
                    # certificate; see the converged-round note in solve()).
                    # For lambdas where solve did apply screens this
                    # intersection is a no-op (final masks are already
                    # subsets).  Without it, Fig 2a/2b-style outputs read
                    # 1.0 active exactly at the lambdas screening handled
                    # outright.
                    g_act[t] &= np.asarray(first_round.group_active)
                    f_act[t] &= (np.asarray(first_round.feat_active)
                                 & g_act[t][:, None])
                elif res.n_epochs == 0:
                    # In low precision the converged gap's cancellation
                    # error can undershoot the GAP radius enough to
                    # mis-certify borderline groups, so the certificate is
                    # neither applied nor reported — zero the counter too,
                    # keeping counters and masks consistent (all-active,
                    # nothing discarded).
                    seq_scr[t] = 0
                    n_seq_active = n_groups
            gfrac[t] = g_act[t].sum() / max(n_groups, 1)
            ffrac[t] = f_act[t].sum() / max(n_feat, 1)
            if screening_rule:
                # g_act already includes the sequential certificate, so this
                # is non-negative; max() guards rounding of refactors only.
                dyn_scr[t] = max(0, n_seq_active - int(g_act[t].sum()))
            if keep_results:
                results.append(res)

        # Batched-lambda path points (the ROADMAP item the distributed
        # strategy delivered first): consecutive lambdas whose sequential
        # certificates agree on the active groups share ONE fused-kernel
        # run through the kernel's lambda-batch grid axis.  Pallas solver
        # backend only (the lax.scan reference has no batch axis), GAP rule
        # only (certificates must be safe spheres), and f64 only (the
        # batched driver adopts certificate masks the way the f64 reporter
        # does).  Additionally gated per-lambda on the path engine's WARM
        # predictor below: batching trades the sequential warm start for
        # launch count, which pays off (and cannot blow the epoch budget)
        # only where lambdas converge in a handful of passes — batching a
        # cold stretch costs extra epochs and discarded probe rounds for
        # nothing.
        batch_ok = (sequential and rule.name == "gap"
                    and self.solver_backend == "pallas"
                    and batch_lambdas > 1
                    and np.dtype(dtype).itemsize >= 8
                    # Batched-lambda runs are lsq-only: the batch driver's
                    # reduced-gap heuristic and fused kernel carry the
                    # squared-loss residual.
                    and self.loss.name == "lsq")

        path_degraded = ""
        t = 0
        while t < T_:
            if self.budget is not None:
                reason = self.budget.exceeded()
                if reason is not None:
                    # Budget tripped between lambdas: return the certified
                    # prefix (arrays truncated below) without starting the
                    # next sequential round.
                    path_degraded = reason
                    break
            lam_ = lambdas[t]
            # Previous-lambda epoch count for the warmness predictor; at
            # the head of a resumed sub-grid it comes from the caller
            # (prev_epochs), so chunked paths predict exactly like the
            # one-shot run.
            ep_prev = int(epochs[t - 1]) if t > 0 else int(prev_epochs or 0)
            first_round = None
            n_seq_active = n_groups
            if sequential and rule.supports_sequential:
                # Sequential rule: certified round at the NEW lambda from
                # the PREVIOUS lambda's primal point, before any epoch here.
                # Rules without sequential support are excluded: the static
                # rule's up-front screen re-masks beta before any round
                # (which would invalidate a certificate evaluated at the
                # un-masked warm start), and the dynamic/DST3 spheres
                # refine during a solve but transfer nothing across
                # lambdas.
                first_round = self.screen(float(lam_), beta, rule=rule)
                if not np.isfinite(float(first_round.gap)):
                    # Corrupted sequential round: refuse its masks (a
                    # NaN-poisoned comparison can claim everything
                    # screened) and re-run once at the same beta — a
                    # round-local corruption's re-run is bit-identical to
                    # the fault-free round (jit determinism).  Still bad:
                    # solve this lambda cold, with no sequential
                    # certificate at all.
                    first_round = self.screen(float(lam_), beta, rule=rule)
                    if not np.isfinite(float(first_round.gap)):
                        first_round = None
                if first_round is not None and screening_rule:
                    with _read("masks"):
                        n_seq_active = int(
                            np.asarray(first_round.group_active).sum()
                        )
                    seq_scr[t] = n_groups - n_seq_active

            warm_here = (first_round is not None
                         and (float(first_round.gap)
                              <= cfg.warm_gap_factor * cfg.tol
                              or 0 < ep_prev <= 4 * cfg.f_ce))
            if batch_ok and warm_here and float(first_round.gap) > cfg.tol:
                # Probe ahead: every GAP sphere from a feasible point is
                # safe, so the current beta can certify several lambdas.
                # The batch shares ONE gathered buffer over the UNION of
                # the certified active sets while each lambda keeps its
                # own masks, so the sets need not coincide exactly — a
                # probe joins as long as the union's power-of-two gather
                # bucket stays within 2x the first lambda's (single-beta
                # certificates are sharp only one grid step ahead, so
                # probe sets balloon with lambda distance; a <= 2x buffer
                # is still a clear win against per-lambda launches on the
                # tiny warm-tail buckets this gate admits).  A probe that
                # would grow the bucket further re-certifies later from a
                # warmer beta (its round is discarded — honest accounting
                # keeps it in self.rounds; the warm gate above bounds that
                # waste to regions where probes usually succeed).
                certs = [first_round]
                union_g = np.asarray(first_round.group_active).copy()
                bucket0 = _bucket(max(int(union_g.sum()), 1))
                while (len(certs) < batch_lambdas
                       and t + len(certs) < T_):
                    k = t + len(certs)
                    ck = self.screen(float(lambdas[k]), beta, rule=rule)
                    if not np.isfinite(float(ck.gap)):
                        # A corrupted probe certificate must never enter
                        # the batched driver's adopted masks; stop probing
                        # — lambda k re-certifies later from a warmer beta.
                        break
                    with _read("masks"):
                        cg = np.asarray(ck.group_active)
                    if (_bucket(max(int((union_g | cg).sum()), 1))
                            <= 2 * bucket0):
                        union_g |= cg
                        certs.append(ck)
                        seq_scr[k] = n_groups - int(cg.sum())
                    else:
                        break
                if len(certs) > 1:
                    with obs_trace.span("lambda") as _lsp:
                        _lsp.set("t", t).set("batched", len(certs))
                        run = self._solve_batch_bcd(
                            lambdas[t:t + len(certs)], beta, certs, caches
                        )
                    for j, res in enumerate(run):
                        record(t + j, res, certs[j],
                               n_groups - int(seq_scr[t + j]))
                    beta = run[-1].beta
                    t += len(certs)
                    deg = next((r.degraded for r in run if r.degraded),
                               None)
                    if deg is not None:
                        # Partially-solved lambdas stay in the prefix —
                        # their recorded gaps are the honest last-certified
                        # values; the unattempted tail is dropped.
                        path_degraded = deg
                        break
                    continue

            if cfg.check_every == "auto":
                # Warm lambdas finish in a handful of passes, so per-epoch
                # early-exit checks beat the f_ce-block floor; cold lambdas
                # keep the cheap block cadence.  Warmness is read off the
                # sequential certificate (gap already near tol), or
                # predicted from the path itself: the previous lambda's
                # epoch count, when positive and within four f_ce-blocks,
                # marks a warm region (warmness varies smoothly along a
                # geometric grid).  A zero count (lambda_max, or a user grid
                # jumping far from the last point) carries no signal and
                # must not force per-epoch checks on a cold lambda.
                warm = (first_round is not None
                        and float(first_round.gap)
                        <= cfg.warm_gap_factor * cfg.tol)
                warm |= 0 < ep_prev <= 4 * cfg.f_ce
                check_t = 1 if warm else None
            else:
                check_t = cfg.check_every

            lam_caches = caches if caches is not None else SolveCaches()
            with obs_trace.span("lambda") as _lsp:
                _lsp.set("t", t)
                res = self.solve(
                    float(lam_),
                    beta0=beta,
                    first_round=first_round,
                    lam_max=lam_max,
                    check_every=check_t,
                    caches=lam_caches,
                )
            beta = res.beta
            if caches is None:
                n_gathers_total += lam_caches.n_gathers
            record(t, res, first_round, n_seq_active)
            t += 1
            if res.degraded:
                path_degraded = res.degraded
                break

        if path_degraded and t < T_:
            # Truncate the dense arrays to the certified prefix: a
            # degraded path never pads with zeros that could be mistaken
            # for solved (and certified) lambdas.
            lambdas = lambdas[:t]
            betas, gaps, epochs = betas[:t], gaps[:t], epochs[:t]
            gfrac, ffrac = gfrac[:t], ffrac[:t]
            g_act, f_act = g_act[:t], f_act[:t]
            seq_scr, dyn_scr = seq_scr[:t], dyn_scr[:t]

        return PathResult(
            lambdas=lambdas,
            betas=betas,
            gaps=gaps,
            epochs=epochs,
            group_active_frac=gfrac,
            feat_active_frac=ffrac,
            group_active=g_act,
            feat_active=f_act,
            seq_screened=seq_scr,
            dyn_screened=dyn_scr,
            n_gathers=(caches.n_gathers if caches is not None
                       else n_gathers_total),
            results=results,
            n_rounds=self.rounds - rounds0,
            # Measured, not assumed: if any round during this path traced an
            # on-the-fly transpose (persistent-design wiring regressed),
            # every subsequent execution of that trace re-copies — attribute
            # the whole path's rounds to it.
            n_transpose_copies=(
                self.rounds - rounds0
                if kops.transpose_trace_count() > traces0 else 0
            ),
            n_compact_rounds=self.compact_rounds - compact0,
            n_full_rounds=self.full_rounds - full0,
            round_flops=self.round_flops - flops0,
            n_fused_epoch_launches=self.fused_epoch_launches - fused0,
            batched_lambdas=self.batched_lambdas - batched0,
            n_group_steps=self.epoch_group_steps - steps0,
            n_group_slots=self.epoch_group_slots - slots0,
            rule_name=rule.name,
            certificates_safe=rule.is_safe,
            degraded=path_degraded,
        )


# ---------------------------------------------------------------------------
# Distributed strategy: FISTA + GAP screening under shard_map, behind the
# same session methods
# ---------------------------------------------------------------------------


class _DistStrategy:
    """Distributed FISTA strategy for :class:`SGLSession` (mesh mode).

    Wraps the shard_map kernels of :mod:`repro.distributed.solver_dist`:
    the certified round is the sharded ``screen`` kernel (GAP sphere +
    Theorem-1 tests with psum/pmax collectives), single lambdas run the
    ``fista`` kernel, and consecutive path points with coinciding certified
    active sets run the ``fista_batch`` kernel — one X read serving all B
    lambdas per step.
    """

    def __init__(self, session: SGLSession, mesh, *, multi_pod: bool,
                 L: Optional[float]) -> None:
        from ..distributed.solver_dist import make_dist_step

        self.session = session
        problem = session.problem
        self.kernels = make_dist_step(
            mesh, tau=float(problem.tau), multi_pod=multi_pod
        )
        self.fista = jax.jit(self.kernels.fista)
        self.fista_batch = jax.jit(self.kernels.fista_batch)
        self.screen_k = jax.jit(self.kernels.screen)
        # The design, response and weights live on the mesh, sharded as the
        # kernels read them (placed once, not resharded per call).
        self.X, self.y, self.w = self.kernels.place(
            problem.X, problem.y, problem.w)
        # Design-matrix norms: constants of the problem, computed once per
        # session on the mesh (Frobenius group bound — safe for Thm 1).
        self.colnorm, self.gfro = jax.jit(self.kernels.norms)(self.X)
        self.ynorm2 = float(jnp.sum(problem.y * problem.y))
        self.L = float(L) if L is not None else _global_lipschitz(problem)

    # -- certified round ----------------------------------------------------

    def _round(self, lam_, beta, feat_mask):
        """Raw sharded round: (feat_mask', group_mask, gap, dual_scale)."""
        s = self.session
        problem = s.problem
        dtype = problem.X.dtype
        s.rounds += 1
        s.full_rounds += 1           # sharded rounds are always full-problem
        s.round_flops += 4.0 * problem.n * problem.G * problem.ng
        return self.screen_k(
            self.X, self.y, jnp.asarray(beta, dtype),
            jnp.asarray(feat_mask, dtype), self.w,
            self.colnorm, self.gfro,
            jnp.asarray(lam_, dtype), jnp.asarray(self.ynorm2, dtype),
        )

    def screen(self, lam_, beta) -> RoundResult:
        problem = self.session.problem
        fm0 = jnp.asarray(problem.feat_mask, problem.X.dtype)
        fmask, gmask, gap, _sc = self._round(lam_, beta, fm0)
        # theta stays sharded on the mesh; certificates travel as masks.
        return RoundResult(gap, None, np.asarray(gmask) > 0,
                           np.asarray(fmask) > 0,
                           safe=self.session.rule.is_safe)

    # -- single-lambda solve ------------------------------------------------

    def _divergence_step(self, gap, state, mask_unchanged, gap0):
        """FISTA restart + divergence safeguard, one check at a time.

        ``state`` is the per-lambda ``[prev_gap, rose_before]`` pair
        (mutated in place).  Returns ``(restart, raise_L)``:

        * ``restart`` — the gap rose since the last check with no new
          screening: kill the momentum (adaptive restart, O'Donoghue &
          Candes 2015).  FISTA's gap is not monotone, and its ripples near
          convergence can span two orders of magnitude, so a rise alone
          says nothing about the step size — threshold-based detectors
          (2x-previous, 100x-best) were both observed to false-trigger and
          run L up by factors of 2^27.
        * ``raise_L`` — the gap rose at TWO consecutive checks despite the
          restart (or went non-finite) AND sits an order of magnitude above
          the solve's first gap ``gap0``: after a restart the first steps
          are momentum-free ISTA, which descends whenever the step is
          valid, so a persistent rise (with the active set unchanged) that
          also climbed past where the solve *started* is the signature of
          an under-estimated Lipschitz constant (see
          :func:`_global_lipschitz`).  L is doubled and persisted for the
          rest of the session: an under-estimate costs speed, never
          correctness.  The ``gap0`` gate exists because low-precision
          runs wobble indefinitely at the f32 gap floor — consecutive-rise
          noise there drove L up by 2^26 in testing, while true divergence
          blows past 10x the initial gap within a few rounds.
        """
        g = float(gap)
        if not np.isfinite(g):
            self.L *= 2.0
            state[0], state[1] = None, False
            return True, True
        rose = (state[0] is not None and mask_unchanged
                and g > state[0])
        raise_L = (rose and state[1]
                   and gap0 is not None and g > 10.0 * gap0)
        if raise_L:
            self.L *= 2.0
        state[0], state[1] = g, rose
        return rose, raise_L

    def solve(self, lam_, beta0=None, first_round=None,
              feat_mask0=None) -> SolveResult:
        cfg = self.session.config
        problem = self.session.problem
        dtype = problem.X.dtype
        tol, f_ce, max_steps = cfg.tol, cfg.f_ce, cfg.max_epochs
        # Low-precision guard (same reasoning as the single-device path
        # reporter): at convergence the rounded gap's cancellation error
        # can undershoot the GAP radius and mis-certify borderline groups,
        # so sub-f64 runs do not adopt the converged round's masks.
        low_prec = np.dtype(dtype).itemsize < 8
        beta = (jnp.zeros((problem.G, problem.ng), dtype) if beta0 is None
                else jnp.asarray(beta0, dtype))
        z = beta
        t_mom = jnp.ones(())
        feat_mask = (jnp.asarray(problem.feat_mask, dtype)
                     if feat_mask0 is None else jnp.asarray(feat_mask0,
                                                            dtype))
        gmask = jnp.asarray(jnp.any(problem.feat_mask, axis=-1), dtype)
        lam_j = jnp.asarray(lam_, dtype)
        gap = jnp.asarray(jnp.inf, dtype)
        gap_history: list = []
        injected = first_round
        div_state = [None, False]      # [prev_gap, rose_before]
        gap0 = None                    # first finite gap of this solve
        best_gap, best_beta = None, None
        prev_nact = None
        n_steps = 0

        for step in range(max_steps):
            if step % f_ce == 0:
                if injected is not None:
                    # Sequential certificate from the path engine — consumed
                    # as round 0 instead of recomputing it.
                    gap = injected.gap
                    gm_new = jnp.asarray(injected.group_active, dtype)
                    fm_new = feat_mask * jnp.asarray(
                        injected.feat_active, dtype
                    )
                    injected = None
                else:
                    fm_new, gm_new, gap, _sc = self._round(
                        lam_j, beta, feat_mask
                    )
                gap_history.append((step, float(gap)))
                if gap0 is None and np.isfinite(float(gap)):
                    gap0 = float(gap)
                if float(gap) <= tol:
                    if not low_prec:
                        feat_mask, gmask = fm_new, gm_new
                    break
                finite = np.isfinite(float(gap))
                nact = float(jnp.sum(fm_new))
                restart, raised = self._divergence_step(
                    gap, div_state, nact == prev_nact, gap0
                )
                if raised:
                    # A diverged trajectory can sit astronomically far from
                    # the optimum (FISTA would need O(dist^2) epochs to walk
                    # back): rewind to the best iterate seen.
                    beta = (best_beta if best_beta is not None
                            else jnp.zeros_like(beta))
                if restart:
                    z = beta
                    t_mom = jnp.ones(())
                if finite:
                    # A NaN round's Theorem-1 comparisons all read False —
                    # adopting those masks would permanently (masks are
                    # monotone) zero beta on a round that certified
                    # nothing.  Only finite rounds update the masks.
                    if best_gap is None or float(gap) < best_gap:
                        best_gap, best_beta = float(gap), beta
                    prev_nact = nact
                    feat_mask, gmask = fm_new, gm_new
                beta = beta * feat_mask
                z = z * feat_mask
            beta, z, t_mom = self.fista(
                self.X, self.y, beta, z, feat_mask, self.w, t_mom,
                lam_j, jnp.asarray(self.L, dtype),
            )
            n_steps = step + 1

        return SolveResult(
            beta=beta,
            theta=None,
            gap=gap,
            n_epochs=n_steps,
            group_active=np.asarray(gmask) > 0,
            feat_active=np.asarray(feat_mask) > 0,
            gap_history=gap_history,
            active_history=[],
        )

    # -- batched-lambda solve (coinciding certified active sets) ------------

    def _solve_batch(self, lams, beta0, certs):
        """Solve B consecutive path points in ONE batched FISTA run.

        All B lambdas warm-start from the same previous-lambda beta and
        carry their own per-lambda certificate masks ((B, G, ng) state);
        every f_ce steps each unconverged lambda gets its own certified
        round (dynamic screening inside the batch).  Returns per-lambda
        SolveResults (beta/masks snapshotted at first convergence).
        """
        cfg = self.session.config
        problem = self.session.problem
        dtype = problem.X.dtype
        tol, f_ce, max_steps = cfg.tol, cfg.f_ce, cfg.max_epochs
        low_prec = np.dtype(dtype).itemsize < 8
        B = len(lams)
        self.session.batched_lambdas += B

        fm_full = jnp.asarray(problem.feat_mask, dtype)
        gm_full = jnp.asarray(jnp.any(problem.feat_mask, axis=-1), dtype)
        mask = jnp.stack([c[0] for c in certs])            # (B, G, ng)
        gmask_b = [c[1] for c in certs]
        gap_b = [c[2] for c in certs]
        gap_history = [[(0, float(g))] for g in gap_b]
        done = np.array([float(g) <= tol for g in gap_b])
        steps_b = np.zeros(B, np.int64)
        final_beta = [beta0 if done[b] else None for b in range(B)]
        # Low-precision guard: a certificate whose gap already reads <= tol
        # converged on a possibly-mis-rounded round, so sub-f64 runs report
        # the full masks instead of adopting it (mirrors the single-device
        # path reporter and _DistStrategy.solve).
        conv_mask = (lambda b: fm_full) if low_prec else (lambda b: mask[b])
        final_mask = [conv_mask(b) if done[b] else None for b in range(B)]
        if low_prec:
            gmask_b = [gm_full if done[b] else gmask_b[b] for b in range(B)]

        beta = jnp.repeat(beta0[None], B, axis=0) * mask
        z = beta
        t_mom = jnp.ones((B,))
        lam_j = jnp.asarray(np.asarray(lams), dtype)
        div_state = [[None, False] for _ in range(B)]
        gap0_b = [float(g) if np.isfinite(float(g)) else None
                  for g in gap_b]      # per-lambda first gap (certificate)
        best_gb = [None] * B
        best_bb = [None] * B
        prev_nact = [None] * B

        step = 0
        while not done.all() and step < max_steps:
            for _ in range(f_ce):
                beta, z, t_mom = self.fista_batch(
                    self.X, self.y, beta, z, mask, self.w, t_mom,
                    lam_j, jnp.asarray(self.L, dtype),
                )
            step += f_ce
            new_mask = []
            restart_b = []
            for b in range(B):
                if done[b]:
                    # Converged lambdas keep iterating inert under their
                    # frozen mask (their reported state is the snapshot).
                    new_mask.append(mask[b])
                    continue
                fm, gm, gap, _sc = self._round(lams[b], beta[b], mask[b])
                gap_history[b].append((step, float(gap)))
                if float(gap) <= tol:
                    done[b] = True
                    steps_b[b] = step
                    final_beta[b] = beta[b]
                    # Same low-precision converged-round guard as above.
                    final_mask[b] = mask[b] if low_prec else fm
                    if not low_prec:
                        gmask_b[b] = gm
                    new_mask.append(fm if not low_prec else mask[b])
                    continue
                finite = np.isfinite(float(gap))
                if gap0_b[b] is None and finite:
                    gap0_b[b] = float(gap)
                nact = float(jnp.sum(fm))
                restart, raised = self._divergence_step(
                    gap, div_state[b], nact == prev_nact[b], gap0_b[b]
                )
                if raised:
                    # Rewind the diverged lambda to its best iterate (see
                    # the single-lambda driver).
                    beta = beta.at[b].set(
                        best_bb[b] if best_bb[b] is not None else 0.0
                    )
                if restart:
                    restart_b.append(b)
                if finite:
                    # NaN-round masks certify nothing — keep the previous
                    # ones (see the single-lambda driver).
                    gmask_b[b] = gm
                    if best_gb[b] is None or float(gap) < best_gb[b]:
                        best_gb[b], best_bb[b] = float(gap), beta[b]
                    prev_nact[b] = nact
                    new_mask.append(fm)
                else:
                    new_mask.append(mask[b])
            mask = jnp.stack(new_mask)
            beta = beta * mask
            z = z * mask
            for b in restart_b:                       # adaptive restarts
                z = z.at[b].set(beta[b])
                t_mom = t_mom.at[b].set(1.0)

        for b in range(B):
            if not done[b]:       # max_steps stragglers
                steps_b[b] = step
                final_beta[b] = beta[b]
                final_mask[b] = mask[b]

        return [
            SolveResult(
                beta=final_beta[b],
                theta=None,
                gap=gap_history[b][-1][1],
                n_epochs=int(steps_b[b]),
                group_active=np.asarray(gmask_b[b]) > 0,
                feat_active=np.asarray(final_mask[b]) > 0,
                gap_history=gap_history[b],
                active_history=[],
            )
            for b in range(B)
        ]

    # -- path engine --------------------------------------------------------

    def solve_path(self, lambdas, T, delta, sequential, keep_results,
                   batch_lambdas, beta0=None) -> PathResult:
        s = self.session
        cfg = s.config
        problem = s.problem
        dtype = problem.X.dtype
        lam_max = s.lam_max
        if lambdas is None:
            lambdas = lambda_grid(lam_max, T=T, delta=delta)
        lambdas = np.asarray(lambdas, float)
        T_ = len(lambdas)
        G, ng = problem.G, problem.ng
        fm_full = jnp.asarray(problem.feat_mask, dtype)
        n_feat = int(np.asarray(problem.feat_mask).sum())
        n_groups = int(np.asarray(jnp.any(problem.feat_mask, axis=-1)).sum())
        rounds0 = s.rounds
        flops0 = s.round_flops
        batched0 = s.batched_lambdas

        betas = np.zeros((T_, G, ng), np.dtype(dtype))
        gaps = np.zeros(T_, float)
        epochs = np.zeros(T_, np.int64)
        gfrac = np.zeros(T_, float)
        ffrac = np.zeros(T_, float)
        g_act = np.zeros((T_, G), bool)
        f_act = np.zeros((T_, G, ng), bool)
        seq_scr = np.zeros(T_, np.int64)
        dyn_scr = np.zeros(T_, np.int64)
        results: list = []

        def record(t, res, n_seq_active):
            betas[t] = np.asarray(res.beta)
            gaps[t] = float(res.gap)
            epochs[t] = res.n_epochs
            g_act[t] = np.asarray(res.group_active)
            f_act[t] = np.asarray(res.feat_active)
            gfrac[t] = g_act[t].sum() / max(n_groups, 1)
            ffrac[t] = f_act[t].sum() / max(n_feat, 1)
            dyn_scr[t] = max(0, n_seq_active - int(g_act[t].sum()))
            if keep_results:
                results.append(res)

        beta = (jnp.zeros((G, ng), dtype) if beta0 is None
                else jnp.asarray(beta0, dtype))
        t = 0
        while t < T_:
            if sequential:
                # Sequential certificates for the upcoming run, all from the
                # current (previous lambda's) primal point — every GAP
                # sphere from a feasible point is safe, so one beta can
                # certify several lambdas ahead.
                certs = [self._round(lambdas[t], beta, fm_full)]
                base = np.asarray(certs[0][1]) > 0
                while (len(certs) < batch_lambdas
                       and t + len(certs) < T_):
                    k = t + len(certs)
                    ck = self._round(lambdas[k], beta, fm_full)
                    if np.array_equal(np.asarray(ck[1]) > 0, base):
                        certs.append(ck)
                    else:
                        # Mismatch: k re-certifies later from a warmer beta.
                        break
                for j, c in enumerate(certs):
                    seq_scr[t + j] = n_groups - int(
                        (np.asarray(c[1]) > 0).sum()
                    )
            else:
                certs = [None]

            low_prec = np.dtype(dtype).itemsize < 8
            if len(certs) == 1:
                cert = certs[0]
                first = None
                n_seq_active = n_groups
                if cert is not None:
                    first = RoundResult(
                        cert[2], None, np.asarray(cert[1]) > 0,
                        np.asarray(cert[0]) > 0,
                        safe=s.rule.is_safe,
                    )
                    n_seq_active = int(np.asarray(first.group_active).sum())
                res = self.solve(float(lambdas[t]), beta0=beta,
                                 first_round=first)
                if low_prec and res.n_epochs == 0:
                    # Converged on the certificate round in sub-f64: the
                    # solve did not adopt (and does not report) its masks,
                    # so keep counters consistent (see the single-device
                    # path reporter).
                    seq_scr[t] = 0
                    n_seq_active = n_groups
                record(t, res, n_seq_active)
                beta = res.beta
                t += 1
            else:
                run = self._solve_batch(lambdas[t:t + len(certs)], beta,
                                        certs)
                for j, res in enumerate(run):
                    if low_prec and res.n_epochs == 0:
                        seq_scr[t + j] = 0
                    n_seq_active = n_groups - int(seq_scr[t + j])
                    record(t + j, res, n_seq_active)
                beta = run[-1].beta
                t += len(certs)

        return PathResult(
            lambdas=lambdas,
            betas=betas,
            gaps=gaps,
            epochs=epochs,
            group_active_frac=gfrac,
            feat_active_frac=ffrac,
            group_active=g_act,
            feat_active=f_act,
            seq_screened=seq_scr,
            dyn_screened=dyn_scr,
            n_gathers=0,
            results=results,
            n_rounds=s.rounds - rounds0,
            n_transpose_copies=0,   # sharded rounds are einsum-based: no
                                    # feature-major copy is ever at stake
            n_compact_rounds=0,     # the mesh strategy always screens on
                                    # the full (sharded) problem
            n_full_rounds=s.rounds - rounds0,
            round_flops=s.round_flops - flops0,
            n_fused_epoch_launches=0,   # BCD mega-kernel is single-device;
                                        # the mesh inner solver is FISTA
            batched_lambdas=s.batched_lambdas - batched0,
            rule_name=s.rule.name,
            certificates_safe=s.rule.is_safe,
        )


# ----------------------------------------------------------------------------
# Static-analysis hook (see repro.analysis.entrypoints for the template)
# ----------------------------------------------------------------------------

from ..analysis.registry import register_traceable  # noqa: E402

register_traceable("batch_reduced_gaps", _batch_reduced_gaps,
                   module=__name__, kind="jit")
