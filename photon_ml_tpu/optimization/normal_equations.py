"""Batched direct solves for the small dense random-effect buckets.

A cold fit runs 7-9 L-BFGS iterations per random-effect bucket, each
iteration re-reading the whole [E, S, K] block from HBM for its line-searched
value/gradient evaluations — on a loop the roofline already shows is
bandwidth-bound (~0.5 flop/byte), those passes over the data ARE the cost.
This module is the Snap ML local-second-order-solver answer (PAPERS.md
1803.06333) recast on the vmapped bucket axis: solve each entity's GLM
subproblem with a handful of exact Newton steps over the assembled Gram/
Hessian matrix instead of a quasi-Newton iteration, collapsing 20-50 data
passes into 2-6.

Two regimes, selected statically per bucket shape:

- **Linear regression** — the subproblem is quadratic, so ONE damped-free
  Newton step from the warm start lands on the exact optimum of the normal
  equations: ``w* = w0 - (X^T W X + diag(l2))^{-1} g(w0)``. One gradient
  evaluation, one Gram assembly, one Cholesky solve, one verifying gradient.
- **Logistic / Poisson / smoothed hinge** — a fixed-cap Newton/IRLS loop:
  per iteration one Hessian assembly ``X^T diag(w l'') X + l2 I`` (the L2
  term is the damping — "L2-damped", nothing hidden), one unrolled Cholesky
  solve (ops/small_linalg for K <= MAX_UNROLL_DIM: no batched custom-calls),
  one value/gradient evaluation. Steps that fail to improve the objective
  are REVERTED and freeze the lane (monotone by construction, no line
  search); warm-started descent passes typically converge in 1-2 steps, the
  claim the host-loop bench measures. The smoothed hinge uses its a.e.
  second derivative (losses._smoothed_hinge_dzz) — quality is pinned by the
  solver parity matrix (tests/test_normal_equations.py), not assumed.

Failure is LOUD, not damped away: a singular Gram matrix (collinear features
with l2=0) or NaN-poisoned inputs produce a non-finite factorization whose
coefficients the coordinate-level divergence guard rejects (previous model
kept + incident) — the closed form propagates the NaN solve directly, and
the Newton/IRLS loop poisons any lane whose direction solve came back
non-finite. Deliberately NO escalating ridge ladder here, unlike
minimize_newton: silently solving a different (damped) problem would
invalidate the exactness contract the closed form exists for. The only
repair is the unit-diagonal guard on exactly-zero diagonal slots (all-zero
padding columns / empty padded lanes), the same guard
``solver_cache.compute_variances`` applies. One honest boundary: a NEAR-
singular system whose factorization still yields finite (huge) directions
makes the IRLS loop's candidates overshoot; the monotone revert then
freezes the lane at its warm start with OBJECTIVE_NOT_IMPROVING recorded —
the same visible-but-not-rejected outcome the line-searched iterative
solvers produce on such data.

Selection (``re_solver`` config on GameEstimator / RandomEffectCoordinate,
threaded through solver_cache so the single-model, population and active-set
delta paths all inherit it):

- ``"lbfgs"``  — the existing quasi-Newton path (default; bitwise status quo).
- ``"direct"`` — force direct solves (rejects L1: the normal equations cannot
  express the L1 subgradient).
- ``"auto"``   — MEASURED per-bucket-shape selection (the host-loop paths):
  the first descent pass runs a one-shot probe of BOTH solvers per bucket
  shape on the actual first-pass inputs, records each solver's mean
  iteration count, and picks per bucket thereafter —
  :class:`AutoSolverDecision` holds the measured record, and the decision
  rides the checkpoint manifest's ``extra_state`` (fingerprint-ADJACENT:
  a resumed run replays the same per-bucket choices bitwise without
  re-measuring, but the knob never invalidates a checkpoint). The static
  ``K <= DIRECT_AUTO_K_MAX`` prior remains only where no measurement can
  exist before the program compiles (the single-trace population/sweep
  path, ``use_direct``).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from photon_ml_tpu.optimization.common import OptResult, convergence_check
from photon_ml_tpu.types import ConvergenceReason

Array = jnp.ndarray

RE_SOLVERS = ("lbfgs", "direct", "auto")

# "auto" takes the direct path only where the trace-time-unrolled Cholesky
# applies (ops/small_linalg.MAX_UNROLL_DIM — beyond it the factorization
# lowers to the batched custom-call the on-chip profile showed dominating).
DIRECT_AUTO_K_MAX = 32

# Newton-step cap for the non-quadratic families (step-halving retries run
# in an inner loop and do NOT consume this budget). Warm-started coordinate-
# descent passes converge in 1-2 steps (quadratic local convergence); the cap
# only binds on cold starts and hostile data, where the monotone revert
# freezes lanes rather than oscillate.
DIRECT_MAX_NEWTON_ITERATIONS = 8

# A lane whose step has been halved this far without improving is frozen
# (OBJECTIVE_NOT_IMPROVING): 2^-8 of a Newton step failing to descend means
# the quadratic model is useless at this point (or the data is hostile).
DIRECT_MIN_STEP_SCALE = 1.0 / 256.0


def validate_re_solver(re_solver: str, has_l1: bool) -> str:
    """Canonicalize + validate an ``re_solver`` config value."""
    solver = str(re_solver).lower()
    if solver not in RE_SOLVERS:
        raise ValueError(
            f"unknown re_solver {re_solver!r}; expected one of {RE_SOLVERS}"
        )
    if solver == "direct" and has_l1:
        raise ValueError(
            "re_solver='direct' cannot solve an L1-regularized subproblem "
            "(the normal equations have no L1 subgradient); use 'auto' "
            "(falls back to the configured optimizer) or 'lbfgs'"
        )
    return solver


def use_direct(re_solver: str, *, k: int, has_l1: bool) -> bool:
    """Static per-bucket-shape solver choice (k is the bucket's trace-time
    coefficient width, so jit's shape cache keys the decision for free).
    Under ``"auto"`` this static prior survives only on the single-trace
    population/sweep path; the host-loop paths resolve ``"auto"`` to a
    measured per-bucket choice first (:class:`AutoSolverDecision`), so the
    strings reaching their trace are always ``"lbfgs"``/``"direct"``."""
    if re_solver == "direct":
        return True
    if re_solver == "auto":
        return not has_l1 and k <= DIRECT_AUTO_K_MAX
    return False


def _shape_key(s: int, k: int) -> str:
    # string keys so the record round-trips through the JSON manifest
    return f"{int(s)}x{int(k)}"


@dataclasses.dataclass
class AutoSolverDecision:
    """Measured per-bucket-shape record behind ``re_solver="auto"``.

    ``per_shape`` maps ``"SxK"`` (a bucket's padded sample/feature widths —
    the same key jit's shape cache uses, so one measurement covers every
    bucket and every streamed chunk of that shape class) to::

        {"choice": "direct" | "lbfgs",
         "lbfgs_iters": <mean iterations over real lanes>,
         "direct_iters": <same for the direct Newton/IRLS loop>,
         "direct_clean": <bool: every direct lane converged — no frozen
                          OBJECTIVE_NOT_IMPROVING lanes, no iteration cap>}

    The pick is by MEASURED iteration counts — direct wins when its probe
    converged cleanly in no more iterations than the quasi-Newton loop —
    replacing the static ``K <= DIRECT_AUTO_K_MAX`` rule on every path that
    can measure before committing to a trace. One honest boundary stated
    rather than hidden: iteration counts, not per-iteration cost — at the
    small K that dominate the hot loop both solvers' iterations are
    data-pass-bound, which is what makes the counts comparable; the
    ``direct_clean`` veto keeps hostile shapes (frozen lanes, cap hits) on
    the line-searched solver regardless of their count.

    The record is checkpoint-FINGERPRINT-ADJACENT state: it rides the
    manifest's ``extra_state`` so a resumed run replays the same per-bucket
    choices bitwise (re-measuring against restored warm tables could flip a
    choice mid-run), but it never enters the fingerprint — the decision is
    an execution strategy, not model identity.
    """

    per_shape: dict = dataclasses.field(default_factory=dict)

    def record(self, s: int, k: int, lbfgs_iters: float, direct_iters: float,
               direct_clean: bool) -> str:
        choice = (
            "direct"
            if direct_clean and direct_iters <= lbfgs_iters
            else "lbfgs"
        )
        self.per_shape[_shape_key(s, k)] = {
            "choice": choice,
            "lbfgs_iters": float(lbfgs_iters),
            "direct_iters": float(direct_iters),
            "direct_clean": bool(direct_clean),
        }
        return choice

    def choice_for(self, s: int, k: int) -> str:
        entry = self.per_shape.get(_shape_key(s, k))
        # an unmeasured shape (a bucket class born after the first pass —
        # continuous growth) keeps the bitwise status-quo solver
        return entry["choice"] if entry else "lbfgs"

    def to_dict(self) -> dict:
        return {"per_shape": {k: dict(v) for k, v in self.per_shape.items()}}

    @classmethod
    def from_dict(cls, d: dict) -> "AutoSolverDecision":
        return cls(per_shape={k: dict(v) for k, v in (d.get("per_shape") or {}).items()})


def _unit_diag_guard(H: Array) -> Array:
    """Repair exactly-zero diagonal slots (all-zero padding columns, empty
    padded lanes) to 1 so the factorization stays well-posed for them — the
    identical guard compute_variances applies. Real singularity (nonzero but
    rank-deficient) is NOT repaired: it must surface as non-finite output."""
    d = jnp.diagonal(H)
    return H + jnp.diag((d == 0.0).astype(H.dtype))


def _posdef_solve(H: Array, b: Array) -> Array:
    """x = H^{-1} b via Cholesky: trace-time unrolled for the small-K vmapped
    regime, LAPACK-style custom-call beyond it (explicit ``re_solver='direct'``
    with a wide bucket)."""
    from photon_ml_tpu.ops import small_linalg

    if H.shape[-1] <= small_linalg.MAX_UNROLL_DIM:
        return small_linalg.small_posdef_solve(H, b)
    import jax.scipy.linalg as jsl

    return jsl.cho_solve(jsl.cho_factor(H, lower=True), b)


def minimize_direct(
    obj,
    data,
    x0: Array,
    l2,
    *,
    quadratic: bool,
    max_iterations: int = DIRECT_MAX_NEWTON_ITERATIONS,
    tolerance: float = 1e-7,
    active=None,
) -> OptResult:
    """Direct Newton/IRLS solve of one GLM subproblem (vmap-compatible).

    ``obj`` is a GLMObjective (identity normalization — random-effect blocks
    are materialized in the solve space); ``quadratic=True`` is the
    linear-regression closed form (one exact step), else the capped monotone
    Newton loop. Returns the same OptResult surface as the iterative
    minimizers so trackers, variances and the divergence guard are oblivious
    to which solver ran.

    Storage-agnostic on the FE side too: the Gram/Hessian assembly routes
    through ``obj.hessian_matrix``, which dispatches on the design matrix's
    storage class — dense blocks take the stock ``A^T diag(d) A`` MXU path,
    sparse (padded COO) designs accumulate ``SparseDesignMatrix.gram``
    column-slab-wise without ever materializing the dense [N, D] (the Snap ML
    sparse-aware kernel hierarchy, 1803.06333) — so direct/IRLS selection is
    no longer dense-only for wide sparse fixed effects.

    ``active`` (traced scalar bool, usually a vmapped lane flag) is the
    population early-exit lever: an inactive lane's initial state is masked
    to read exactly stationary (f0=0, g0=0), so the Newton loop converges it
    in ZERO iterations — under vmap the batched while_loop's trip count then
    tracks the slowest ACTIVE lane, not the slowest lane. The masked lane's
    coefficients come back as its warm start; callers select-freeze the full
    previous state around the solve anyway.
    """
    from jax import lax

    x0 = jnp.asarray(x0)

    def vg(w):
        return obj.value_and_gradient(data, w, l2)

    def newton_direction(x, g):
        H = _unit_diag_guard(obj.hessian_matrix(data, x, l2))
        return -_posdef_solve(H, g)

    f0, g0 = vg(x0)
    if active is not None:
        f0 = jnp.where(active, f0, jnp.zeros((), f0.dtype))
        g0 = jnp.where(active, g0, jnp.zeros_like(g0))

    if quadratic:
        # one Newton step from anywhere IS the optimum of a quadratic: the
        # normal equations (X^T W X + diag(l2)) w = X^T W (y - off), expressed
        # as a warm-start correction so an already-solved entity moves by
        # exactly the accumulated residual terms
        x = x0 + newton_direction(x0, g0)
        f, g = vg(x)
        finite = jnp.isfinite(f) & jnp.all(jnp.isfinite(x))
        reason = jnp.where(
            finite,
            jnp.asarray(ConvergenceReason.GRADIENT_CONVERGED, jnp.int32),
            jnp.asarray(ConvergenceReason.NOT_CONVERGED, jnp.int32),
        )
        return OptResult(
            coefficients=x,
            value=f,
            gradient=g,
            iterations=jnp.asarray(1, jnp.int32),
            convergence_reason=reason,
        )

    loss_abs_tol = jnp.abs(f0) * tolerance
    grad_abs_tol = jnp.linalg.norm(g0) * tolerance
    reason0 = jnp.where(
        jnp.linalg.norm(g0) == 0.0,
        jnp.asarray(ConvergenceReason.GRADIENT_CONVERGED, jnp.int32),
        jnp.asarray(ConvergenceReason.NOT_CONVERGED, jnp.int32),
    )
    init = (x0, f0, g0, jnp.asarray(0, jnp.int32), reason0)

    def cond(state):
        return state[4] == ConvergenceReason.NOT_CONVERGED

    def body(state):
        x, f, g, k, _ = state
        # Monotone damped Newton WITHOUT a line search: ONE Gram/Hessian
        # assembly + Cholesky solve per Newton step; the candidate is
        # validated by objective evaluations alone. Rejected candidates halve
        # the step in an INNER loop that reuses the already-factored
        # direction (x and g are unchanged while halving, so re-assembling
        # the Hessian there would produce bitwise-identical directions at ~K
        # gradient-passes of wasted reads each). NaN-poisoned inputs have f
        # already NaN, so `improved` stays False and the poisoned x0 passes
        # through to the divergence guard.
        p = newton_direction(x, g)
        # a non-finite direction means the factorization itself failed
        # (singular system, NaN-poisoned assembly): surface NaN coefficients
        # for the divergence guard instead of a silent revert — the loud half
        # of the reject contract the closed form gets for free
        solve_failed = ~jnp.all(jnp.isfinite(p))

        def try_step(alpha):
            x_c = x + alpha * p
            f_c, g_c = vg(x_c)
            return x_c, f_c, g_c

        def accepted(f_c):
            return jnp.isfinite(f_c) & (f_c <= f)

        def is_plateau(f_c):
            # a rejected candidate WITHIN the objective tolerance is a
            # plateau, not an overshoot: the lane is converged to the data's
            # resolution (reduced-precision storage raises loss_abs_tol via
            # the tolerance floor — iterating past the storage noise floor
            # is wasted reads)
            return jnp.isfinite(f_c) & (jnp.abs(f_c - f) <= loss_abs_tol)

        def halve_cond(inner):
            alpha, _x_c, f_c, _g_c = inner
            keep_halving = ~accepted(f_c) & ~is_plateau(f_c)
            # a NaN CURRENT objective or a failed factorization means the
            # lane is poisoned, not overshooting: no step length helps,
            # skip the ladder
            return keep_halving & jnp.isfinite(f) & ~solve_failed & (
                alpha * 0.5 >= DIRECT_MIN_STEP_SCALE
            )

        def halve_body(inner):
            alpha, _x_c, _f_c, _g_c = inner
            alpha = alpha * 0.5
            return (alpha,) + try_step(alpha)

        one = jnp.asarray(1.0, x0.dtype)
        _alpha, x_c, f_c, g_c = lax.while_loop(
            halve_cond, halve_body, (one,) + try_step(one)
        )
        improved = accepted(f_c) & ~solve_failed
        k_new = k + 1
        reason = convergence_check(
            value=f_c,
            prev_value=f,
            grad=g_c,
            iteration=k_new,
            max_iterations=max_iterations,
            loss_abs_tol=loss_abs_tol,
            grad_abs_tol=grad_abs_tol,
            # the halving ladder is exhausted (or hit a plateau) when the
            # inner loop exits unaccepted; a still-ascending 2^-8 Newton
            # step means the quadratic model is useless here (or the data
            # is hostile) — but a plateau reads as FUNCTION_VALUES_CONVERGED
            # through the |f_c - f| check, not as a failure
            objective_failed=((~improved) & (~is_plateau(f_c))) | solve_failed,
        )
        x_new = jnp.where(improved, x_c, x)
        # failed factorization: poison the lane's coefficients so the
        # coordinate-level divergence guard rejects the whole update
        x_new = jnp.where(solve_failed, x + jnp.nan, x_new)
        f_new = jnp.where(improved, f_c, f)
        g_new = jnp.where(improved, g_c, g)
        return (x_new, f_new, g_new, k_new, reason)

    x, f, g, k, reason = lax.while_loop(cond, body, init)
    # a lane whose very first state was non-finite (NaN-poisoned warm start
    # or data) never improved: surface the poison instead of a clean revert,
    # so the coordinate-level divergence guard rejects the update
    poisoned = ~(jnp.isfinite(f0) & jnp.all(jnp.isfinite(g0)))
    x = jnp.where(poisoned, x0 + jnp.nan, x)
    return OptResult(
        coefficients=x,
        value=f,
        gradient=g,
        iterations=k,
        convergence_reason=reason,
    )
