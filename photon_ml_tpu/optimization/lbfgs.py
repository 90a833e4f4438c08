"""L-BFGS as one jitted ``lax.while_loop`` (batched-first; vmap gives per-entity solves).

Functional re-design of photon-lib optimization/LBFGS.scala:39-157 (which bridges to
Breeze): two-loop recursion over fixed-size newest-first (s, y) buffers, strong-Wolfe
line search, optional box projection after each step (the reference's constraintMap
handling, OptimizationUtils.projectCoefficientsToSubspace), and the reference's
convergence-reason semantics (common.convergence_check).

TPU notes: the [m, d] history buffers are NEWEST-FIRST — ``push_history`` rolls
them one slot and writes position 0, so the two-loop recursion unrolls over the
static history length with static slot indices (plain fused vector-op chains;
a circular buffer would need 2m sequential dynamic slices per iteration). One
optimizer run is one XLA program with zero host round-trips (vs one Spark
broadcast + treeAggregate per iteration in the reference).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optimization import linesearch
from photon_ml_tpu.optimization.common import (
    OptResult,
    convergence_check,
    init_tracking,
    record_tracking,
)
from photon_ml_tpu.types import ConvergenceReason

Array = jnp.ndarray


class _LBFGSState(NamedTuple):
    x: Array
    f: Array
    g: Array
    S: Array  # [m, d] step history, newest first (push_history layout)
    Y: Array  # [m, d] gradient-difference history, newest first
    rho: Array  # [m] 1 / (s.y), newest first
    k: Array  # iteration counter
    evals: Array  # value-and-gradient evaluations so far (int32)
    n_written: Array  # total (s, y) pairs ever stored (min(n_written, m) valid)
    reason: Array
    tracked_values: Optional[Array]
    tracked_gnorms: Optional[Array]


def two_loop_direction(g: Array, S: Array, Y: Array, rho: Array, n_written: Array) -> Array:
    """-H.g via the standard two-loop recursion, NEWEST-FIRST layout.

    Pair 0 is the newest (``push_history`` rolls the buffers on store);
    ``n_written`` counts pairs actually stored, so min(n_written, m) leading
    slots are valid and the rest are masked.

    The recursion is unrolled over the (static) history length with STATIC
    slot indices: the previous circular-buffer form indexed ``S[j]`` with a
    traced slot inside ``lax.fori_loop`` — 2m sequential dynamic-slice ops
    per optimizer iteration, pure latency in the vmapped random-effect
    regime (the solver while_loops were the pass's floor in the 2026-07-31
    on-chip trace, ROADMAP.md S2). Static slices fuse into plain vector
    op chains instead.
    """
    m = S.shape[0]
    dtype = g.dtype
    n_pairs = jnp.minimum(n_written, m)

    q = g.astype(dtype)
    alphas = []
    for i in range(m):  # newest -> oldest, static index
        a = rho[i] * jnp.dot(S[i], q)
        a = jnp.where(i < n_pairs, a, 0.0)
        q = q - a * Y[i]
        alphas.append(a)

    # Initial Hessian scaling gamma = s.y / y.y from the newest pair.
    ydoty = jnp.dot(Y[0], Y[0])
    gamma = jnp.where(
        (n_pairs > 0) & (ydoty > 0), jnp.dot(S[0], Y[0]) / jnp.where(ydoty > 0, ydoty, 1.0), 1.0
    )
    r = gamma * q

    for i in range(m - 1, -1, -1):  # oldest -> newest, static index
        beta = rho[i] * jnp.dot(Y[i], r)
        upd = (alphas[i] - beta) * S[i]
        r = r + jnp.where(i < n_pairs, 1.0, 0.0) * upd
    return -r


def push_history(S, Y, rho, n_written, s, y, sy, good_pair):
    """Store a curvature pair in newest-first order (shared by LBFGS, OWLQN,
    LBFGSB): roll every buffer one slot and write position 0 — static-index
    updates, matching two_loop_direction's layout. Skipped pairs leave the
    buffers AND the valid-pair count untouched (the helper owns both so they
    cannot desynchronize). Returns (S, Y, rho, n_written)."""
    S_new = jnp.roll(S, 1, axis=0).at[0].set(s)
    Y_new = jnp.roll(Y, 1, axis=0).at[0].set(y)
    rho_new = jnp.roll(rho, 1).at[0].set(1.0 / jnp.where(good_pair, sy, 1.0))
    return (
        jnp.where(good_pair, S_new, S),
        jnp.where(good_pair, Y_new, Y),
        jnp.where(good_pair, rho_new, rho),
        n_written + jnp.where(good_pair, 1, 0).astype(n_written.dtype),
    )


def minimize_lbfgs(
    value_and_grad: Callable[[Array], tuple[Array, Array]],
    x0: Array,
    *,
    max_iterations: int = 100,
    tolerance: float = 1e-7,
    history_length: int = 10,
    max_line_search_iterations: int = 10,
    lower_bounds: Optional[Array] = None,
    upper_bounds: Optional[Array] = None,
    track_states: bool = False,
) -> OptResult:
    """Minimize a smooth function with L-BFGS.

    lower/upper_bounds, when given, are applied by projecting the iterate after each
    accepted step (the reference's post-step constraint projection, LBFGS.scala:120-130
    via OptimizationUtils). For fully constrained optimization use minimize_lbfgsb.
    """
    m = history_length
    x0 = jnp.asarray(x0)
    d = x0.shape[-1]
    dtype = x0.dtype

    def project(x):
        if lower_bounds is not None:
            x = jnp.maximum(x, lower_bounds)
        if upper_bounds is not None:
            x = jnp.minimum(x, upper_bounds)
        return x

    x0 = project(x0)
    f0, g0 = value_and_grad(x0)
    loss_abs_tol = jnp.abs(f0) * tolerance
    grad_abs_tol = jnp.linalg.norm(g0) * tolerance
    tv, tg = init_tracking(max_iterations, f0, jnp.linalg.norm(g0), track_states)

    # Already stationary (exact zero gradient, e.g. warm start at the optimum).
    reason0 = jnp.where(
        jnp.linalg.norm(g0) == 0.0,
        jnp.asarray(ConvergenceReason.GRADIENT_CONVERGED, jnp.int32),
        jnp.asarray(ConvergenceReason.NOT_CONVERGED, jnp.int32),
    )

    init = _LBFGSState(
        x=x0,
        f=f0,
        g=g0,
        S=jnp.zeros((m, d), dtype),
        Y=jnp.zeros((m, d), dtype),
        rho=jnp.zeros((m,), dtype),
        k=jnp.asarray(0, jnp.int32),
        evals=jnp.asarray(1, jnp.int32),  # the initial evaluation at x0
        n_written=jnp.asarray(0, jnp.int32),
        reason=reason0,
        tracked_values=tv,
        tracked_gnorms=tg,
    )

    def cond(st: _LBFGSState):
        return st.reason == ConvergenceReason.NOT_CONVERGED

    def body(st: _LBFGSState):
        with jax.named_scope("lbfgs.direction"):
            direction = two_loop_direction(st.g, st.S, st.Y, st.rho, st.n_written)
            dphi0 = jnp.dot(st.g, direction)
            # Safeguard: fall back to steepest descent if not a descent direction.
            bad = dphi0 >= 0
            direction = jnp.where(bad, -st.g, direction)
            dphi0 = jnp.where(bad, -jnp.dot(st.g, st.g), dphi0)

        def phi(a):
            xt = st.x + a * direction
            ft, gt = value_and_grad(xt)
            return ft, gt, jnp.dot(gt, direction)

        gnorm = jnp.linalg.norm(st.g)
        init_alpha = jnp.where(
            st.k == 0, jnp.minimum(1.0, 1.0 / jnp.where(gnorm > 0, gnorm, 1.0)), 1.0
        ).astype(dtype)
        with jax.named_scope("lbfgs.linesearch"):
            ls = linesearch.strong_wolfe(
                phi, st.f, st.g, dphi0, init_alpha,
                max_iters=max_line_search_iterations,
                # a batched outer loop freezes converged lanes' carries but still
                # computes their bodies: without this mask a converged lane's
                # stale-state search sets the inner trip count every iteration
                active=st.reason == ConvergenceReason.NOT_CONVERGED,
            )
        evals = st.evals + ls.evals

        step = ls.alpha * direction
        x_new = project(st.x + step)
        s = x_new - st.x
        # After projection the gradient returned by the line search may not match
        # x_new; recompute only when a projection is active (static decision).
        if lower_bounds is not None or upper_bounds is not None:
            f_new, g_new = value_and_grad(x_new)
            evals = evals + 1
        else:
            f_new, g_new = ls.value, ls.grad

        y = g_new - st.g
        sy = jnp.dot(s, y)
        # Curvature safeguard (strong Wolfe guarantees sy > 0 on the accepted path).
        good_pair = sy > 1e-10
        S, Y, rho, n_written = push_history(
            st.S, st.Y, st.rho, st.n_written, s, y, sy, good_pair
        )

        k_new = st.k + 1
        reason = convergence_check(
            value=f_new,
            prev_value=st.f,
            grad=g_new,
            iteration=k_new,
            max_iterations=max_iterations,
            loss_abs_tol=loss_abs_tol,
            grad_abs_tol=grad_abs_tol,
            objective_failed=~ls.success,
        )
        # On line-search failure keep the previous iterate.
        x_new = jnp.where(ls.success, x_new, st.x)
        f_new = jnp.where(ls.success, f_new, st.f)
        g_new = jnp.where(ls.success, g_new, st.g)

        tv, tg = record_tracking(st.tracked_values, st.tracked_gnorms, k_new, f_new, jnp.linalg.norm(g_new))
        return _LBFGSState(x_new, f_new, g_new, S, Y, rho, k_new, evals, n_written, reason, tv, tg)

    final = lax.while_loop(cond, body, init)
    return OptResult(
        coefficients=final.x,
        value=final.f,
        gradient=final.g,
        iterations=final.k,
        convergence_reason=final.reason,
        tracked_values=final.tracked_values,
        tracked_grad_norms=final.tracked_gnorms,
        evaluations=final.evals,
    )
