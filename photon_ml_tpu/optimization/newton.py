"""Damped Newton–Cholesky as one jitted ``lax.while_loop`` (batched-first).

A TPU-first addition with no direct reference counterpart: the reference's
second-order option is truncated-Newton TRON (optimization/TRON.scala:80-253),
designed for high-dimensional problems where the Hessian cannot be materialized.
The random-effect inner solves are the opposite regime — thousands of
independent problems of a few dozen coefficients each
(RandomEffectCoordinate.scala:109-127) — where the d x d Hessian is tiny and the
MXU builds it in one batched ``X^T diag(w l'') X`` contraction. Direct Newton
steps with a Cholesky solve then converge quadratically (typically < 10
iterations where L-BFGS needs 30+ passes), and every extra pass avoided is a
full read of the entity block from HBM.

Robustness: the Hessian is PD for every GLM loss with L2 > 0; for the
unregularized/rank-deficient case each step picks the smallest ridge from an
escalating damping ladder that yields a finite Cholesky factor (Levenberg
style). Steps are validated by the same strong-Wolfe line search as L-BFGS
(alpha=1 accepted near the optimum, so the extra evaluations vanish), with a
steepest-descent fallback when the damped solve is somehow not a descent
direction. Convergence semantics match the shared reference contract
(common.convergence_check, Optimizer.scala:135-149).
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

# Relative ridge ladder: multiples of mean|diag(H)| tried in order until the
# Cholesky factorization is finite. Level 0 (no damping) wins for every
# well-posed GLM Hessian, so the ladder costs nothing on the common path
# (d is small; the d^3 factorizations are negligible next to the N d^2
# Hessian build).
_DAMPING_LADDER = (0.0, 1e-8, 1e-5, 1e-2, 1.0)


class _NewtonState(NamedTuple):
    x: Array
    f: Array
    g: Array
    k: Array
    reason: Array
    tracked_values: Optional[Array]
    tracked_gnorms: Optional[Array]


def _newton_direction(H: Array, g: Array) -> Array:
    """Solve (H + tau I) p = -g with the smallest usable-ladder tau.

    All ladder levels factorize and solve as ONE batched op (a sequential
    scan would cost ~3 small ops per level inside the optimizer while_loop —
    pure latency on TPU); the first level whose factor AND direction are
    finite wins. A finite factor alone is not enough: near-singular pivots
    (~1e-19) give a finite L whose solve still explodes, so such levels
    escalate to more damping.

    Small systems (the vmapped random-effect regime) use the trace-time
    unrolled factorization of ops/small_linalg: the on-chip profile showed
    XLA's batched Cholesky custom-call costing more than the whole
    surrounding optimizer loop at K=8 (2026-07-31 trace, ROADMAP.md S2).
    """
    from photon_ml_tpu.ops import small_linalg

    d = H.shape[-1]
    dtype = H.dtype
    eye = jnp.eye(d, dtype=dtype)
    scale = jnp.mean(jnp.abs(jnp.diagonal(H))) + jnp.asarray(1e-30, dtype)

    taus = jnp.asarray(_DAMPING_LADDER, dtype)
    Hs = H[None, :, :] + (taus[:, None, None] * scale) * eye[None, :, :]
    unroll = d <= small_linalg.MAX_UNROLL_DIM
    Ls = small_linalg.small_cholesky(Hs) if unroll else jnp.linalg.cholesky(Hs)
    finite_L = jnp.all(jnp.isfinite(Ls), axis=(1, 2))
    Ls_safe = jnp.where(finite_L[:, None, None], Ls, eye[None, :, :])
    negg = jnp.broadcast_to(-g, (taus.shape[0], d))
    if unroll:
        cands = small_linalg.small_solve_upper_t(
            Ls_safe, small_linalg.small_solve_lower(Ls_safe, negg)
        )  # [levels, d]
    else:
        ys = jax.scipy.linalg.solve_triangular(Ls_safe, negg[..., None], lower=True)
        cands = jax.scipy.linalg.solve_triangular(
            jnp.swapaxes(Ls_safe, -1, -2), ys, lower=False
        )[..., 0]  # [levels, d]
    good = finite_L & jnp.all(jnp.isfinite(cands), axis=1)
    idx = jnp.argmax(good)  # first usable level
    # Even the max-damped factorization failed (non-finite H): steepest descent.
    return jnp.where(jnp.any(good), cands[idx], -g)


def minimize_newton(
    value_and_grad: Callable[[Array], tuple[Array, Array]],
    hessian: Callable[[Array], Array],
    x0: Array,
    *,
    max_iterations: int = 100,
    tolerance: float = 1e-7,
    max_line_search_iterations: int = 10,
    lower_bounds: Optional[Array] = None,
    upper_bounds: Optional[Array] = None,
    track_states: bool = False,
) -> OptResult:
    """Minimize a twice-differentiable function by damped Newton–Cholesky.

    ``hessian(x)`` must return the full [d, d] Hessian of the same objective as
    ``value_and_grad`` (regularization included in both). Box bounds, when
    given, are applied by post-step projection exactly as in minimize_lbfgs.
    """
    x0 = jnp.asarray(x0)
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

    reason0 = jnp.where(
        jnp.linalg.norm(g0) == 0.0,
        jnp.asarray(ConvergenceReason.GRADIENT_CONVERGED, jnp.int32),
        jnp.asarray(ConvergenceReason.NOT_CONVERGED, jnp.int32),
    )

    init = _NewtonState(
        x=x0, f=f0, g=g0, k=jnp.asarray(0, jnp.int32), reason=reason0,
        tracked_values=tv, tracked_gnorms=tg,
    )

    def cond(st: _NewtonState):
        return st.reason == ConvergenceReason.NOT_CONVERGED

    def body(st: _NewtonState):
        H = hessian(st.x)
        direction = _newton_direction(H, st.g)
        dphi0 = jnp.dot(st.g, direction)
        bad = dphi0 >= 0
        direction = jnp.where(bad, -st.g, direction)
        dphi0 = jnp.where(bad, -jnp.dot(st.g, st.g), dphi0)

        def phi(a):
            xt = st.x + a * direction
            ft, gt = value_and_grad(xt)
            return ft, gt, jnp.dot(gt, direction)

        ls = linesearch.strong_wolfe(
            phi, st.f, st.g, dphi0, jnp.asarray(1.0, dtype),
            max_iters=max_line_search_iterations,
            # frozen-lane mask, as in minimize_lbfgs
            active=st.reason == ConvergenceReason.NOT_CONVERGED,
        )

        x_new = project(st.x + ls.alpha * direction)
        if lower_bounds is not None or upper_bounds is not None:
            f_new, g_new = value_and_grad(x_new)
        else:
            f_new, g_new = ls.value, ls.grad

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
        x_new = jnp.where(ls.success, x_new, st.x)
        f_new = jnp.where(ls.success, f_new, st.f)
        g_new = jnp.where(ls.success, g_new, st.g)

        tv, tg = record_tracking(
            st.tracked_values, st.tracked_gnorms, k_new, f_new, jnp.linalg.norm(g_new)
        )
        return _NewtonState(x_new, f_new, g_new, k_new, reason, tv, tg)

    final = lax.while_loop(cond, body, init)
    return OptResult(
        coefficients=final.x,
        value=final.f,
        gradient=final.g,
        iterations=final.k,
        convergence_reason=final.reason,
        tracked_values=final.tracked_values,
        tracked_grad_norms=final.tracked_gnorms,
    )
