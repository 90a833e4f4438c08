"""Strong-Wolfe line search as a single bounded ``lax.while_loop``.

The reference delegates line search to Breeze's StrongWolfeLineSearch
(photon-lib optimization/LBFGS.scala:59-108 bridges to breeze.optimize.LBFGS). We need
the same *guarantees* (sufficient decrease + curvature, so BFGS updates stay positive
definite) in a form that jit/vmaps: one while_loop whose state machine covers both the
bracketing and zoom phases of Nocedal & Wright Alg. 3.5/3.6, with bisection-with-
interpolation-safeguard steps and a hard evaluation budget.

phi(a) = f(x + a*d); the search returns the accepted step alpha and f/g at the
accepted point (one extra evaluation is never wasted: callers reuse them).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jnp.ndarray

C1 = 1e-4  # sufficient-decrease constant
C2 = 0.9  # curvature constant (quasi-Newton standard)

_BRACKETING = 0
_ZOOM = 1
_DONE = 2
_FAILED = 3


class LineSearchResult(NamedTuple):
    alpha: Array
    value: Array
    grad: Array  # gradient at x + alpha * d
    success: Array  # bool; False -> no Wolfe point found within budget
    evals: Array


class _State(NamedTuple):
    stage: Array
    i: Array
    # current trial
    a: Array
    f_a: Array
    g_a: Array  # full gradient at trial (kept so the caller reuses it)
    dphi_a: Array
    # previous trial (bracketing) / low end (zoom)
    a_lo: Array
    f_lo: Array
    dphi_lo: Array
    # high end (zoom)
    a_hi: Array
    f_hi: Array
    dphi_hi: Array
    # best Armijo-satisfying point seen (fallback when curvature never holds)
    a_best: Array
    f_best: Array
    g_best: Array


def _interp_quadratic(a_lo, f_lo, dphi_lo, a_hi, f_hi):
    """Minimizer of the quadratic through (a_lo, f_lo, dphi_lo) and (a_hi, f_hi)."""
    denom = 2.0 * (f_hi - f_lo - dphi_lo * (a_hi - a_lo))
    num = dphi_lo * (a_hi - a_lo) ** 2
    cand = a_lo - num / jnp.where(denom == 0.0, 1.0, denom)
    return jnp.where(denom == 0.0, 0.5 * (a_lo + a_hi), cand)


def strong_wolfe(
    phi: Callable[[Array], tuple[Array, Array, Array]],
    f0: Array,
    g0: Array,
    dphi0: Array,
    init_alpha: Array,
    max_iters: int = 10,
    active=None,
) -> LineSearchResult:
    """Find alpha satisfying the strong Wolfe conditions.

    ``phi(a)`` must return (f(x+ad), grad(x+ad), dphi(a) = grad.d); ``g0`` is the
    full gradient at alpha = 0, so a total failure returns the consistent triple
    (alpha=0, f0, g0). ``dphi0`` must be negative (descent direction).

    Degenerate-descent early-out: when even the bracketing phase's maximal
    alpha expansion (2^max_iters) cannot turn ``|dphi0|`` into a decrease
    visible at f0's float RESOLUTION (one ulp), no trial can measurably
    satisfy Armijo — the search would thrash bracketing/zoom for the full
    budget and report whatever the fallback holds. Such calls return
    immediately as a SUCCESSFUL no-op (alpha=0, f0, g0): the iterate is at
    the objective's float resolution, which the caller's convergence check
    then reads as FUNCTION_VALUES_CONVERGED. The 2^max_iters headroom keeps
    badly SCALED directions searchable (a collapsed quasi-Newton gamma can
    make dphi0 sub-ulp while the gradient is large — alpha expansion
    recovers those), while truly converged lanes sit many orders of
    magnitude below even the scaled threshold. This matters doubly for
    vmapped batched solves (the random-effect regime): one while_loop body
    runs max-lane iterations, so a single already-converged lane otherwise
    drags EVERY lane through ~max_iters wasted evaluations per outer step —
    the latency floor of the flagship pass in the 2026-07-31 on-chip trace
    (ROADMAP.md S2).

    ``active`` (optional bool): the caller's own keep-iterating mask. A
    batched outer while_loop FREEZES a converged lane's carry but still
    computes its body — including this inner search, whose stale-state
    thrash would otherwise set the inner loop's max-lane trip count every
    outer iteration. Inactive lanes return the no-op immediately; their
    results are discarded by the outer freeze anyway, so this cannot change
    any converging lane's numerics.
    """

    dtype = f0.dtype
    big = jnp.asarray(jnp.inf, dtype)
    fin = jnp.finfo(dtype)
    # ~(>=), not (<): a NaN dphi0 must stay SEARCHABLE so it reaches the
    # failure path (a no-op "success" would report convergence at a NaN
    # gradient); a non-finite f0 likewise searches — any finite trial
    # trivially satisfies Armijo against inf and escapes in one step
    thresh = fin.eps * jnp.maximum(jnp.abs(f0), fin.tiny) / 2.0 ** min(max_iters, 60)
    searchable = ~(dphi0 >= -thresh) | ~jnp.isfinite(f0)
    if active is not None:
        searchable = searchable & active

    def mk(stage, i, a, f_a, g_a, dphi_a, a_lo, f_lo, dphi_lo, a_hi, f_hi, dphi_hi, a_best, f_best, g_best):
        return _State(
            jnp.asarray(stage, jnp.int32), jnp.asarray(i, jnp.int32),
            a, f_a, g_a, dphi_a, a_lo, f_lo, dphi_lo, a_hi, f_hi, dphi_hi,
            a_best, f_best, g_best,
        )

    zero = jnp.zeros((), dtype)
    # unsearchable lanes trial alpha=0 (an exact no-op point) and start DONE
    a1 = jnp.where(searchable, jnp.asarray(init_alpha, dtype), zero)
    f_a1, g_a1, dphi_a1 = phi(a1)
    f_a1 = jnp.where(searchable, f_a1, f0)
    dphi_a1 = jnp.where(searchable, dphi_a1, dphi0)
    g_a1 = jax.tree.map(
        lambda gn, g_0: jnp.where(searchable, gn, g_0), g_a1, g0
    )
    # best-so-far starts at alpha = 0; the first body pass folds in the a1 trial.
    st = mk(
        jnp.where(searchable, _BRACKETING, _DONE), 1, a1, f_a1, g_a1, dphi_a1,
        zero, f0, dphi0,  # lo starts at 0
        big, big, big,
        zero, f0, g0,
    )

    armijo = lambda a, f_a: f_a <= f0 + C1 * a * dphi0
    curvature = lambda dphi_a: jnp.abs(dphi_a) <= -C2 * dphi0

    def cond(st: _State):
        return (st.stage < _DONE) & (st.i < max_iters)

    def body(st: _State):
        # ---- evaluate transition for the current trial point -------------------
        is_bracket = st.stage == _BRACKETING

        arm = armijo(st.a, st.f_a)
        curv = curvature(st.dphi_a)

        # track best Armijo point
        better = arm & (st.f_a < st.f_best)
        a_best = jnp.where(better, st.a, st.a_best)
        f_best = jnp.where(better, st.f_a, st.f_best)
        g_best = jax.tree.map(lambda new, old: jnp.where(better, new, old), st.g_a, st.g_best)

        # -- bracketing phase (Alg 3.5) -----------------------------------------
        # violation: armijo fails, or f_a >= f_lo (after first step)
        brk_hi = (~arm) | ((st.f_a >= st.f_lo) & (st.i > 1))
        brk_done = arm & curv
        brk_pos = arm & ~curv & (st.dphi_a >= 0)
        # else: extend interval

        # -- zoom phase (Alg 3.6) ------------------------------------------------
        zm_shrink_hi = (~arm) | (st.f_a >= st.f_lo)
        zm_done = arm & curv
        zm_move_hi = arm & ~curv & (st.dphi_a * (st.a_hi - st.a_lo) >= 0)

        stage = jnp.where(
            is_bracket,
            jnp.where(brk_done, _DONE, _ZOOM * (brk_hi | brk_pos) + _BRACKETING * (~(brk_hi | brk_pos))),
            jnp.where(zm_done, _DONE, _ZOOM),
        ).astype(jnp.int32)

        # new lo/hi for bracketing transitions (zoom-entry keeps the old lo; both the
        # dphi>=0 entry and the plain interval extension move lo to the current trial)
        b_a_lo = jnp.where(brk_hi, st.a_lo, st.a)
        b_f_lo = jnp.where(brk_hi, st.f_lo, st.f_a)
        b_dphi_lo = jnp.where(brk_hi, st.dphi_lo, st.dphi_a)
        b_a_hi = jnp.where(brk_hi, st.a, jnp.where(brk_pos, st.a_lo, big))
        b_f_hi = jnp.where(brk_hi, st.f_a, jnp.where(brk_pos, st.f_lo, big))
        b_dphi_hi = jnp.where(brk_hi, st.dphi_a, jnp.where(brk_pos, st.dphi_lo, big))

        # new lo/hi for zoom transitions
        z_a_lo = jnp.where(zm_shrink_hi, st.a_lo, st.a)
        z_f_lo = jnp.where(zm_shrink_hi, st.f_lo, st.f_a)
        z_dphi_lo = jnp.where(zm_shrink_hi, st.dphi_lo, st.dphi_a)
        z_a_hi = jnp.where(zm_shrink_hi, st.a, jnp.where(zm_move_hi, st.a_lo, st.a_hi))
        z_f_hi = jnp.where(zm_shrink_hi, st.f_a, jnp.where(zm_move_hi, st.f_lo, st.f_hi))
        z_dphi_hi = jnp.where(zm_shrink_hi, st.dphi_a, jnp.where(zm_move_hi, st.dphi_lo, st.dphi_hi))

        a_lo = jnp.where(is_bracket, b_a_lo, z_a_lo)
        f_lo = jnp.where(is_bracket, b_f_lo, z_f_lo)
        dphi_lo = jnp.where(is_bracket, b_dphi_lo, z_dphi_lo)
        a_hi = jnp.where(is_bracket, b_a_hi, z_a_hi)
        f_hi = jnp.where(is_bracket, b_f_hi, z_f_hi)
        dphi_hi = jnp.where(is_bracket, b_dphi_hi, z_dphi_hi)

        # ---- next trial point ---------------------------------------------------
        in_zoom_next = stage == _ZOOM
        # zoom step: quadratic interpolation, safeguarded to the middle 80% of [lo, hi]
        lo, hi = jnp.minimum(a_lo, a_hi), jnp.maximum(a_lo, a_hi)
        cand = _interp_quadratic(a_lo, f_lo, dphi_lo, a_hi, f_hi)
        width = hi - lo
        cand = jnp.clip(cand, lo + 0.1 * width, hi - 0.1 * width)
        a_zoom = jnp.where(jnp.isfinite(cand), cand, 0.5 * (lo + hi))
        a_extend = 2.0 * st.a  # bracketing: grow
        a_next = jnp.where(in_zoom_next, a_zoom, a_extend)
        a_next = jnp.where(stage == _DONE, st.a, a_next)

        # evaluate (wasted when DONE, but keeps the loop shape static; the loop exits
        # immediately after, so at most one redundant eval per search)
        f_n, g_n, dphi_n = phi(a_next)
        keep = stage == _DONE
        f_n = jnp.where(keep, st.f_a, f_n)
        dphi_n = jnp.where(keep, st.dphi_a, dphi_n)
        g_n = jax.tree.map(lambda new, old: jnp.where(keep, old, new), g_n, st.g_a)

        return _State(
            stage, st.i + 1, a_next, f_n, g_n, dphi_n,
            a_lo, f_lo, dphi_lo, a_hi, f_hi, dphi_hi,
            a_best, f_best, g_best,
        )

    final = lax.while_loop(cond, body, st)

    success = final.stage == _DONE
    # Fallback: best Armijo point if any, else failure.
    has_fallback = final.a_best > 0
    alpha = jnp.where(success, final.a, jnp.where(has_fallback, final.a_best, 0.0))
    value = jnp.where(success, final.f_a, jnp.where(has_fallback, final.f_best, f0))
    grad = jax.tree.map(
        lambda ga, gb: jnp.where(success, ga, gb), final.g_a, final.g_best
    )
    return LineSearchResult(
        alpha=alpha,
        value=value,
        grad=grad,
        success=success | has_fallback,
        evals=final.i,
    )


def backtracking_armijo(
    phi: Callable[[Array], tuple[Array, Array]],
    f0: Array,
    dphi0: Array,
    init_alpha: Array,
    max_iters: int = 10,
    shrink: float = 0.5,
    active=None,
) -> LineSearchResult:
    """Armijo backtracking (used by OWLQN / projected LBFGSB line searches, where the
    directional derivative of the projected path is not smooth enough for Wolfe).

    ``phi(a)`` returns (f, grad) at the trial point; dphi0 is the initial directional
    derivative of the (possibly pseudo-) gradient.

    Shares strong_wolfe's degenerate-descent early-out: when ``|dphi0|`` is
    below the float resolution of f0, the first trial is alpha=0 (an exact
    no-op whose Armijo test passes trivially) so the loop never runs —
    batched solves stop paying max-lane backtracking for converged lanes.
    (Backtracking only SHRINKS alpha, so no expansion headroom is needed in
    the threshold; init_alpha <= 1 for every caller.)
    """

    fin = jnp.finfo(f0.dtype)
    # same NaN/inf handling as strong_wolfe: non-finite states must search
    searchable = ~(
        dphi0 >= -(fin.eps * jnp.maximum(jnp.abs(f0), fin.tiny))
    ) | ~jnp.isfinite(f0)
    if active is not None:
        searchable = searchable & active
    a1 = jnp.where(searchable, jnp.asarray(init_alpha, f0.dtype), 0.0)
    f1, g1 = phi(a1)

    def cond(st):
        a, f_a, g_a, i = st
        return (f_a > f0 + C1 * a * dphi0) & (i < max_iters)

    def body(st):
        a, f_a, g_a, i = st
        a = a * shrink
        f_n, g_n = phi(a)
        return (a, f_n, g_n, i + 1)

    a, f_a, g_a, i = lax.while_loop(cond, body, (a1, f1, g1, jnp.asarray(1, jnp.int32)))
    success = f_a <= f0 + C1 * a * dphi0
    return LineSearchResult(alpha=jnp.where(success, a, 0.0), value=jnp.where(success, f_a, f0), grad=g_a, success=success, evals=i)
