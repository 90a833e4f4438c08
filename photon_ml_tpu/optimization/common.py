"""Shared optimizer infrastructure.

Re-creates the reference Optimizer framework semantics (photon-lib
optimization/Optimizer.scala:36-249) in functional, jit/vmap-compatible form:

- relative -> absolute tolerances derived from the INITIAL state
  (loss_abs_tol = f0 * rel_tol, grad_abs_tol = ||g0|| * rel_tol; Optimizer.scala:60-66)
- convergence reasons (Optimizer.scala:135-149): MAX_ITERATIONS,
  OBJECTIVE_NOT_IMPROVING, FUNCTION_VALUES_CONVERGED, GRADIENT_CONVERGED
- optional per-iteration state tracking (OptimizationStatesTracker.scala): fixed-size
  arrays of (value, grad_norm) so tracking survives jit.

Everything is batched-first: OptResult fields carry whatever leading batch axes vmap
introduces, and convergence is per-problem state inside the masked while_loop.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax.numpy as jnp

from photon_ml_tpu.types import ConvergenceReason, OptimizerType

Array = jnp.ndarray

DEFAULT_TOLERANCE = 1e-7  # OptimizerConfig default in the reference CLI
DEFAULT_MAX_ITER = 100


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Static optimizer configuration (reference optimization/OptimizerConfig.scala:47).

    ``box_constraints`` maps to the reference's constraintMap (projection after each
    step for LBFGS, native handling in LBFGSB).
    """

    optimizer_type: OptimizerType = OptimizerType.LBFGS
    max_iterations: int = DEFAULT_MAX_ITER
    tolerance: float = DEFAULT_TOLERANCE
    # LBFGS-family knobs
    history_length: int = 10
    # 10 iterations SHARED across bracketing AND zoom by the single
    # while_loop (optimization/linesearch.py) — NOT parity with Breeze:
    # the reference's StrongWolfeLineSearch caps EACH phase at 10 (20
    # worst-case), so this combined budget is up to 2x tighter, relying on
    # the best-Armijo fallback to keep over-budget steps monotone (the
    # ls15 bench variant measures the combined-parity point). Kept at 10
    # because in the vmapped random-effect regime the while_loop runs
    # max-lane iterations — with thousands of lanes SOME lane zooms near
    # the budget almost every step, so the budget directly bounds the
    # whole batch's per-step cost (docs/PERFORMANCE.md round-5 table:
    # 30 -> 15 -> 10 measured +42%/+35% with every quality gate green)
    max_line_search_iterations: int = 10
    # TRON knobs (TRON.scala:253-262)
    max_cg_iterations: int = 20
    max_improvement_failures: int = 5
    track_states: bool = False

    def __post_init__(self):
        object.__setattr__(self, "optimizer_type", OptimizerType(self.optimizer_type))


class OptResult(NamedTuple):
    """Terminal optimizer state (+ optional per-iteration tracking arrays)."""

    coefficients: Array
    value: Array
    gradient: Array
    iterations: Array  # int – iterations actually performed
    convergence_reason: Array  # int – ConvergenceReason code
    tracked_values: Optional[Array] = None  # [max_iter+1] objective values (nan-padded)
    tracked_grad_norms: Optional[Array] = None
    # int – value-and-gradient evaluations of the solve (the initial one, every
    # line-search trial, a post-projection re-evaluation); None from minimisers
    # that do not count them
    evaluations: Optional[Array] = None

    @property
    def converged(self) -> Array:
        return self.convergence_reason != ConvergenceReason.NOT_CONVERGED

    def reason_name(self) -> str:
        """Human-readable convergence reason (scalar results only)."""
        return ConvergenceReason(int(self.convergence_reason)).name


def convergence_check(
    *,
    value: Array,
    prev_value: Array,
    grad: Array,
    iteration: Array,
    max_iterations: int,
    loss_abs_tol: Array,
    grad_abs_tol: Array,
    objective_failed: Array | bool = False,
) -> Array:
    """Return the ConvergenceReason code for the current state (0 = keep going).

    Order of checks matches Optimizer.getConvergenceReason (Optimizer.scala:135-149).
    """
    reason = jnp.where(
        iteration >= max_iterations,
        ConvergenceReason.MAX_ITERATIONS,
        jnp.where(
            jnp.asarray(objective_failed),
            ConvergenceReason.OBJECTIVE_NOT_IMPROVING,
            jnp.where(
                jnp.abs(value - prev_value) <= loss_abs_tol,
                ConvergenceReason.FUNCTION_VALUES_CONVERGED,
                jnp.where(
                    jnp.linalg.norm(grad) <= grad_abs_tol,
                    ConvergenceReason.GRADIENT_CONVERGED,
                    ConvergenceReason.NOT_CONVERGED,
                ),
            ),
        ),
    )
    return reason.astype(jnp.int32)


def init_tracking(max_iterations: int, f0: Array, g0_norm: Array, enabled: bool):
    """Fixed-size nan-padded tracking arrays (jit-compatible states tracker)."""
    if not enabled:
        return None, None
    values = jnp.full((max_iterations + 1,), jnp.nan, dtype=f0.dtype).at[0].set(f0)
    gnorms = jnp.full((max_iterations + 1,), jnp.nan, dtype=f0.dtype).at[0].set(g0_norm)
    return values, gnorms


def record_tracking(values, gnorms, idx, f, gnorm):
    if values is None:
        return None, None
    return values.at[idx].set(f), gnorms.at[idx].set(gnorm)
