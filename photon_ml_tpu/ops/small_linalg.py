"""Unrolled small-K dense linear algebra for batched per-entity solves.

XLA lowers ``jnp.linalg.cholesky`` / ``solve_triangular`` to LAPACK-style
custom-calls; batched over thousands of tiny [K, K] systems (the NEWTON
random-effect regime, K <= a few dozen) the on-chip profile shows those calls
costing more than the entire surrounding optimizer loop
(the 2026-07-31 on-chip trace, ROADMAP.md S2: [2000, 5, 8, 8] Cholesky
custom-calls ~8 ms per invocation). A K x K factorization is ~K^3/3 flops — microseconds of VPU
work when expressed as K trace-time-unrolled vector steps that XLA can fuse.

These routines unroll over the (static) K axis and vectorize over arbitrary
leading batch dimensions, so the vmapped/laddered Newton direction uses them
directly. Semantics match the jnp.linalg versions where it matters:
a non-PD input produces NaNs in the factor (sqrt of a negative pivot), which
the damping ladder's finiteness check relies on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jnp.ndarray

# Above this the unrolled graph stops paying for itself (and graph size grows
# linearly in K); callers fall back to the custom-call path.
MAX_UNROLL_DIM = 32


def small_cholesky(H: Array) -> Array:
    """Lower-triangular Cholesky factor of ``H`` ([..., K, K], K static).

    Cholesky–Crout unrolled over columns: K vector steps over the batch, no
    custom-calls. Non-PD inputs yield NaN pivots that propagate down their
    column (matching jnp.linalg.cholesky's NaN signalling on TPU)."""
    K = H.shape[-1]
    L = jnp.zeros_like(H)
    rows = jnp.arange(K)
    for j in range(K):
        # s_i = sum_{k<j} L[i,k] L[j,k]  (static slice: k < j)
        if j:
            s = jnp.einsum("...ik,...k->...i", L[..., :, :j], L[..., j, :j],
                           precision=jax.lax.Precision.HIGHEST)
        else:
            s = jnp.zeros(H.shape[:-1], H.dtype)
        pivot = jnp.sqrt(H[..., j, j] - s[..., j])
        col = (H[..., :, j] - s) / pivot[..., None]
        col = jnp.where(rows == j, pivot[..., None], col)
        col = jnp.where(rows < j, 0.0, col)
        L = L.at[..., :, j].set(col)
    return L


def small_solve_lower(L: Array, b: Array) -> Array:
    """Solve L y = b by forward substitution ([..., K, K] @ [..., K])."""
    K = L.shape[-1]
    if K == 0:  # degenerate zero-coefficient system (empty feature space)
        return b
    parts = []
    for i in range(K):
        acc = b[..., i]
        if i:
            prev = jnp.stack(parts, axis=-1)  # [..., i]
            acc = acc - jnp.einsum("...k,...k->...", L[..., i, :i], prev,
                                   precision=jax.lax.Precision.HIGHEST)
        parts.append(acc / L[..., i, i])
    return jnp.stack(parts, axis=-1)


def small_solve_upper_t(L: Array, y: Array) -> Array:
    """Solve L^T x = y by back substitution (L lower-triangular)."""
    K = L.shape[-1]
    if K == 0:  # degenerate zero-coefficient system
        return y
    parts = [None] * K
    for i in range(K - 1, -1, -1):
        acc = y[..., i]
        if i < K - 1:
            tail = jnp.stack(parts[i + 1 :], axis=-1)  # [..., K-1-i]
            acc = acc - jnp.einsum("...k,...k->...", L[..., i + 1 :, i], tail,
                                   precision=jax.lax.Precision.HIGHEST)
        parts[i] = acc / L[..., i, i]
    return jnp.stack(parts, axis=-1)


def small_posdef_solve(H: Array, b: Array) -> Array:
    """x = H^-1 b for PD [..., K, K] systems via the unrolled factorization."""
    L = small_cholesky(H)
    return small_solve_upper_t(L, small_solve_lower(L, b))


def _small_solve_lower_matrix(L: Array, B: Array) -> Array:
    """Forward substitution with matrix RHS: L Y = B ([..., K, M])."""
    K = L.shape[-1]
    if K == 0:  # degenerate zero-coefficient system
        return B
    rows = []
    for i in range(K):
        acc = B[..., i, :]
        if i:
            prev = jnp.stack(rows, axis=-2)  # [..., i, M]
            acc = acc - jnp.einsum(
                "...k,...km->...m", L[..., i, :i], prev,
                precision=jax.lax.Precision.HIGHEST,
            )
        rows.append(acc / L[..., i, i][..., None])
    return jnp.stack(rows, axis=-2)


def small_spd_inverse_diag(H: Array) -> Array:
    """diag(H^-1) for PD [..., K, K] via the unrolled factorization.

    H^-1 = L^-T L^-1, so diag(H^-1)_j = ||column j of L^-1||^2; L^-1 comes
    from ONE unrolled forward substitution against the identity (K steps
    regardless of the K-column RHS). This is the per-entity FULL-variance
    hot op (DistributedOptimizationProblem.computeVariances semantics) —
    vmapped over entities it otherwise lowers to the slow batched-Cholesky
    custom-call (ROADMAP.md S2)."""
    K = H.shape[-1]
    L = small_cholesky(H)
    eye = jnp.broadcast_to(jnp.eye(K, dtype=H.dtype), H.shape)
    Linv = _small_solve_lower_matrix(L, eye)  # [..., K, K]
    return jnp.sum(Linv * Linv, axis=-2)
