"""The GLM objective: value / gradient / Hessian products as fused array programs.

This single module replaces the reference's whole aggregator family —
ValueAndGradientAggregator.scala:34-280, HessianVectorAggregator.scala:37-173,
HessianDiagonalAggregator.scala, HessianMatrixAggregator.scala:31-129 — and the
Distributed/SingleNode objective-function split (DistributedGLMLossFunction.scala,
SingleNodeGLMLossFunction.scala). There is no distributed/local fork here: the same
jitted function runs on one chip, and under a sharded-in-data jit/shard_map the
reductions become psum over the mesh (the treeAggregate equivalent) automatically.

Normalization is folded in algebraically (never materializing normalized data):
  margins   z = X.(factor*w) - (factor*w).shift + offset
  gradient  g_j = factor_j * (X^T(w*dz)_j - shift_j * sum(w*dz))
  H.v          = factor * (X^T(w*dzz*dv) - shift * sum(w*dzz*dv)),
                 dv = X.(factor*v) - (factor*v).shift
which is exactly the effectiveCoefficients/marginShift algebra of the reference.

The objective value is sum_i w_i * l(z_i, y_i) (+ lambda/2 ||coef||^2 when l2 > 0),
matching the un-averaged reference convention. l2_weight is a traced argument so
regularization sweeps re-use one compiled program (the reference mutates
regularizationWeight for the same reason, DistributedOptimizationProblem.scala:64-75).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.dataset import LabeledData
from photon_ml_tpu.function.losses import PointwiseLoss
from photon_ml_tpu.normalization import NO_NORMALIZATION, NormalizationContext

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class GLMObjective:
    """Pointwise loss + optional normalization + optional L2 term.

    All methods are pure and jit/vmap-compatible; ``data`` is a LabeledData pytree and
    ``coef`` lives in the *transformed* (normalized) space, as in the reference.
    """

    loss: PointwiseLoss
    normalization: NormalizationContext = NO_NORMALIZATION
    # Callers that vmap the objective (per-entity buckets, batched sweeps,
    # bootstrap) must disable the Pallas fast path: pallas_call has no batching
    # rule for this kernel, and those inner problems are the wrong regime for
    # it anyway (small D, batch axis provides the parallelism).
    allow_fused: bool = True
    # Set when the objective runs INSIDE shard_map over a sample-sharded data
    # axis: every data reduction (loss sum, gradient vector sum, prefactor
    # sums, Hessian blocks) is psum'd over this named axis before the
    # replicated algebra (L2 terms, normalization gradient transform) is
    # applied. This is what lets the opaque Pallas kernels run per-device on a
    # multi-chip mesh: each device fuses over its own [N/m, D] block and the
    # psum plays the role of GSPMD's auto-inserted all-reduce
    # (ValueAndGradientAggregator.scala:240-255's treeAggregate, made explicit).
    psum_axis: object = None

    # -- internals -------------------------------------------------------------------

    def _margins(self, data: LabeledData, coef: Array) -> Array:
        eff, margin_shift = self.normalization.effective_coefficients(coef)
        return data.X.matvec(eff) + margin_shift + data.offsets

    def _l2_value(self, coef: Array, l2_weight) -> Array:
        return 0.5 * l2_weight * jnp.dot(coef, coef)

    def _psum(self, x: Array) -> Array:
        """Cross-device data-reduction sum (identity outside shard_map)."""
        if self.psum_axis is None:
            return x
        return jax.lax.psum(x, self.psum_axis)

    @staticmethod
    def _weighted(weights: Array, x: Array) -> Array:
        """weights * x with weight-0 rows EXCLUDED rather than multiplied:
        0 * inf = NaN would otherwise let an excluded/padded row whose margin
        overflows the pointwise loss (e.g. exp in Poisson at f32) poison the
        whole reduction. Weight-0 rows appear everywhere by design: down-sampled
        negatives, padded entity buckets, weight-masked learning-curve subsets."""
        return jnp.where(weights != 0, weights * x, jnp.zeros((), dtype=x.dtype))

    # -- public API ------------------------------------------------------------------

    def value(self, data: LabeledData, coef: Array, l2_weight=0.0) -> Array:
        z = self._margins(data, coef)
        l = self.loss.loss(z, data.labels)
        data_sum = self._psum(jnp.sum(self._weighted(data.weights, l)))
        return data_sum + self._l2_value(coef, l2_weight)

    def value_and_gradient(
        self, data: LabeledData, coef: Array, l2_weight=0.0
    ) -> tuple[Array, Array]:
        fused = self._fused_value_and_gradient(data, coef, l2_weight)
        if fused is not None:
            return fused
        z = self._margins(data, coef)
        l, dz = self.loss.loss_and_dz(z, data.labels)
        wdz = self._weighted(data.weights, dz)
        value = self._psum(jnp.sum(self._weighted(data.weights, l)))
        value = value + self._l2_value(coef, l2_weight)
        vector_sum = self._psum(data.X.rmatvec(wdz))
        grad = self.normalization.apply_to_gradient(vector_sum, self._psum(jnp.sum(wdz)))
        return value, grad + l2_weight * coef

    def _fused_eligible(self, X, coef) -> bool:
        """Shared eligibility gate for the Pallas fast paths: opt-in switch on,
        dense f32/bf16 single-device problem, f32 coefficients. The
        value+gradient and HVP evaluations share exactly this decision; the
        full-Hessian path adds a tighter dimension cap on top
        (pallas_glm.MAX_HESS_DIM — its [D, D] VMEM accumulator is the binding
        constraint), so a wide NEWTON solve may fuse its gradient evaluations
        while building the Hessian through the stock lowering. That mix is
        numerically fine — every path computes the same math — the shared gate
        exists so eligibility rules evolve in one place."""
        from photon_ml_tpu.data.matrix import DenseDesignMatrix
        from photon_ml_tpu.ops import pallas_glm

        return (
            self.allow_fused
            and isinstance(X, DenseDesignMatrix)
            and X.values.ndim == 2
            and X.dtype in (jnp.float32, jnp.bfloat16)
            and coef.dtype == jnp.float32
            and pallas_glm.should_fuse(
                X.n_cols, X.dtype, per_device=self.psum_axis is not None
            )
        )

    def _fused_value_and_gradient(self, data: LabeledData, coef: Array, l2_weight):
        """Opt-in Pallas fast path (ops/pallas_glm.py): the two-matmul XLA
        lowering reads X from HBM twice per evaluation; the fused kernel reads
        it once. Returns None when ineligible (= stock path)."""
        from photon_ml_tpu.ops import pallas_glm

        X = data.X
        if not self._fused_eligible(X, coef):
            return None
        eff, margin_shift = self.normalization.effective_coefficients(coef)
        val, vec, wsum = pallas_glm.fused_loss_grad_sums(
            X.values,
            data.labels,
            data.offsets,
            data.weights,
            eff,
            jnp.broadcast_to(jnp.asarray(margin_shift, jnp.float32), ()),
            loss_and_dz=self.loss.loss_and_dz,
            interpret=pallas_glm.interpret_mode(),
        )
        value = self._psum(val) + self._l2_value(coef, l2_weight)
        grad = self.normalization.apply_to_gradient(self._psum(vec), self._psum(wsum))
        return value, grad + l2_weight * coef

    def _fused_hessian_vector(self, data: LabeledData, coef, vector, l2_weight):
        """Pallas fast path for the HVP (one X pass instead of three); same
        gating as _fused_value_and_gradient. TRON runs one HVP per CG step, so
        this is the hottest op of a TRON solve."""
        from photon_ml_tpu.ops import pallas_glm

        X = data.X
        if not self._fused_eligible(X, coef):
            return None
        eff, margin_shift = self.normalization.effective_coefficients(coef)
        eff_v, shift_v = self.normalization.effective_coefficients(vector)
        vec, usum = pallas_glm.fused_hessian_vector_sums(
            X.values,
            data.labels,
            data.offsets,
            data.weights,
            eff,
            jnp.asarray(margin_shift, jnp.float32),
            eff_v,
            jnp.asarray(shift_v, jnp.float32),
            dzz=self.loss.dzz,
            interpret=pallas_glm.interpret_mode(),
        )
        hv = self.normalization.apply_to_gradient(self._psum(vec), self._psum(usum))
        return hv + l2_weight * vector

    def gradient(self, data: LabeledData, coef: Array, l2_weight=0.0) -> Array:
        return self.value_and_gradient(data, coef, l2_weight)[1]

    def hessian_vector(
        self, data: LabeledData, coef: Array, vector: Array, l2_weight=0.0
    ) -> Array:
        """Gauss-Newton/true Hessian-vector product (TRON inner loop)."""
        fused = self._fused_hessian_vector(data, coef, vector, l2_weight)
        if fused is not None:
            return fused
        z = self._margins(data, coef)
        dzz = self.loss.dzz(z, data.labels)
        eff_v, shift_v = self.normalization.effective_coefficients(vector)
        dv = data.X.matvec(eff_v) + shift_v  # normalized-space directional margins
        u = self._weighted(data.weights, dzz * dv)
        vector_sum = self._psum(data.X.rmatvec(u))
        hv = self.normalization.apply_to_gradient(vector_sum, self._psum(jnp.sum(u)))
        return hv + l2_weight * vector

    def hessian_diagonal(self, data: LabeledData, coef: Array, l2_weight=0.0) -> Array:
        """diag(H) for SIMPLE variance (HessianDiagonalAggregator semantics)."""
        z = self._margins(data, coef)
        d = self._weighted(data.weights, self.loss.dzz(z, data.labels))
        sq = data.X.rmatvec_sq(d)  # sum_i d_i x_ij^2
        norm = self.normalization
        if norm.shifts is not None:
            shifts = jnp.asarray(norm.shifts, dtype=sq.dtype)
            lin = data.X.rmatvec(d)  # sum_i d_i x_ij
            sq = sq - 2.0 * shifts * lin + shifts * shifts * jnp.sum(d)
        if norm.factors is not None:
            f = jnp.asarray(norm.factors, dtype=sq.dtype)
            sq = sq * f * f
        # sq is linear in the per-sample sums, so one psum after the
        # normalization algebra equals psum-ing each constituent sum
        return self._psum(sq) + l2_weight

    def hessian_matrix(self, data: LabeledData, coef: Array, l2_weight=0.0) -> Array:
        """Full d x d Hessian for FULL variance (HessianMatrixAggregator.scala:31-129)
        and the NEWTON/direct-IRLS solvers' per-iteration build.

        Dispatches on the design matrix's storage class: dense materializes
        the normalized design (modest feature dims — the reference's FULL
        variance restriction); sparse accumulates the weighted Gram
        block-of-columns at a time (SparseDesignMatrix.gram) and applies the
        shift/factor normalization algebraically, so ``re_solver="auto"``-
        style direct selection is no longer dense-only on the FE side.
        """
        fused = self._fused_hessian_matrix(data, coef, l2_weight)
        if fused is not None:
            return fused
        z = self._margins(data, coef)
        d = self._weighted(data.weights, self.loss.dzz(z, data.labels))
        sparse = self._sparse_hessian_matrix(data.X, d, l2_weight)
        if sparse is not None:
            return sparse
        A = data.X.to_dense()
        if A.dtype == jnp.bfloat16:
            # variance math runs at the reduction dtype: applying shifts/factors
            # in bf16 would double the rounding error (cf. DenseDesignMatrix._sq)
            A = A.astype(d.dtype)
        norm = self.normalization
        if norm.shifts is not None:
            A = A - jnp.asarray(norm.shifts, dtype=A.dtype)[None, :]
        if norm.factors is not None:
            A = A * jnp.asarray(norm.factors, dtype=A.dtype)[None, :]
        H = self._psum(A.T @ (A * d[:, None]))
        return H + l2_weight * jnp.eye(H.shape[0], dtype=H.dtype)

    def _sparse_hessian_matrix(self, X, d: Array, l2_weight):
        """Sparse-storage Hessian: G = X^T diag(d) X accumulated without a
        dense [N, D] (SparseDesignMatrix.gram), then the dense branch's
        normalized-design algebra applied as rank-one corrections —
        with F = diag(factors) and shift vector s,

          H = F (G - lin s^T - s lin^T + (sum d) s s^T) F,   lin = X^T d

        which is exactly (X - 1 s^T)^T D (X - 1 s^T) scaled by F on both
        sides. Returns None for dense storage (the caller's stock path)."""
        from photon_ml_tpu.data.matrix import SparseDesignMatrix

        if not isinstance(X, SparseDesignMatrix):
            return None
        G = X.gram(d)
        if G.dtype != d.dtype:
            # variance math runs at the reduction dtype (cf. the dense branch)
            G = G.astype(d.dtype)
        norm = self.normalization
        if norm.shifts is not None:
            s = jnp.asarray(norm.shifts, dtype=G.dtype)
            lin = X.rmatvec(d)
            G = (
                G
                - lin[:, None] * s[None, :]
                - s[:, None] * lin[None, :]
                + jnp.sum(d) * (s[:, None] * s[None, :])
            )
        if norm.factors is not None:
            f = jnp.asarray(norm.factors, dtype=G.dtype)
            G = G * (f[:, None] * f[None, :])
        H = self._psum(G)
        return H + l2_weight * jnp.eye(H.shape[0], dtype=H.dtype)

    def _fused_hessian_matrix(self, data: LabeledData, coef, l2_weight):
        """Pallas fast path for the full Hessian (the NEWTON per-iteration hot
        op): one X pass, normalized rows built in VMEM instead of
        materializing the normalized design in HBM."""
        from photon_ml_tpu.ops import pallas_glm

        X = data.X
        if (
            not self._fused_eligible(X, coef)
            or X.n_cols > pallas_glm.MAX_HESS_DIM
        ):
            return None
        eff, margin_shift = self.normalization.effective_coefficients(coef)
        d = X.n_cols
        norm = self.normalization
        shifts = (
            jnp.zeros((d,), jnp.float32)
            if norm.shifts is None
            else jnp.asarray(norm.shifts, jnp.float32)
        )
        factors = (
            jnp.ones((d,), jnp.float32)
            if norm.factors is None
            else jnp.asarray(norm.factors, jnp.float32)
        )
        H = pallas_glm.fused_hessian_matrix(
            X.values,
            data.labels,
            data.offsets,
            data.weights,
            eff,
            jnp.asarray(margin_shift, jnp.float32),
            shifts,
            factors,
            dzz=self.loss.dzz,
            interpret=pallas_glm.interpret_mode(),
        )
        return self._psum(H) + l2_weight * jnp.eye(d, dtype=H.dtype)

    # -- scoring ---------------------------------------------------------------------

    def margins(self, data: LabeledData, coef: Array) -> Array:
        return self._margins(data, coef)


def make_value_and_grad(objective: GLMObjective, data: LabeledData, l2_weight=0.0):
    """Close over data: returns f(coef) -> (value, grad) for the optimizers."""

    def fn(coef: Array):
        return objective.value_and_gradient(data, coef, l2_weight)

    return fn


def make_hessian_vector(objective: GLMObjective, data: LabeledData, l2_weight=0.0):
    def fn(coef: Array, vector: Array):
        return objective.hessian_vector(data, coef, vector, l2_weight)

    return fn
