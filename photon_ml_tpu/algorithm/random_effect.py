"""The random-effect solver: millions of independent per-entity GLM fits as
vmap-ed bucket solves.

Replaces RandomEffectCoordinate.updateModel (photon-api algorithm/
RandomEffectCoordinate.scala:104-153: activeData.join(problems).leftOuterJoin(models)
-> per-entity L-BFGS inside mapValues) and RandomEffectOptimizationProblem
(optimization/game/RandomEffectOptimizationProblem.scala:42-182). The join machinery
vanishes: each EntityBucket is one jitted ``vmap(minimize)`` call over a dense
[E, S, K] block — zero cross-device communication during solves (the same property
the reference gets from executor-local solves), and the entity axis shards cleanly
over a mesh.

Warm start and normalization: blocks are materialized in the (optionally) normalized
space; initial models arrive in original space and are converted per entity with
gathered factor/shift vectors, then solutions are converted back, so the stored
RandomEffectModel is always in the original feature space (the reference's
RandomEffectModelInProjectedSpace conversion, model/RandomEffectModelInProjectedSpace
.scala:151 + NormalizationContext coefficient algebra).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.random_effect import (
    EntityBucket,
    RandomEffectDataset,
    _next_pow2,
)
from photon_ml_tpu.function.losses import loss_for_task
from photon_ml_tpu.models.game import RandomEffectModel
from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.optimization.config import GLMOptimizationConfiguration
from photon_ml_tpu.optimization.solver_cache import re_bucket_solver
from photon_ml_tpu.types import (
    ConvergenceReason,
    OptimizerType,
    TaskType,
    VarianceComputationType,
)
from photon_ml_tpu.util.timed import count as timed_count

Array = jnp.ndarray


@dataclasses.dataclass
class RandomEffectTracker:
    """Aggregate per-entity convergence stats (RandomEffectOptimizationTracker.scala:158).

    ``evaluations_mean`` is the mean over entities of the solver's own count
    of value-and-gradient evaluations; ``lane_waste`` the share of
    row-evaluations the vmapped bucket loops executed for lanes that had
    already stopped: a bucket of E lanes of S padded rows runs until its
    slowest lane ends, E * S * max(ev) row-evaluations, of which S * sum(ev)
    were some lane's own. Both None where a bucket's minimiser counts no
    evaluations (``OptResult.evaluations``)."""

    convergence_reason_counts: dict[str, int]
    iterations_mean: float
    iterations_max: int
    n_entities: int
    evaluations_mean: Optional[float] = None
    lane_waste: Optional[float] = None
    padded_rows: int = 0  # sum over buckets of lanes x padded rows per lane

    @staticmethod
    def from_arrays(
        reasons: np.ndarray,
        iterations: np.ndarray,
        evaluations: Optional[Sequence] = None,
        lane_rows: Optional[Sequence] = None,
    ) -> "RandomEffectTracker":
        """``evaluations``: per bucket, the real lanes' counts (or None);
        ``lane_rows``: per bucket, the padded rows per lane."""
        counts: dict[str, int] = {}
        for code, cnt in zip(*np.unique(reasons, return_counts=True)):
            counts[ConvergenceReason(int(code)).name] = int(cnt)
        tracker = RandomEffectTracker(
            convergence_reason_counts=counts,
            iterations_mean=float(iterations.mean()) if len(iterations) else 0.0,
            iterations_max=int(iterations.max()) if len(iterations) else 0,
            n_entities=len(reasons),
        )
        if evaluations and all(ev is not None for ev in evaluations):
            # per bucket: padded rows per lane, lanes, sum and max of the counts
            stats = [
                (int(s), len(ev), int(ev.sum()), int(ev.max()))
                for s, ev in zip(lane_rows, evaluations)
                if len(ev)
            ]
            ran = sum(s * lanes * most for s, lanes, _total, most in stats)
            if ran:
                own = sum(s * total for s, _lanes, total, _most in stats)
                lanes = sum(lanes for _s, lanes, _total, _most in stats)
                tracker.evaluations_mean = sum(total for _s, _l, total, _m in stats) / lanes
                tracker.lane_waste = 1.0 - own / ran
                tracker.padded_rows = sum(s * lanes for s, lanes, _total, _most in stats)
        return tracker

    def publish(self, coordinate_id: str) -> None:
        """The solver counters of one update, to the program's recorder."""
        if self.evaluations_mean is not None:
            timed_count(
                "solver.evaluations", self.evaluations_mean,
                cid=coordinate_id, kind="re", entities=self.n_entities,
            )
            timed_count(
                "solver.lane_waste", self.lane_waste,
                cid=coordinate_id, kind="re", rows=self.padded_rows,
            )

    def summary(self) -> str:
        text = (
            f"entities={self.n_entities} reasons={self.convergence_reason_counts} "
            f"iters mean={self.iterations_mean:.1f} max={self.iterations_max}"
        )
        if self.evaluations_mean is not None:
            text += f" evals mean={self.evaluations_mean:.1f} lane waste={self.lane_waste:.0%}"
        return text


class LazyRandomEffectTracker:
    """RandomEffectTracker whose per-entity stats stay ON DEVICE until first
    read. The single-program coordinate update returns its convergence
    reasons/iterations as device arrays; materializing them eagerly would put
    a blocking host sync back between coordinate updates — exactly the
    round-trip the fused update removes. Attribute access (``summary()``,
    ``iterations_mean``...) triggers one batched ``device_get``.

    ``guard_ok`` is the update's device-side divergence flag (all updated
    coefficients finite, computed BEFORE the in-program reject select): the
    descent loop reads it in its once-per-iteration batched transfer.

    ``real_masks`` (host bool array per bucket, or None) excludes
    mesh-placement padding lanes from the stats — the per-bucket path's
    ``rows < E`` filter, applied lazily at materialization so the stats of
    the sharded and per-bucket paths agree."""

    def __init__(
        self, reasons_parts, iters_parts, guard_ok=None, real_masks=None,
        evals_parts=None, lane_rows=None, coordinate_id=None,
    ):
        self.guard_ok = guard_ok
        self._pending = (
            tuple(reasons_parts),
            tuple(iters_parts),
            None if evals_parts is None else tuple(evals_parts),
        )
        self._masks = None if real_masks is None else tuple(real_masks)
        self._lane_rows = lane_rows
        self._coordinate_id = coordinate_id
        self._inner: Optional[RandomEffectTracker] = None

    def device_values(self):
        """What ``materialize`` reads from the device (None once it has): the
        descent loop fetches every tracker's in ONE batched transfer."""
        return self._pending

    def materialize(self, host=None) -> RandomEffectTracker:
        """One batched ``device_get`` of the update's per-lane stats (or
        ``host``: ``device_values()`` already fetched by the caller), once;
        publishes the solver counters under the coordinate's id."""
        if self._inner is None:
            reasons_h, iters_h, evals_h = (
                jax.device_get(self._pending) if host is None else host
            )
            masks = (
                self._masks
                if self._masks is not None
                else tuple(slice(None) for _ in reasons_h)
            )
            reasons = (
                np.concatenate(
                    [np.asarray(a)[m] for a, m in zip(reasons_h, masks)]
                )
                if reasons_h
                else np.zeros(0, np.int32)
            )
            iters = (
                np.concatenate(
                    [np.asarray(a)[m] for a, m in zip(iters_h, masks)]
                )
                if iters_h
                else np.zeros(0, np.int32)
            )
            evals = (
                None
                if evals_h is None
                else [
                    None if a is None else np.asarray(a)[m]
                    for a, m in zip(evals_h, masks)
                ]
            )
            self._inner = RandomEffectTracker.from_arrays(
                reasons, iters, evals, self._lane_rows
            )
            self._pending = None
            if self._coordinate_id is not None:
                self._inner.publish(self._coordinate_id)
        return self._inner

    def summary(self) -> str:
        return self.materialize().summary()

    def __getattr__(self, name):
        # only reached for names not set in __init__ (materialized fields)
        return getattr(self.materialize(), name)


def _gather_norm_vectors(
    normalization: Optional[NormalizationContext], proj: Array, dtype
) -> tuple[Optional[Array], Optional[Array], Optional[Array]]:
    """Per-entity (factors[E,K], shifts[E,K], intercept mask[E,K]) gathered from the
    global normalization vectors through the projection table; padding slots get
    factor 1 / shift 0."""
    if normalization is None or normalization.is_identity:
        return None, None, None
    pad = proj < 0
    safe = jnp.maximum(proj, 0)
    factors = None
    shifts = None
    if normalization.factors is not None:
        f = jnp.asarray(np.asarray(normalization.factors), dtype=dtype)
        factors = jnp.where(pad, 1.0, f[safe])
    if normalization.shifts is not None:
        s = jnp.asarray(np.asarray(normalization.shifts), dtype=dtype)
        shifts = jnp.where(pad, 0.0, s[safe])
    icpt_mask = None
    if normalization.intercept_index is not None:
        icpt_mask = (proj == normalization.intercept_index).astype(dtype)
    if shifts is not None:
        # The shift correction routes through the intercept slot (b' = b + w.shift);
        # an entity whose projection lacks the intercept column would be silently
        # mis-converted back to original space, so fail loudly instead.
        if icpt_mask is None:
            raise ValueError(
                "Normalization with shifts requires intercept_index so per-entity "
                "coefficients can be converted between spaces"
            )
        missing = np.flatnonzero(~np.asarray(icpt_mask.any(axis=-1)))
        if len(missing):
            raise ValueError(
                f"{len(missing)} entities lack the intercept column in their "
                "projection; cannot apply shift normalization (ensure the intercept "
                "survives feature selection, e.g. pass intercept_index to the "
                "dataset builder)"
            )
    return factors, shifts, icpt_mask


def _to_transformed(w, factors, shifts, icpt_mask):
    """original -> transformed space, rowwise (NormalizationContext
    modelToTransformedSpace: b' = b + w.shift; w' = w / factor)."""
    if shifts is not None:
        dot = jnp.sum(w * shifts, axis=-1, keepdims=True)
        w = w + icpt_mask * dot
    if factors is not None:
        w = w / factors
    return w


def _to_original(w, factors, shifts, icpt_mask):
    """transformed -> original (w = w' * factor; b -= w.shift)."""
    if factors is not None:
        w = w * factors
    if shifts is not None:
        dot = jnp.sum(w * shifts, axis=-1, keepdims=True)
        w = w - icpt_mask * dot
    return w


def precompute_norm_tables(
    dataset: RandomEffectDataset,
    normalization: Optional[NormalizationContext],
    dtype,
) -> tuple:
    """Per-bucket (factors, shifts, intercept-mask) triples for the
    single-program coordinate update, gathered ONCE per (dataset,
    normalization) instead of once per bucket per update — the gather (and
    its host-side missing-intercept validation) is invariant across descent
    iterations. Buckets get None when normalization is identity/absent."""
    if normalization is None or normalization.is_identity:
        return tuple(None for _ in dataset.buckets)
    out = []
    for bucket in dataset.buckets:
        K = bucket.shape[1]
        proj_b = dataset.proj_indices[bucket.entity_rows, :K]
        out.append(_gather_norm_vectors(normalization, proj_b, dtype))
    return tuple(out)


def bucket_score_slots(
    dataset: RandomEffectDataset,
    precision,
    normalization: Optional[NormalizationContext],
) -> Optional[Array]:
    """The dataset's ``sample_slots`` where an all-resident update program
    may score from the bucket blocks it has just solved, else None (it scores
    through the view). THE rule, for the coordinate, its ``score_path`` and
    the population trainer. The bucket score is taken only where it is the
    score a resumed or warm-started fit recomputes from the stored table
    (``coord.score(model)``: ``random_effect_view_score`` in original space),
    so that resume stays bit-identical:

    - a reduced ``precision`` stores the ROUNDED table, and the bucket score
      would use the solve's unrounded coefficients: view;
    - a NORMALIZED coordinate's blocks hold ``x' = (x - shift) * factor``, so
      their score ``w' . x'`` is the same margin rounded elsewhere (ulps apart
      from the original-space ``w . x``): view;
    - a dataset without slots (passive rows, mesh placement, scoring-only)
      has no complete way back from its blocks: view.

    What is left is raw float32 blocks: the same eight products a sample as
    the view kernel's, in two differently fused programs. Their bit-equality
    is OBSERVED (XLA:CPU, and the v5e at the benchmark cell's shapes), not
    built in; the resume and cross-path gates hold it
    (tests/test_bucket_score.py, test_working_set.py, test_update_program.py)."""
    if not precision.is_reference:
        return None
    if normalization is not None and not normalization.is_identity:
        return None
    return dataset.sample_slots


class UpdateProgramData(NamedTuple):
    """The dataset's arrays as the update programs read them."""

    buckets: tuple
    view: tuple  # (entity_rows [N], local_cols [N, nnz], vals [N, nnz])
    sample_slots: Optional[Array]  # [N] int32, or None: score through ``view``


def update_program_data(
    dataset: RandomEffectDataset,
    precision,
    normalization: Optional[NormalizationContext],
) -> UpdateProgramData:
    """Buckets, scoring view and (``bucket_score_slots``) slot index for the
    update programs (``solver_cache._re_coordinate_update_fn``), built by
    ONE function for the coordinate and the population trainer, so a sweep
    lane and a single fit of the same setting keep one arithmetic.

    Under a reduced policy the feature arrays the programs read every solver
    iteration (bucket blocks, the view's values) come back cast ONCE to the
    storage dtype: storage-width bytes are the HBM traffic the policy
    halves, and solves and scores upcast in-register. A cast keeps a placed
    array's sharding (computation follows data)."""
    buckets = tuple(dataset.buckets)
    view = dataset.scoring_view()
    if not precision.is_reference:
        buckets = tuple(
            dataclasses.replace(b, X=precision.to_storage(b.X)) for b in buckets
        )
        view = (view[0], view[1], precision.to_storage(view[2]))
    return UpdateProgramData(
        buckets, view, bucket_score_slots(dataset, precision, normalization)
    )


def build_l2_rows(
    dataset: RandomEffectDataset,
    l2: float,
    per_entity_reg_weights,
    dtype,
    table_rows: int,
) -> Array:
    """Row-aligned per-entity L2 table (shared by the per-bucket loop and the
    single-program update so the two paths gather identical weights). Padded
    entity rows (mesh placement) gather the base weight harmlessly."""
    E = dataset.n_entities
    l2_table = np.full(max(table_rows, E + 1), float(l2))
    if per_entity_reg_weights is not None:
        if isinstance(per_entity_reg_weights, dict):
            row_by_entity = {e: i for i, e in enumerate(dataset.entity_ids)}
            for e_id, w_e in per_entity_reg_weights.items():
                row = row_by_entity.get(e_id, -1)
                if row >= 0:
                    l2_table[row] = float(w_e)
        else:
            arr = np.asarray(per_entity_reg_weights, dtype=np.float64)
            if arr.shape[0] != E:
                raise ValueError(
                    f"per_entity_reg_weights has {arr.shape[0]} entries for "
                    f"{E} entities"
                )
            l2_table[:E] = arr
    return jnp.asarray(l2_table, dtype=dtype)


def _bucket_solver_plan(re_solver, n_buckets: int) -> tuple:
    """Normalize ``re_solver`` to one solver string per bucket: a tuple/list
    is a measured per-bucket plan (``measure_auto_solvers``), a plain string
    applies to every bucket."""
    if isinstance(re_solver, (tuple, list)):
        if len(re_solver) != n_buckets:
            raise ValueError(
                f"per-bucket re_solver plan covers {len(re_solver)} buckets, "
                f"dataset has {n_buckets}"
            )
        return tuple(re_solver)
    return (re_solver,) * n_buckets


def _bucket_shape(bucket) -> tuple:
    """A bucket's (S, K) shape class — robust to host-backed (numpy) and
    device-backed bucket arrays alike."""
    X = bucket.X
    return (int(X.shape[1]), int(X.shape[2]))


_AUTO_CLEAN_REASONS = (
    int(ConvergenceReason.FUNCTION_VALUES_CONVERGED),
    int(ConvergenceReason.GRADIENT_CONVERGED),
)


def measure_auto_solvers(
    dataset: RandomEffectDataset,
    task: TaskType,
    configuration: GLMOptimizationConfiguration,
    offsets_plus_scores: Array,
    *,
    initial_model: Optional[RandomEffectModel] = None,
    normalization: Optional[NormalizationContext] = None,
    per_entity_reg_weights=None,
    dtype=None,
):
    """One-shot measurement probe behind ``re_solver="auto"``: run BOTH
    bucket solvers per bucket SHAPE on the actual first-pass inputs (warm
    start, offsets-plus-scores, per-entity L2, normalization space) and
    record each solver's mean iteration count over real lanes — the
    measured record the per-bucket pick is keyed on
    (optimization/normal_equations.AutoSolverDecision).

    One probe per (S, K) shape class covers every bucket and every streamed
    working-set chunk of that class (the solver choice is a trace-time
    property of the shape, so this is exactly jit's own granularity). The
    probe solves with variance computation OFF — variances are computed
    after convergence and cannot change iteration counts — and its outputs
    are discarded: the first real pass re-runs under the chosen plan, so
    the descent's numerics never depend on probe state. L1 configurations
    return an empty record (every shape resolves to the quasi-Newton
    solver): the normal equations cannot express the L1 subgradient, so
    there is nothing to measure.
    """
    from photon_ml_tpu.optimization.normal_equations import AutoSolverDecision

    task = TaskType(task)
    decision = AutoSolverDecision()
    l1 = configuration.l1_weight
    if l1:
        return decision
    E, K_all = dataset.n_entities, dataset.max_k
    if dtype is None:
        dtype = dataset.sample_vals.dtype
    coeffs = None
    if initial_model is not None:
        coeffs = np.asarray(
            jax.device_get(initial_model.aligned_to(dataset).coeffs)
        ).astype(dtype)
    l2_rows = build_l2_rows(
        dataset, configuration.l2_weight, per_entity_reg_weights, dtype, E
    )
    l1_arr = jnp.asarray(0.0, dtype=dtype)
    seen: set = set()
    for bucket in dataset.buckets:
        S, K = _bucket_shape(bucket)
        if (S, K) in seen:
            continue
        seen.add((S, K))
        rows = np.asarray(bucket.entity_rows, dtype=np.int64)
        real = rows < E
        if not real.any():
            continue
        X_b = jnp.asarray(bucket.X)
        y_b = jnp.asarray(bucket.labels)
        w_b = jnp.asarray(bucket.weights)
        sid = jnp.asarray(bucket.sample_ids)
        off_b = jnp.take(offsets_plus_scores, jnp.maximum(sid, 0), axis=0)
        off_b = jnp.where(sid >= 0, off_b, 0.0).astype(dtype)
        if coeffs is None:
            init_b = jnp.zeros((len(rows), K), dtype=dtype)
        else:
            init_b = jnp.asarray(
                np.ascontiguousarray(coeffs[np.minimum(rows, E - 1), :K])
            )
        proj_b = dataset.proj_indices[jnp.minimum(jnp.asarray(rows), E - 1), :K]
        factors, shifts, icpt_mask = _gather_norm_vectors(
            normalization, proj_b, dtype
        )
        if normalization is not None and not normalization.is_identity:
            init_b = _to_transformed(init_b, factors, shifts, icpt_mask)
        l2_b = jnp.take(l2_rows, jnp.minimum(jnp.asarray(rows), E - 1))
        measured = {}
        for solver in ("lbfgs", "direct"):
            solve = re_bucket_solver(
                task, configuration.optimizer_config, False,
                VarianceComputationType.NONE, solver,
            )
            _, reasons_b, iters_b, _, _ = solve(
                X_b, y_b, w_b, off_b, init_b, l2_b, l1_arr
            )
            reasons_h, iters_h = jax.device_get((reasons_b, iters_b))  # jaxlint: disable=HS001 once-per-shape measurement probe, first pass only — the read IS the product
            measured[solver] = (
                float(np.asarray(iters_h)[real].mean()),
                bool(np.isin(np.asarray(reasons_h)[real], _AUTO_CLEAN_REASONS).all()),
            )
        decision.record(
            S, K,
            lbfgs_iters=measured["lbfgs"][0],
            direct_iters=measured["direct"][0],
            direct_clean=measured["direct"][1],
        )
    return decision


def train_random_effect(
    dataset: RandomEffectDataset,
    task: TaskType,
    configuration: GLMOptimizationConfiguration,
    offsets_plus_scores: Array,
    *,
    initial_model: Optional[RandomEffectModel] = None,
    normalization: Optional[NormalizationContext] = None,
    variance_computation: VarianceComputationType = VarianceComputationType.NONE,
    dtype=None,
    per_entity_reg_weights=None,
    re_solver: str = "lbfgs",
) -> tuple[RandomEffectModel, RandomEffectTracker]:
    """Fit one GLM per entity over all buckets.

    ``offsets_plus_scores`` is the [N] global array of base offsets plus the other
    coordinates' partial scores (the reference's addScoresToOffsets join becomes a
    gather through bucket.sample_ids).

    ``per_entity_reg_weights`` ({entity_id: l2} or [E] array aligned with
    ``dataset.entity_ids``) overrides the configuration's L2 weight per entity
    — the per-entity regularization the reference envisioned
    (RandomEffectOptimizationProblem.scala:34-37). Entities absent from a dict
    keep the configuration weight.

    ``re_solver`` ("lbfgs" | "direct" | "auto", or a per-bucket tuple of
    "lbfgs"/"direct" — the measured-"auto" plan from
    :func:`measure_auto_solvers`) selects the inner bucket solver
    (optimization/normal_equations.py): direct Gram/Cholesky Newton solves
    instead of the configured quasi-Newton loop. Default keeps the bitwise
    status quo.
    """
    task = TaskType(task)
    loss = loss_for_task(task)
    opt_type = OptimizerType(configuration.optimizer_config.optimizer_type)
    if opt_type in (OptimizerType.TRON, OptimizerType.NEWTON) and not loss.has_hessian:
        raise ValueError(f"{opt_type.value} requires a twice-differentiable loss")
    l2 = configuration.l2_weight
    l1 = configuration.l1_weight
    variance_computation = VarianceComputationType(variance_computation)

    E, K_all = dataset.n_entities, dataset.max_k
    if dtype is None:
        dtype = dataset.sample_vals.dtype
    coeffs_sharding = getattr(dataset, "coeffs_sharding", None)
    # mesh backend: the per-entity coefficient table lives entity-sharded (the
    # reference never collects RandomEffectModel either, RandomEffectModel.scala:
    # 36-304); its height is padded to the mesh multiple with always-zero rows
    table_rows = getattr(dataset, "coeffs_rows", None) or E

    def _place(table):
        if table.shape[0] < table_rows:
            table = jnp.concatenate(
                [table, jnp.zeros((table_rows - table.shape[0], K_all), dtype=table.dtype)]
            )
        if coeffs_sharding is not None:
            table = jax.device_put(table, coeffs_sharding)
        return table

    coeffs_global = _place(jnp.zeros((E, K_all), dtype=dtype))

    # Warm start: re-layout the initial model into this dataset's entity-row and
    # slot order (aligned_to is a no-op when layouts already match — the common
    # case inside coordinate descent).
    if initial_model is not None:
        coeffs_global = _place(initial_model.aligned_to(dataset).coeffs.astype(dtype))

    variances_global = (
        _place(jnp.zeros((E, K_all), dtype=dtype))
        if variance_computation != VarianceComputationType.NONE
        else None
    )

    # per-entity L2 table, row-aligned with the coefficient table
    l2_rows = build_l2_rows(dataset, l2, per_entity_reg_weights, dtype, table_rows)

    # tracker inputs stay DEVICE arrays inside the loop: a host sync per bucket
    # (np.asarray) would block dispatch of the next bucket's solve; everything
    # transfers in one device_get after the last bucket is enqueued
    reasons_parts, iters_parts, evals_parts, rows_parts = [], [], [], []

    # re_bucket_solver is lru-cached, so per-bucket resolution costs a dict
    # hit; a tuple plan (measured "auto" — measure_auto_solvers) picks the
    # solver per bucket, a plain string keeps one solver for all buckets
    solver_plan = _bucket_solver_plan(re_solver, len(dataset.buckets))
    for bucket, bucket_solver in zip(dataset.buckets, solver_plan):
        solve = re_bucket_solver(
            task, configuration.optimizer_config, bool(l1), variance_computation,
            bucket_solver,
        )
        S, K = bucket.shape
        proj_b = dataset.proj_indices[bucket.entity_rows, :K]
        factors, shifts, icpt_mask = _gather_norm_vectors(normalization, proj_b, dtype)

        off_b = jnp.take(offsets_plus_scores, jnp.maximum(bucket.sample_ids, 0), axis=0)
        off_b = jnp.where(bucket.sample_ids >= 0, off_b, 0.0).astype(dtype)

        init_b = coeffs_global[bucket.entity_rows, :K]
        if normalization is not None and not normalization.is_identity:
            init_b = _to_transformed(init_b, factors, shifts, icpt_mask)

        w_b, reasons_b, iters_b, evals_b, var_b = solve(
            bucket.X,
            bucket.labels,
            bucket.weights,
            off_b,
            init_b,
            jnp.take(l2_rows, jnp.minimum(bucket.entity_rows, l2_rows.shape[0] - 1)),
            jnp.asarray(l1 or 0.0, dtype=dtype),
        )

        if normalization is not None and not normalization.is_identity:
            w_b = _to_original(w_b, factors, shifts, icpt_mask)
            if variances_global is not None and factors is not None:
                # w = w' * factor  =>  Var(w) = Var(w') * factor^2 (diagonal
                # approximation: the intercept's shift cross-covariances are not
                # tracked, matching the reference's diagonal variance output).
                var_b = var_b * factors**2

        # mesh-placed buckets pad the entity axis with rows == E: their scatters
        # are dropped by XLA's out-of-bounds-update semantics and they are
        # excluded from the tracker below
        coeffs_global = coeffs_global.at[bucket.entity_rows, :K].set(w_b)
        if variances_global is not None:
            variances_global = variances_global.at[bucket.entity_rows, :K].set(var_b)
        reasons_parts.append(reasons_b)
        iters_parts.append(iters_b)
        evals_parts.append(evals_b)
        rows_parts.append(bucket.entity_rows)

    if table_rows > E:
        # bucket padding targets row E, which is in-bounds when the table height
        # is padded — keep every padding row identically zero
        coeffs_global = coeffs_global.at[E:].set(0.0)
        if variances_global is not None:
            variances_global = variances_global.at[E:].set(0.0)
    if coeffs_sharding is not None:
        coeffs_global = jax.device_put(coeffs_global, coeffs_sharding)
        if variances_global is not None:
            variances_global = jax.device_put(variances_global, coeffs_sharding)

    if reasons_parts:
        # the one host sync for the tracker, after every bucket solve is queued
        reasons_h, iters_h, evals_h, rows_h = jax.device_get(
            (reasons_parts, iters_parts, evals_parts, rows_parts)
        )
        real = [np.asarray(r) < E for r in rows_h]
        reasons_all = np.concatenate([np.asarray(a)[m] for a, m in zip(reasons_h, real)])
        iters_all = np.concatenate([np.asarray(a)[m] for a, m in zip(iters_h, real)])
        evals_real = [None if a is None else np.asarray(a)[m] for a, m in zip(evals_h, real)]
    else:
        reasons_all = iters_all = np.zeros(0, np.int32)
        evals_real = None
    tracker = RandomEffectTracker.from_arrays(
        reasons_all, iters_all, evals_real, [b.shape[0] for b in dataset.buckets]
    )
    model = RandomEffectModel(
        re_type=dataset.re_type,
        feature_shard_id=dataset.feature_shard_id,
        task=task,
        entity_ids=dataset.entity_ids,
        coeffs=coeffs_global,
        proj_indices=dataset.proj_indices,
        variances=variances_global,
        projector=dataset.projector,
    )
    return model, tracker


# ----------------------------------------------------------- active-set mode
# The continuous-training delta pass (photon_ml_tpu/continuous/): re-solve
# ONLY the entities in an active set, warm-started from the previous
# generation's table. Active lanes are GATHERED out of each bucket into a
# pow2-padded sub-bucket (bounding the compiled shape family across deltas),
# solved by the same cached vmapped solver body the full per-bucket loop and
# the PR 4 single-program path share (solver_cache._re_bucket_solve_fn — the
# three paths are bitwise interchangeable per lane), and SCATTERED back into
# the full coefficient table. Untouched rows are never rewritten: jax arrays
# are immutable, so the returned table holds the previous generation's bits
# for every inactive entity by construction.


@dataclasses.dataclass
class ActiveSetStats:
    """What one delta update actually solved (the bench's active_set_fraction
    numerator/denominator and the honesty record for the paper trail)."""

    n_entities: int  # dataset entities (the denominator)
    n_active: int  # entities selected for re-solve
    n_solved_lanes: int  # vmapped lanes dispatched (incl. pow2 padding)
    buckets_touched: int
    buckets_total: int

    @property
    def active_fraction(self) -> float:
        return self.n_active / self.n_entities if self.n_entities else 0.0


def train_random_effect_delta(
    dataset: RandomEffectDataset,
    task: TaskType,
    configuration: GLMOptimizationConfiguration,
    offsets_plus_scores: Array,
    prev_model: RandomEffectModel,
    active_mask: np.ndarray,
    *,
    normalization: Optional[NormalizationContext] = None,
    variance_computation: VarianceComputationType = VarianceComputationType.NONE,
    dtype=None,
    per_entity_reg_weights=None,
    min_entities_pad: int = 8,
    re_solver: str = "lbfgs",
) -> tuple[RandomEffectModel, RandomEffectTracker, ActiveSetStats]:
    """Active-set counterpart of :func:`train_random_effect`.

    ``active_mask`` is a host bool array over ``dataset.entity_ids`` rows;
    only masked entities are re-solved (over their FULL accumulated data —
    the blockwise-update contract of the distributed-CD literature), everything
    else keeps the previous generation's coefficients bit for bit.
    ``prev_model`` must cover the dataset's entities (align it first /
    build the dataset with ``entity_order`` so growth appends at the tail).

    Mesh-sharded datasets are supported: the gathered active sub-buckets are
    re-placed under the dataset's entity sharding (lane counts padded to a
    mesh multiple), the warm-start table is padded/placed under
    ``coeffs_sharding``, and padding lanes scatter to the table HEIGHT (out
    of bounds on any backend — dropped), so inactive entities keep the
    previous generation's shard contents bit for bit.
    """
    task = TaskType(task)
    loss = loss_for_task(task)
    opt_type = OptimizerType(configuration.optimizer_config.optimizer_type)
    if opt_type in (OptimizerType.TRON, OptimizerType.NEWTON) and not loss.has_hessian:
        raise ValueError(f"{opt_type.value} requires a twice-differentiable loss")
    l2 = configuration.l2_weight
    l1 = configuration.l1_weight
    variance_computation = VarianceComputationType(variance_computation)
    variance_on = variance_computation != VarianceComputationType.NONE

    E, K_all = dataset.n_entities, dataset.max_k
    if dtype is None:
        dtype = dataset.sample_vals.dtype
    active_mask = np.asarray(active_mask, dtype=bool)
    if active_mask.shape != (E,):
        raise ValueError(
            f"active_mask shape {active_mask.shape} != ({E},) entities"
        )

    coeffs_sharding = getattr(dataset, "coeffs_sharding", None)
    table_rows = getattr(dataset, "coeffs_rows", None) or E
    mesh_multiple = (
        coeffs_sharding.mesh.devices.size if coeffs_sharding is not None else 1
    )

    def _place(table):
        # mesh backend: pad the table height to the device multiple (rows
        # >= E are always-zero padding) and pin the entity sharding — same
        # discipline as train_random_effect
        from photon_ml_tpu.parallel.mesh import pad_rows_and_place

        return pad_rows_and_place(table, table_rows, coeffs_sharding)

    aligned = prev_model.aligned_to(dataset)
    coeffs_global = aligned.coeffs
    if coeffs_global.dtype != dtype:
        coeffs_global = coeffs_global.astype(dtype)
    coeffs_global = _place(coeffs_global)
    if variance_on and aligned.variances is None and not active_mask.all():
        # only active entities receive solved variances; everything else
        # would export variance exactly 0.0, which reads as infinite
        # confidence (see coordinate_descent._strip_variances)
        raise ValueError(
            "variance computation is enabled but the warm-start model "
            "carries no variances: inactive entities would keep variance "
            "0.0 in the exported model. Run one variance-bearing full pass "
            "first (or disable variance computation for delta passes)."
        )
    if variance_on:
        variances_global = _place(
            jnp.zeros((E, K_all), dtype=dtype)
            if aligned.variances is None
            else aligned.variances.astype(dtype)
        )
    else:
        variances_global = None

    l2_rows = build_l2_rows(dataset, l2, per_entity_reg_weights, dtype, E)
    l1_arr = jnp.asarray(l1 or 0.0, dtype=dtype)
    solver_plan = _bucket_solver_plan(re_solver, len(dataset.buckets))

    reasons_parts, iters_parts, real_counts = [], [], []
    scatter_rows_parts, coef_updates, var_updates = [], [], []
    n_active = int(active_mask.sum())
    n_lanes = 0
    buckets_touched = 0
    for bucket, bucket_solver in zip(dataset.buckets, solver_plan):
        rows_host = np.asarray(bucket.entity_rows)
        real = rows_host < E  # mesh-padding rows never appear here, but be safe
        sel = np.flatnonzero(real & active_mask[np.minimum(rows_host, E - 1)])
        if len(sel) == 0:
            continue
        buckets_touched += 1
        solve = re_bucket_solver(
            task, configuration.optimizer_config, bool(l1), variance_computation,
            bucket_solver,
        )
        S, K = bucket.shape
        Eb = bucket.n_entities
        if len(sel) == Eb:
            # every lane active: the bucket's arrays ARE the solve inputs —
            # identical shapes to the full path, no gather/copy at all
            scatter_rows = rows_host
            n_real = Eb
            rows_b = rows_host
            X_b, y_b = bucket.X, bucket.labels
            w_b, sid_b = bucket.weights, bucket.sample_ids
        else:
            pad_to = min(_next_pow2(len(sel), min_entities_pad), Eb)
            if mesh_multiple > 1:
                # entity-sharded sub-buckets need a device-divisible lane
                # count; the placed bucket's Eb is already a mesh multiple,
                # so the cap stays valid
                pad_to = min(-(-pad_to // mesh_multiple) * mesh_multiple, Eb)
            # pow2-pad the lane count with DUPLICATES of the first active lane
            # (a twin solve converges like its sibling — far fewer wasted
            # iterations than an artificial zero-data lane) whose scatter is
            # dropped via an out-of-bounds row (the table HEIGHT: row E is a
            # real always-zero padding row on mesh-padded tables, table_rows
            # is out of bounds everywhere)
            idx = np.concatenate([sel, np.full(pad_to - len(sel), sel[0])])
            scatter_rows = np.concatenate(
                [
                    rows_host[sel],
                    np.full(
                        pad_to - len(sel), table_rows, dtype=rows_host.dtype
                    ),
                ]
            )
            n_real = len(sel)
            rows_b = rows_host[idx]  # in-bounds rows (duplicates for padding)
            if isinstance(bucket.X, np.ndarray):
                # host-backed bucket (the working-set tier re-points
                # dataset.buckets at host arrays): gather ON HOST and move
                # only the active sub-bucket — jnp.take would transfer the
                # whole bucket to device first
                X_b = jnp.asarray(np.ascontiguousarray(bucket.X[idx]))
                y_b = jnp.asarray(np.ascontiguousarray(bucket.labels[idx]))
                w_b = jnp.asarray(np.ascontiguousarray(bucket.weights[idx]))
                sid_b = jnp.asarray(
                    np.ascontiguousarray(bucket.sample_ids[idx])
                )
            else:
                idx_dev = jnp.asarray(idx.astype(np.int32))
                X_b = jnp.take(bucket.X, idx_dev, axis=0)
                y_b = jnp.take(bucket.labels, idx_dev, axis=0)
                w_b = jnp.take(bucket.weights, idx_dev, axis=0)
                sid_b = jnp.take(bucket.sample_ids, idx_dev, axis=0)
            if coeffs_sharding is not None:
                # re-place the gathered sub-bucket under the entity sharding:
                # the vmapped solve then partitions lane-parallel exactly like
                # the full path's buckets
                from photon_ml_tpu.parallel.mesh import batch_sharding

                mesh = coeffs_sharding.mesh
                X_b = jax.device_put(X_b, batch_sharding(mesh, ndim=3))
                y_b = jax.device_put(y_b, batch_sharding(mesh, ndim=2))
                w_b = jax.device_put(w_b, batch_sharding(mesh, ndim=2))
                sid_b = jax.device_put(sid_b, batch_sharding(mesh, ndim=2))
        n_lanes += len(rows_b)

        proj_b = dataset.proj_indices[jnp.asarray(rows_b), :K]
        factors, shifts, icpt_mask = _gather_norm_vectors(normalization, proj_b, dtype)

        off_b = jnp.take(offsets_plus_scores, jnp.maximum(sid_b, 0), axis=0)
        off_b = jnp.where(sid_b >= 0, off_b, 0.0).astype(dtype)

        if isinstance(coeffs_global, np.ndarray):
            # host-authoritative table (working-set model): gather the warm
            # rows on host, move only the [L, K] slice
            init_b = jnp.asarray(np.ascontiguousarray(coeffs_global[rows_b, :K]))
        else:
            init_b = coeffs_global[jnp.asarray(rows_b), :K]
        if normalization is not None and not normalization.is_identity:
            init_b = _to_transformed(init_b, factors, shifts, icpt_mask)

        coefs_b, reasons_b, iters_b, _evals_b, var_b = solve(
            X_b,
            y_b,
            w_b,
            off_b,
            init_b,
            jnp.take(l2_rows, jnp.minimum(jnp.asarray(rows_b), l2_rows.shape[0] - 1)),
            l1_arr,
        )

        if normalization is not None and not normalization.is_identity:
            coefs_b = _to_original(coefs_b, factors, shifts, icpt_mask)
            if variances_global is not None and factors is not None:
                var_b = var_b * factors**2

        scatter_rows_parts.append(scatter_rows)
        coef_updates.append(coefs_b)
        if variances_global is not None:
            var_updates.append(var_b)
        reasons_parts.append(reasons_b)
        iters_parts.append(iters_b)
        real_counts.append(n_real)

    if coef_updates:
        # ONE O(E x K_all) table-copy scatter per pass, not one per touched
        # bucket: pad each bucket's [L, K] block to K_all (an active entity's
        # columns beyond its bucket width are zero in the warm table — the
        # same invariant the full path's [:K] scatter relies on) and apply a
        # single concatenated row scatter. Padding lanes scatter to row E:
        # out of bounds, dropped — inactive entities keep the previous
        # generation's bits untouched.
        rows_dev = jnp.asarray(
            np.concatenate(scatter_rows_parts).astype(np.int32)
        )

        def _pad_blocks(blocks):
            return jnp.concatenate(
                [
                    b
                    if b.shape[1] == K_all
                    else jnp.pad(b, ((0, 0), (0, K_all - b.shape[1])))
                    for b in blocks
                ],
                axis=0,
            )

        if isinstance(coeffs_global, np.ndarray):
            # host-authoritative table (working-set model): D2H the solved
            # blocks and scatter on host — the full table never goes up.
            # Padding lanes carry out-of-bounds rows; filter instead of drop.
            rows_np = np.concatenate(scatter_rows_parts).astype(np.int64)
            keep = rows_np < coeffs_global.shape[0]
            blocks = np.asarray(jax.device_get(_pad_blocks(coef_updates)))
            coeffs_global = np.array(coeffs_global, copy=True)
            coeffs_global[rows_np[keep]] = blocks[keep].astype(
                coeffs_global.dtype
            )
            if variances_global is not None:
                vblocks = np.asarray(jax.device_get(_pad_blocks(var_updates)))
                variances_global = np.array(variances_global, copy=True)
                variances_global[rows_np[keep]] = vblocks[keep].astype(
                    variances_global.dtype
                )
        else:
            coeffs_global = coeffs_global.at[rows_dev].set(
                _pad_blocks(coef_updates)
            )
            if variances_global is not None:
                variances_global = variances_global.at[rows_dev].set(
                    _pad_blocks(var_updates)
                )
        if coeffs_sharding is not None:
            # pin the table sharding after the scatter so the exported model
            # (and the next delta's warm start) stays entity-sharded
            coeffs_global = jax.device_put(coeffs_global, coeffs_sharding)
            if variances_global is not None:
                variances_global = jax.device_put(
                    variances_global, coeffs_sharding
                )

    if reasons_parts:
        reasons_h, iters_h = jax.device_get((reasons_parts, iters_parts))
        reasons_all = np.concatenate(
            [np.asarray(a)[:k] for a, k in zip(reasons_h, real_counts)]
        )
        iters_all = np.concatenate(
            [np.asarray(a)[:k] for a, k in zip(iters_h, real_counts)]
        )
    else:
        reasons_all = iters_all = np.zeros(0, np.int32)
    tracker = RandomEffectTracker.from_arrays(reasons_all, iters_all)
    if variance_on and aligned.variances is None and not reasons_parts:
        variances_global = None  # nothing solved: don't invent a zero table
    model = RandomEffectModel(
        re_type=dataset.re_type,
        feature_shard_id=dataset.feature_shard_id,
        task=task,
        entity_ids=dataset.entity_ids,
        coeffs=coeffs_global,
        proj_indices=dataset.proj_indices,
        variances=variances_global,
        projector=dataset.projector,
    )
    stats = ActiveSetStats(
        n_entities=E,
        n_active=n_active,
        n_solved_lanes=n_lanes,
        buckets_touched=buckets_touched,
        buckets_total=len(dataset.buckets),
    )
    return model, tracker, stats


@functools.partial(jax.jit, static_argnums=0)
def _bucket_gradient_norms(loss, X, y, w, off, coefs, l2) -> Array:
    """Per-entity L2 norm of the regularized subproblem gradient at ``coefs``:
    g_e = X_e^T (w ⊙ dl/dz) + l2_e · w_e over one [E, S, K] bucket."""
    z = jnp.einsum("esk,ek->es", X, coefs) + off
    _, dz = loss.loss_and_dz(z, y)
    g = jnp.einsum("es,esk->ek", w * dz, X) + l2[:, None] * coefs
    return jnp.sqrt(jnp.sum(g * g, axis=-1))


def random_effect_gradient_norms(
    dataset: RandomEffectDataset,
    model: RandomEffectModel,
    offsets_plus_scores: Array,
    task: TaskType,
    *,
    l2: float = 0.0,
    per_entity_reg_weights=None,
    normalization: Optional[NormalizationContext] = None,
    dtype=None,
) -> np.ndarray:
    """Host [E] array of per-entity gradient norms of the random-effect
    subproblem at the model's current coefficients — the active-set screening
    signal (continuous/active_set.py): an entity whose gradient norm exceeds
    the caller's threshold has drifted from its optimum (e.g. its residual
    moved because OTHER coordinates updated) and earns a re-solve even
    without new rows. One vmapped forward+backward per bucket shape class —
    a single cheap pass, no solver iterations."""
    task = TaskType(task)
    loss = loss_for_task(task)
    E = dataset.n_entities
    if dtype is None:
        dtype = dataset.sample_vals.dtype
    aligned = model.aligned_to(dataset)
    coeffs = aligned.coeffs
    if coeffs.dtype != dtype:
        coeffs = coeffs.astype(dtype)
    l2_rows = build_l2_rows(dataset, l2, per_entity_reg_weights, dtype, E)
    norms = np.zeros(E, dtype=np.float64)
    parts, rows_parts = [], []
    for bucket in dataset.buckets:
        rows_host = np.asarray(bucket.entity_rows)
        S, K = bucket.shape
        proj_b = dataset.proj_indices[bucket.entity_rows, :K]
        factors, shifts, icpt_mask = _gather_norm_vectors(normalization, proj_b, dtype)
        off_b = jnp.take(offsets_plus_scores, jnp.maximum(bucket.sample_ids, 0), axis=0)
        off_b = jnp.where(bucket.sample_ids >= 0, off_b, 0.0).astype(dtype)
        w_init = coeffs[bucket.entity_rows, :K]
        if normalization is not None and not normalization.is_identity:
            w_init = _to_transformed(w_init, factors, shifts, icpt_mask)
        g = _bucket_gradient_norms(
            loss,
            bucket.X,
            bucket.labels,
            bucket.weights,
            off_b,
            w_init,
            jnp.take(l2_rows, jnp.minimum(bucket.entity_rows, l2_rows.shape[0] - 1)),
        )
        parts.append(g)
        rows_parts.append(rows_host)
    if parts:
        parts_h = jax.device_get(parts)
        for g_h, rows_h in zip(parts_h, rows_parts):
            real = rows_h < E
            norms[rows_h[real]] = np.asarray(g_h)[real]
    return norms
