"""Cross-call jit cache for GLM solves.

The reference re-uses one physical plan per optimization problem and mutates the
regularization weight across sweep configurations
(DistributedOptimizationProblem.updateRegularizationWeight:64-75). The XLA
analog: compile ONE program per *static* solver configuration — (task,
OptimizerConfig, which optional terms exist, variance type) — and pass
everything that varies across coordinate-descent iterations, sweep
configurations and tests as traced arguments (data, x0, l2/l1 weights, bounds,
normalization vectors). Without this cache every `minimize` call re-traces its
`lax.while_loop` from a fresh closure, which dominated both training wall-clock
and the test suite.

Solvers are cached at module level with `functools.lru_cache`; jax.jit then
adds its own per-input-shape cache underneath, so the combined key is
(static config) x (array shapes/dtypes/shardings) — exactly the reuse surface.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from photon_ml_tpu.function.losses import loss_for_task
from photon_ml_tpu.function.objective import GLMObjective
from photon_ml_tpu.optimization import normal_equations
from photon_ml_tpu.optimization.common import OptimizerConfig, OptResult
from photon_ml_tpu.optimization.factory import build_minimizer
from photon_ml_tpu.optimization.precision import FLOAT32, PrecisionPolicy
from photon_ml_tpu.types import OptimizerType, TaskType, VarianceComputationType

Array = jnp.ndarray


def compute_variances(obj: GLMObjective, data, coef, l2, variance, dtype):
    """SIMPLE: 1/diag(H); FULL: diag(H^-1) via Cholesky
    (DistributedOptimizationProblem.computeVariances:84-108). The single shared
    implementation behind glm_solver, re_bucket_solver and
    GLMOptimizationProblem.compute_variances. The unit-diagonal guard keeps the
    Cholesky well-posed for all-zero padding slots (vmapped entity buckets)."""
    variance = VarianceComputationType(variance)
    if variance == VarianceComputationType.SIMPLE:
        diag = obj.hessian_diagonal(data, coef, l2)
        return 1.0 / jnp.where(diag == 0.0, jnp.inf, diag)
    if variance == VarianceComputationType.FULL:
        from photon_ml_tpu.ops import small_linalg

        H = obj.hessian_matrix(data, coef, l2)
        H = H + jnp.diag((jnp.diag(H) == 0.0).astype(H.dtype))
        if H.shape[-1] <= small_linalg.MAX_UNROLL_DIM:
            # per-entity (vmapped) regime: the unrolled factorization avoids
            # the batched-Cholesky custom-call (ROADMAP.md S2)
            return small_linalg.small_spd_inverse_diag(H)
        L = jnp.linalg.cholesky(H)
        eye = jnp.eye(H.shape[0], dtype=H.dtype)
        Linv = jax.scipy.linalg.solve_triangular(L, eye, lower=True)
        return jnp.diag(Linv.T @ Linv)
    return jnp.zeros((0,), dtype=dtype)


@functools.lru_cache(maxsize=None)
def glm_solver(
    task: TaskType,
    opt_config: OptimizerConfig,
    has_l1: bool,
    has_lower: bool,
    has_upper: bool,
    variance: VarianceComputationType,
    allow_fused: bool = True,
):
    """Jitted ``solve(data, x0, l2, l1, lower, upper, norm) -> (OptResult, variances)``.

    Absent optional terms (decided by the static flags) still occupy an argument
    slot with a dummy zeros array — jit signatures are fixed; dead arguments are
    eliminated by XLA.
    """
    task = TaskType(task)
    loss = loss_for_task(task)
    minimize = build_minimizer(opt_config)
    use_hvp = OptimizerType(opt_config.optimizer_type) == OptimizerType.TRON
    use_hess = OptimizerType(opt_config.optimizer_type) == OptimizerType.NEWTON
    variance = VarianceComputationType(variance)

    # the inner functions of this module are NAMED for what they are: XLA
    # calls the compiled module jit_<name>, and that is how a profiler trace
    # (and the benchmark's breakdown) tells one program from another
    def fe_solve(data, x0, l2, l1, lower, upper, norm):
        obj = GLMObjective(loss, norm, allow_fused=allow_fused)

        def vg(w):
            return obj.value_and_gradient(data, w, l2)

        kwargs = {}
        if use_hvp:
            kwargs["hvp"] = lambda w, v: obj.hessian_vector(data, w, v, l2)
        if use_hess:
            kwargs["hess"] = lambda w: obj.hessian_matrix(data, w, l2)
        if has_l1:
            kwargs["l1_weight"] = l1
        if has_lower:
            kwargs["lower_bounds"] = lower
        if has_upper:
            kwargs["upper_bounds"] = upper
        with jax.named_scope("fe.solve"):
            result = minimize(vg, x0, **kwargs)
        variances = compute_variances(
            obj, data, result.coefficients, l2, variance, x0.dtype
        )
        return result, variances

    return jax.jit(fe_solve)


def _masked_value_and_grad(vg, active):
    """The population early-exit lever: wrap a value-and-gradient so an
    INACTIVE lane's objective reads exactly stationary (f=0, g=0). Every
    minimizer's zero-gradient init check (``reason0`` in lbfgs/owlqn/tron/
    newton/lbfgsb) then converges the lane in ZERO iterations, so a vmapped
    while_loop's trip count tracks the slowest ACTIVE lane — frozen lanes
    still ride the batched body (vmap computes all lanes every trip) but no
    longer extend it. Callers must select-freeze the lane's outputs to its
    previous state; the masked solve's job is only to stop burning trips.
    OWLQN needs the L1 weight masked too (the pseudo-gradient of a zero
    smooth gradient is still ``l1*sign(x)``) — see the call sites."""

    def masked(w):
        f, g = vg(w)
        return (
            jnp.where(active, f, jnp.zeros((), f.dtype)),
            jnp.where(active, g, jnp.zeros_like(g)),
        )

    return masked


def _re_bucket_solve_fn(
    task: TaskType,
    opt_config: OptimizerConfig,
    has_l1: bool,
    variance: VarianceComputationType,
    re_solver: str = "lbfgs",
    with_active: bool = False,
):
    """Unjitted vmapped bucket solve shared by ``re_bucket_solver`` (one jit
    per bucket) and ``re_coordinate_update_program`` (every bucket chained in
    one trace) — one body, so the two paths stay bitwise interchangeable.

    ``re_solver`` selects the inner minimizer per bucket SHAPE at trace time
    (optimization/normal_equations.py): ``"direct"`` replaces the configured
    quasi-Newton loop with batched Gram/Cholesky Newton solves, ``"auto"``
    does so for the small-K buckets the roofline says dominate, ``"lbfgs"``
    (default) keeps the configured optimizer — the bitwise status quo.

    ``with_active=True`` appends a broadcast per-lane ``active`` flag to the
    solve signature (the population early-exit path): inactive lanes see a
    masked stationary objective and solve in zero iterations, and report
    zero iterations. Default False keeps the existing program signatures
    untouched.

    Returns per lane ``(coefficients, reason, iterations, evaluations,
    variances)``; ``evaluations`` is the minimiser's own count of
    value-and-gradient evaluations (``OptResult.evaluations``), None from a
    minimiser that does not count them."""
    task = TaskType(task)
    loss = loss_for_task(task)
    minimize = build_minimizer(opt_config)
    use_hvp = OptimizerType(opt_config.optimizer_type) == OptimizerType.TRON
    use_hess = OptimizerType(opt_config.optimizer_type) == OptimizerType.NEWTON
    variance = VarianceComputationType(variance)
    re_solver = normal_equations.validate_re_solver(re_solver, has_l1)

    from photon_ml_tpu.data.dataset import LabeledData
    from photon_ml_tpu.data.matrix import DenseDesignMatrix

    def lane_counts(res, active):
        iters, evals = res.iterations, res.evaluations
        if active is not None:
            iters = jnp.where(active, iters, jnp.zeros_like(iters))
            if evals is not None:
                evals = jnp.where(active, evals, jnp.zeros_like(evals))
        return iters, evals

    def re_bucket_solve(Xe, ye, we, oe, w0, l2, l1, active=None):
        data = LabeledData(X=DenseDesignMatrix(Xe), labels=ye, offsets=oe, weights=we)
        obj = GLMObjective(loss, allow_fused=False)  # vmapped: no pallas path

        if normal_equations.use_direct(
            re_solver, k=Xe.shape[-1], has_l1=has_l1
        ):
            # reduced-precision feature storage floors the convergence
            # tolerance at the storage dtype's epsilon: objective evaluations
            # carry storage-level noise, and Newton steps chasing an f32-grade
            # tolerance through it just burn data reads on reverts
            tolerance = opt_config.tolerance
            if Xe.dtype != w0.dtype:
                tolerance = max(tolerance, float(jnp.finfo(Xe.dtype).eps))
            res = normal_equations.minimize_direct(
                obj,
                data,
                w0,
                l2,
                quadratic=task == TaskType.LINEAR_REGRESSION,
                tolerance=tolerance,
                active=active,
            )
            var = compute_variances(obj, data, res.coefficients, l2, variance, w0.dtype)
            iters, evals = lane_counts(res, active)
            return res.coefficients, res.convergence_reason, iters, evals, var

        def vg(w):
            return obj.value_and_gradient(data, w, l2)

        if active is not None:
            vg = _masked_value_and_grad(vg, active)

        kwargs = {}
        if use_hvp:
            kwargs["hvp"] = lambda w, v: obj.hessian_vector(data, w, v, l2)
        if use_hess:
            kwargs["hess"] = lambda w: obj.hessian_matrix(data, w, l2)
        if has_l1:
            # the OWLQN pseudo-gradient of a masked (zero) smooth gradient is
            # l1*sign(x) — a frozen lane would still iterate; zero its L1 too
            kwargs["l1_weight"] = (
                l1 if active is None else jnp.where(active, l1, jnp.zeros_like(l1))
            )
        res = minimize(vg, w0, **kwargs)
        var = compute_variances(obj, data, res.coefficients, l2, variance, w0.dtype)
        iters, evals = lane_counts(res, active)
        return res.coefficients, res.convergence_reason, iters, evals, var

    if with_active:
        return jax.vmap(re_bucket_solve, in_axes=(0, 0, 0, 0, 0, 0, None, None))
    return jax.vmap(re_bucket_solve, in_axes=(0, 0, 0, 0, 0, 0, None))


@functools.lru_cache(maxsize=None)
def re_bucket_solver(
    task: TaskType,
    opt_config: OptimizerConfig,
    has_l1: bool,
    variance: VarianceComputationType,
    re_solver: str = "lbfgs",
):
    """Jitted vmapped per-entity bucket solve:
    ``solve(X, y, w, offsets, w0, l2, l1) -> (coefs, reasons, iters, evals,
    variances)``
    with X [E, S, K], l2 a PER-ENTITY [E] vector (the reference only envisioned
    per-entity regularization weights, RandomEffectOptimizationProblem.scala:
    34-37 — here each entity's solve traces its own weight) and l1 broadcast —
    the executor-local random-effect hot loop of RandomEffectCoordinate.scala:
    109-127 as one XLA program per bucket shape class."""
    return jax.jit(_re_bucket_solve_fn(task, opt_config, has_l1, variance, re_solver))


def _re_coordinate_update_fn(
    task: TaskType,
    opt_config: OptimizerConfig,
    has_l1: bool,
    variance: VarianceComputationType,
    n_entities: int,
    re_solver: str = "lbfgs",
    precision: PrecisionPolicy = FLOAT32,
    with_active: bool = False,
):
    """Unjitted whole-coordinate update body shared by
    ``re_coordinate_update_program`` (one model) and
    ``re_population_update_program`` (a leading population axis vmapped over
    it) — one body, so the two programs stay semantically interchangeable
    per lane.

    ``sample_slots`` (the last argument of the returned body, default None)
    says what the ``[N]`` training score is computed from, a trace-time fact
    of the call that ``algorithm/random_effect.bucket_score_slots`` decides
    for the coordinate and the population trainer. None: the body calls
    ``random_effect_view_score(table, *view)`` on the updated table, whose
    ``[N, K]`` intermediate was two thirds of the update on a v5e (PERF.md).
    The dataset's ``[N]`` slot index: the body scores each bucket right after
    its solve, ``sum_k X_b[e, s, k] * w_b[e, k]`` (scope ``re.bucket_score``:
    contiguous reads of the block the solve has just streamed, a broadcast
    where the view gathers table rows, no column gather), and brings the
    block slots back to the sample axis with ONE gather of ``N`` scalars
    (scope ``re.score_gather``); ``view`` is then not read. Slots are given
    only for raw float32 blocks, where the bucket score is the view score of
    the returned table (what a resume recomputes); the body refuses them
    under a reduced policy or with normalization tables.

    ``precision`` (optimization/precision.py) splits STORAGE from
    ACCUMULATION dtypes: under a reduced policy the donated coefficient/
    variance tables and the bucket/view feature arrays live in bf16/f16 HBM
    (the caller supplies them pre-cast — see
    ``algorithm/random_effect.update_program_data``) while every solve,
    normalization conversion and score upcasts to f32 in-register (XLA fuses
    the converts into the consuming gathers/contractions, so only
    storage-width bytes cross HBM). The reference f32 policy makes every
    cast an identity, preserving the bitwise parity contract with the
    per-bucket path.

    ``with_active=True`` (the population early-exit form) appends a scalar
    ``active`` argument after ``l1``: a frozen (inactive) lane's bucket
    solves run zero iterations (masked stationary objective — see
    ``_masked_value_and_grad``) and the lane's outputs are select-frozen to
    the PREVIOUS table/score/variances bit for bit. The explicit select
    matters: a zero-iteration solve alone would round-trip the warm start
    through the normalization space conversion, which is not a bitwise
    identity. The returned per-lane ``ok`` flag reports True for frozen
    lanes (carrying committed state is not a reject), and the returned
    iteration counts are zero there."""
    # a per-bucket tuple plan (measured re_solver="auto") builds one solve
    # body per DISTINCT solver and indexes it per bucket at trace time — the
    # whole plan is part of the lru_cache key, so a changed plan is a new
    # program, never a silent retrace of an old one
    if isinstance(re_solver, tuple):
        solve_bodies = {
            s: _re_bucket_solve_fn(task, opt_config, has_l1, variance, s, with_active)
            for s in sorted(set(re_solver))
        }
        solve_plan = tuple(solve_bodies[s] for s in re_solver)
    else:
        solve_plan = None
        solve = _re_bucket_solve_fn(
            task, opt_config, has_l1, variance, re_solver, with_active
        )
    reduced = not precision.is_reference

    def update_core(
        coeffs_prev, score_prev, var_prev, offsets_plus_scores, l2_rows, l1,
        buckets, norm_tables, view, sample_slots=None, active=None,
    ):
        from photon_ml_tpu.algorithm.random_effect import _to_original, _to_transformed
        from photon_ml_tpu.models.game import random_effect_view_score

        coeffs = coeffs_prev
        variances = var_prev
        if sample_slots is not None and (
            reduced or any(tbl is not None for tbl in norm_tables)
        ):
            # algorithm/random_effect.bucket_score_slots: either would score
            # something else than the stored table's view score
            raise ValueError(
                "sample_slots: the bucket score is for raw float32 blocks; "
                "reduced precision and normalized coordinates score the "
                "stored table through the view"
            )
        bucket_scores = []
        # the dtype every solve runs at: the table dtype itself on the
        # reference path (bitwise status quo), f32 under a reduced policy
        solve_dtype = precision.accum_dtype if reduced else coeffs.dtype
        if solve_plan is not None and len(solve_plan) != len(buckets):
            raise ValueError(
                f"per-bucket re_solver plan covers {len(solve_plan)} buckets, "
                f"update traces {len(buckets)}"
            )
        reasons, iters, evals = [], [], []
        for b_i, (bucket, norm_tbl) in enumerate(zip(buckets, norm_tables)):
            solve_b = solve_plan[b_i] if solve_plan is not None else solve
            S, K = bucket.shape
            with jax.named_scope("re.offsets_gather"):
                off_b = jnp.take(
                    offsets_plus_scores, jnp.maximum(bucket.sample_ids, 0), axis=0
                )
                off_b = jnp.where(bucket.sample_ids >= 0, off_b, 0.0).astype(solve_dtype)
            init_b = coeffs[bucket.entity_rows, :K]
            if reduced:
                init_b = init_b.astype(solve_dtype)
            if norm_tbl is not None:
                factors, shifts, icpt_mask = norm_tbl
                init_b = _to_transformed(init_b, factors, shifts, icpt_mask)
            solve_args = (
                # a reduced block upcasts into the solve like every other
                # operand here: left bf16, DenseDesignMatrix's dot rounds the
                # coefficients to bf16, and the population axis makes it a
                # BF16 x BF16 = F32 matrix product that XLA:CPU does not have
                bucket.X.astype(solve_dtype) if reduced else bucket.X,
                bucket.labels,
                bucket.weights,
                off_b,
                init_b,
                jnp.take(l2_rows, jnp.minimum(bucket.entity_rows, l2_rows.shape[0] - 1)),
                l1,
            )
            if with_active:
                solve_args = solve_args + (active,)
            with jax.named_scope("re.bucket_solve"):
                w_b, reasons_b, iters_b, evals_b, var_b = solve_b(*solve_args)
            if sample_slots is not None:
                # the margin of the block's own rows. Multiply-and-sum, not
                # a dot: at default precision a dot runs bfloat16 passes on
                # the TPU's MXU.
                with jax.named_scope("re.bucket_score"):
                    bucket_scores.append(
                        jnp.sum(bucket.X * w_b[:, None, :], axis=-1).reshape(-1)
                    )
            if norm_tbl is not None:
                w_b = _to_original(w_b, factors, shifts, icpt_mask)
                if variances is not None and factors is not None:
                    # Var(w) = Var(w') * factor^2, same diagonal approximation
                    # as the per-bucket path
                    var_b = var_b * factors**2
            if reduced:
                w_b = w_b.astype(coeffs.dtype)
                if variances is not None:
                    var_b = var_b.astype(variances.dtype)
            with jax.named_scope("re.table_scatter"):
                coeffs = coeffs.at[bucket.entity_rows, :K].set(w_b)
                if variances is not None:
                    variances = variances.at[bucket.entity_rows, :K].set(var_b)
            reasons.append(reasons_b)
            iters.append(iters_b)
            evals.append(evals_b)
        if coeffs.shape[0] > n_entities:
            # padded table heights keep every padding row identically zero
            coeffs = coeffs.at[n_entities:].set(0.0)
            if variances is not None:
                variances = variances.at[n_entities:].set(0.0)
        if sample_slots is not None:
            # N scalars through the inverse of the buckets' sample_ids; a
            # sample of no bucket reads the one zero slot past the blocks
            with jax.named_scope("re.score_gather"):
                zero_slot = jnp.zeros((1,), dtype=score_prev.dtype)
                score = jnp.take(
                    jnp.concatenate([*bucket_scores, zero_slot]), sample_slots
                ).astype(score_prev.dtype)
        elif reduced:
            entity_rows, local_cols, vals = view
            # storage-width bytes cross HBM; the multiply-accumulate runs f32
            score = random_effect_view_score(
                coeffs.astype(solve_dtype),
                entity_rows,
                local_cols,
                vals.astype(solve_dtype),
            )
        else:
            score = random_effect_view_score(coeffs, *view)
        # Device-side divergence guard: variances are deliberately excluded
        # (algorithm/coordinate.coefficient_arrays — a singular-Hessian
        # variance failure must not discard a converged mean update).
        with jax.named_scope("re.guard"):
            ok = jnp.isfinite(coeffs).all()
            keep = ok if active is None else jnp.logical_and(ok, active)
            coeffs_out = jnp.where(keep, coeffs, coeffs_prev)
            score_out = jnp.where(keep, score, score_prev)
            var_out = None if variances is None else jnp.where(keep, variances, var_prev)
            if active is not None:
                # a frozen lane carrying its committed state is not a reject
                ok = jnp.logical_or(ok, jnp.logical_not(active))
        return (
            coeffs_out, score_out, var_out, ok,
            tuple(reasons), tuple(iters), tuple(evals),
        )

    if with_active:

        def re_coordinate_update(
            coeffs_prev, score_prev, var_prev, offsets_plus_scores, l2_rows,
            l1, active, buckets, norm_tables, view, sample_slots=None,
        ):
            return update_core(
                coeffs_prev, score_prev, var_prev, offsets_plus_scores,
                l2_rows, l1, buckets, norm_tables, view, sample_slots, active,
            )

        return re_coordinate_update

    def re_coordinate_update(
        coeffs_prev, score_prev, var_prev, offsets_plus_scores, l2_rows, l1,
        buckets, norm_tables, view, sample_slots=None,
    ):
        return update_core(
            coeffs_prev, score_prev, var_prev, offsets_plus_scores, l2_rows,
            l1, buckets, norm_tables, view, sample_slots,
        )

    return re_coordinate_update


@functools.lru_cache(maxsize=None)
def re_coordinate_update_program(
    task: TaskType,
    opt_config: OptimizerConfig,
    has_l1: bool,
    variance: VarianceComputationType,
    n_entities: int,
    re_solver: str = "lbfgs",
    precision: PrecisionPolicy = FLOAT32,
    shardings: tuple = None,
):
    """ONE jitted, donated XLA program for a whole random-effect coordinate
    update: offset gather, every bucket's vmapped solve chained in a single
    trace, normalization space conversion, per-entity-L2 gather, coefficient
    table scatter, padding-row re-zero, the coordinate's ``[N]`` score, and
    the divergence guard's finiteness flag — the per-bucket host loop of
    ``train_random_effect`` collapsed into one dispatch per update.

    ``update(coeffs_prev, score_prev, var_prev, offsets_plus_scores, l2_rows,
    l1, buckets, norm_tables, view, sample_slots=None) -> (coeffs, score,
    variances, ok, reasons_per_bucket, iters_per_bucket, evals_per_bucket)``

    - ``coeffs_prev`` ``[E, K_max]`` / ``score_prev`` ``[N]`` / ``var_prev``
      (``[E, K_max]`` or None) are DONATED: the hot loop stops copying the
      coefficient table once per bucket (the old ``.at[].set`` chain), and
      callers must never touch those buffers again — feed the outputs forward.
    - ``ok`` is the device-side divergence flag: all updated coefficients
      finite. When False the outputs are the donated PREVIOUS table/score/
      variances via ``lax.select`` (``jnp.where``), preserving the host
      guard's reject semantics bit-for-bit without a blocking host read.
    - ``norm_tables``: per bucket, None or the per-entity (factors, shifts,
      intercept-mask) triple from ``precompute_norm_tables`` — gathered ONCE
      per (dataset, normalization), not per update per bucket.
    - ``view``: the dataset's per-sample scoring view (entity rows, local
      cols, vals) — without ``sample_slots`` the score uses the same
      ``random_effect_view_score`` kernel as the eager path (mesh-placed
      datasets, passive rows, normalization, reduced precision).
    - ``sample_slots``: None, or the dataset's ``[N]`` slot index — the score
      then comes from the bucket blocks just solved and one ``[N]`` gather
      (``_re_coordinate_update_fn``; raw float32 blocks on one device) and
      ``view`` is not read.
    - ``re_solver`` / ``precision``: the direct-solve and storage-precision
      levers (normal_equations.py / precision.py); the defaults reproduce
      the bitwise-gated status quo. ``re_solver`` also accepts a per-bucket
      tuple of "lbfgs"/"direct" — the measured-"auto" plan
      (algorithm/random_effect.measure_auto_solvers); the tuple is part of
      this cache's key, so a changed plan resolves a NEW program rather
      than retracing an old one.
    - ``shardings``: None on the host backend; on a mesh, the
      ``(table_sharding, score_sharding)`` NamedSharding pair
      (hashable — part of the cache key). The update body is placement-
      agnostic (GSPMD partitions it from the input shardings: entity-sharded
      bucket solves stay collective-free, the offset/score gathers become
      the [N]/[E,K]-bounded collectives parallel/hlo_guards.py audits); the
      explicit output constraints pin the donated state's shardings so
      iteration N+1 consumes iteration N's buffers with NO resharding
      between updates — the whole point of donating across a descent run.
    """
    update = _re_coordinate_update_fn(
        task, opt_config, has_l1, variance, n_entities, re_solver, precision
    )
    if shardings is not None:
        table_sharding, score_sharding = shardings
        inner_update = update

        def re_coordinate_update(coeffs_prev, score_prev, var_prev, *rest):
            coeffs, score, var, *counts = inner_update(
                coeffs_prev, score_prev, var_prev, *rest
            )
            coeffs = jax.lax.with_sharding_constraint(coeffs, table_sharding)
            score = jax.lax.with_sharding_constraint(score, score_sharding)
            if var is not None:
                var = jax.lax.with_sharding_constraint(var, table_sharding)
            return (coeffs, score, var, *counts)

        update = re_coordinate_update

    return jax.jit(update, donate_argnums=(0, 1, 2))


@functools.lru_cache(maxsize=None)
def re_chunk_update_program(
    task: TaskType,
    opt_config: OptimizerConfig,
    has_l1: bool,
    variance: VarianceComputationType,
    k_all: int,
    re_solver: str = "lbfgs",
):
    """One jitted, donated update for a STREAMED working-set chunk
    (data/working_set.py): ``[C, S, K]`` entity lanes solved with the same
    vmapped bucket solve as the all-resident program, their ``[N]`` score
    contribution scattered into a running partial, and the chunk's own
    divergence-guard flag returned for the host-side commit decision.

    ``update(init_chunk, score_partial, X, y, w, sample_ids, l2, l1,
    norm_rows, offsets_plus_scores, view_cols, view_vals) ->
    (w_out, var_out, score_partial, ok, reasons, iters)``

    - ``init_chunk`` ``[C, K]`` and ``score_partial`` ``[N]`` are DONATED:
      the chunk's warm-start rows are consumed by the solve (hot chunks feed
      the previous pass's output straight back in) and the score partial is
      threaded through the whole pass without a copy per chunk.
    - The score contribution routes the chunk's samples through the SAME
      ``random_effect_view_score`` kernel as the full-table score (the
      all-resident program's view path), with the
      chunk's lanes standing in as a C-row table — per-sample gather/
      multiply/add order is identical, so per-chunk scatter assembly is
      bitwise-equal to the full-table score. Padding lanes carry
      ``sample_ids = -1`` and their scatter drops (out-of-range row ``N``).
    - ``k_all`` pads the lane table to the full view width so the sample
      view's local columns (always < the owning bucket's K) index safely.
    - The bitwise cross-path contract rides the lbfgs-family solve (the
      repo's bitwise status quo): probe-confirmed lane-count-stable for
      batches >= 2, while the batch-1 lowering differs by an ulp — so the
      working-set scheduler gives single-chunk buckets their exact
      all-resident batch shape. Two tolerance-scoped exceptions, both from
      batch-count-sensitive batched-GEMM lowerings: the direct solver's
      Gram accumulation (streamed-vs-resident parity for
      ``re_solver="direct"`` is tolerance-gated), and the FULL-variance
      Hessian build ``A.T @ (A*d)`` when a bucket is SPLIT across chunks
      (coefficients stay bitwise; the variance drifts ~1 ulp on a few
      lanes at some shapes — tests/test_working_set.py documents the
      bounds).
    """
    solve = _re_bucket_solve_fn(task, opt_config, has_l1, variance, re_solver)
    variance_on = VarianceComputationType(variance) != VarianceComputationType.NONE

    def re_chunk_update(
        init_chunk, score_partial, X, y, w, sample_ids, l2, l1, norm_rows,
        offsets_plus_scores, view_cols, view_vals,
    ):
        from photon_ml_tpu.algorithm.random_effect import _to_original, _to_transformed
        from photon_ml_tpu.models.game import random_effect_view_score

        C, S, K = X.shape
        off = jnp.take(offsets_plus_scores, jnp.maximum(sample_ids, 0), axis=0)
        off = jnp.where(sample_ids >= 0, off, 0.0).astype(init_chunk.dtype)
        init = init_chunk
        if norm_rows is not None:
            factors, shifts, icpt_mask = norm_rows
            init = _to_transformed(init, factors, shifts, icpt_mask)
        w_out, reasons, iters, _evals, var_out = solve(X, y, w, off, init, l2, l1)
        if norm_rows is not None:
            w_out = _to_original(w_out, factors, shifts, icpt_mask)
            if variance_on and factors is not None:
                var_out = var_out * factors**2
        ok = jnp.isfinite(w_out).all()
        # the chunk's lanes as a C-row table through the full-table kernel;
        # tail columns >= K are never gathered (view cols < the bucket's K)
        w_table = jnp.zeros((C, k_all), dtype=w_out.dtype).at[:, :K].set(w_out)
        lane_rows = jnp.where(
            sample_ids >= 0,
            jnp.arange(C, dtype=jnp.int32)[:, None],
            jnp.int32(-1),
        ).reshape(-1)
        sid_flat = sample_ids.reshape(-1)
        safe = jnp.maximum(sid_flat, 0)
        contrib = random_effect_view_score(
            w_table,
            lane_rows,
            jnp.take(view_cols, safe, axis=0),
            jnp.take(view_vals, safe, axis=0),
        )
        n = score_partial.shape[0]
        idx = jnp.where(sid_flat >= 0, sid_flat, n)
        score_out = score_partial.at[idx].set(
            contrib.astype(score_partial.dtype), mode="drop"
        )
        return (
            w_out,
            var_out if variance_on else None,
            score_out,
            ok,
            reasons,
            iters,
        )

    return jax.jit(re_chunk_update, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def re_chunk_score_program():
    """Chunked scoring for an arbitrary host-resident table (the working
    set's initial-score path): one chunk's FULL-WIDTH coefficient rows come
    up as a C-row lane table and its samples route through
    ``random_effect_view_score`` exactly as the all-resident score does —
    scatter-assembling the partials is bitwise-equal to the full-table call.

    ``score(score_partial, w_rows, sample_ids, view_cols, view_vals) ->
    score_partial`` with ``score_partial`` ``[N]`` DONATED (threaded through
    every chunk of the pass)."""

    def re_chunk_score(score_partial, w_rows, sample_ids, view_cols, view_vals):
        from photon_ml_tpu.models.game import random_effect_view_score

        C = w_rows.shape[0]
        lane_rows = jnp.where(
            sample_ids >= 0,
            jnp.arange(C, dtype=jnp.int32)[:, None],
            jnp.int32(-1),
        ).reshape(-1)
        sid_flat = sample_ids.reshape(-1)
        safe = jnp.maximum(sid_flat, 0)
        contrib = random_effect_view_score(
            w_rows,
            lane_rows,
            jnp.take(view_cols, safe, axis=0),
            jnp.take(view_vals, safe, axis=0),
        )
        n = score_partial.shape[0]
        idx = jnp.where(sid_flat >= 0, sid_flat, n)
        return score_partial.at[idx].set(
            contrib.astype(score_partial.dtype), mode="drop"
        )

    return jax.jit(re_chunk_score, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def re_population_update_program(
    task: TaskType,
    opt_config: OptimizerConfig,
    has_l1: bool,
    variance: VarianceComputationType,
    n_entities: int,
    re_solver: str = "lbfgs",
    precision: PrecisionPolicy = FLOAT32,
    with_active: bool = False,
):
    """``re_coordinate_update_program`` with a LEADING POPULATION AXIS: one
    donated XLA program trains P hyperparameter settings' random-effect
    coordinate updates simultaneously over SHARED device-resident data
    (photon_ml_tpu/sweep/ — the model-selection axis batched the way Snap ML
    batches its small local solves, arxiv 1803.06333).

    ``update(coeffs_prev [P,E,K], score_prev [P,N], var_prev ([P,E,K] or
    None), offsets_plus_scores [P,N], l2_rows [P,rows], l1 [P], buckets,
    norm_tables, view, sample_slots=None) -> (coeffs [P,E,K], score [P,N],
    variances, ok [P], reasons, iters)``

    The per-lane body is EXACTLY ``_re_coordinate_update_fn`` — bucket data,
    normalization tables, the scoring view and the slot index (given where
    ``bucket_score_slots`` gives it to a single fit of the same setting)
    broadcast across the population
    (read from HBM once per update for all P settings); coefficient tables,
    scores, regularization rows and the L1 weight carry the population axis.
    Population state is donated exactly like the single-model program. The
    per-lane divergence reject applies independently per setting.

    A lane's output is a bitwise-deterministic function of that lane's inputs
    alone (no cross-lane ops exist under vmap; converged lanes' while_loop
    carries are select-frozen) — the property the sweep's sequential fallback
    path builds its bitwise-parity contract on (sweep/population.py).

    ``with_active=True`` adds a per-lane ``[P]`` bool ``active`` argument
    after ``l1`` (the early-exit program family): inactive lanes solve in
    zero iterations and carry their previous state bitwise — see
    ``_re_coordinate_update_fn``."""
    update = _re_coordinate_update_fn(
        task, opt_config, has_l1, variance, n_entities, re_solver, precision,
        with_active,
    )
    lanes = (0,) * (7 if with_active else 6)
    population = jax.vmap(update, in_axes=lanes + (None, None, None, None))

    def re_population_update(*args, sample_slots=None):
        # the sweep keeps no tracker: the per-lane evaluation counts are
        # dropped here (and die in XLA), the six outputs stay what they were
        return population(*args, sample_slots)[:6]

    return jax.jit(re_population_update, donate_argnums=(0, 1, 2))


def _fe_population_update_fn(
    task: TaskType,
    opt_config: OptimizerConfig,
    has_l1: bool,
    down_sampling: bool = False,
    with_active: bool = False,
):
    """Unjitted vmapped fixed-effect population update body, shared by
    ``fe_population_update_program`` (one donated jit per update) and the
    fused whole-sweep pass (``parallel/game.population_sweep_fn`` — every
    iteration's update chained in one trace). One body, two drivers, so the
    per-update and fused paths stay semantically interchangeable per lane.
    See ``fe_population_update_program`` for the update contract;
    ``with_active=True`` inserts a per-lane ``active [P]`` argument after
    ``keep_u`` (inactive lanes: zero-iteration masked solve, outputs
    select-frozen to the previous state bitwise, flags report no reject,
    iterations report zero)."""
    from photon_ml_tpu.data.dataset import LabeledData
    from photon_ml_tpu.function.losses import POSITIVE_RESPONSE_THRESHOLD

    task = TaskType(task)
    loss = loss_for_task(task)
    minimize = build_minimizer(opt_config)
    use_hvp = OptimizerType(opt_config.optimizer_type) == OptimizerType.TRON
    use_hess = OptimizerType(opt_config.optimizer_type) == OptimizerType.NEWTON
    classification = task.is_classification

    def solve_one(w_prev, s_prev, off, l2, l1, rate, keep_u, active, data, norm):
        weights = data.weights
        if down_sampling:
            if classification:
                pos = data.labels > POSITIVE_RESPONSE_THRESHOLD
                weights = jnp.where(
                    pos, weights, jnp.where(keep_u < rate, weights / rate, 0.0)
                )
            else:
                weights = jnp.where(keep_u < rate, weights, 0.0)
        d2 = LabeledData(X=data.X, labels=data.labels, offsets=off, weights=weights)
        obj = GLMObjective(loss, norm, allow_fused=False)  # vmapped: no pallas path
        x0 = norm.to_transformed_space_device(w_prev)

        def vg(w):
            return obj.value_and_gradient(d2, w, l2)

        if active is not None:
            vg = _masked_value_and_grad(vg, active)

        kwargs = {}
        if use_hvp:
            kwargs["hvp"] = lambda w, v: obj.hessian_vector(d2, w, v, l2)
        if use_hess:
            kwargs["hess"] = lambda w: obj.hessian_matrix(d2, w, l2)
        if has_l1:
            kwargs["l1_weight"] = (
                l1 if active is None else jnp.where(active, l1, jnp.zeros_like(l1))
            )
        res = minimize(vg, x0, **kwargs)
        means = norm.to_original_space_device(res.coefficients)
        score = data.X.matvec(means)
        # same two checks, same order, as the host loop's divergence guard
        # (coordinate_descent._guard_cause)
        value_ok = jnp.isfinite(res.value)
        coefs_ok = jnp.isfinite(means).all()
        ok = jnp.logical_and(value_ok, coefs_ok)
        iters = res.iterations
        if active is not None:
            # a frozen lane carries its state bitwise (the norm-space
            # round-trip is not an identity, so the select is load-bearing),
            # reports no reject and no iterations
            ok = jnp.logical_and(ok, active)
            value_ok = jnp.logical_or(value_ok, jnp.logical_not(active))
            coefs_ok = jnp.logical_or(coefs_ok, jnp.logical_not(active))
            iters = jnp.where(active, iters, jnp.zeros_like(iters))
        means_out = jnp.where(ok, means, w_prev)
        score_out = jnp.where(ok, score, s_prev)
        return (
            means_out, score_out, coefs_ok, value_ok,
            res.value, iters, res.convergence_reason,
        )

    if with_active:
        vmapped = jax.vmap(
            solve_one, in_axes=(0, 0, 0, 0, 0, 0, None, 0, None, None)
        )

        def fe_population_update(
            coeffs_prev, score_prev, offsets_pop, l2, l1, rates, keep_u,
            active, data, norm,
        ):
            return vmapped(
                coeffs_prev, score_prev, offsets_pop, l2, l1, rates, keep_u,
                active, data, norm,
            )

        return fe_population_update

    vmapped = jax.vmap(
        solve_one, in_axes=(0, 0, 0, 0, 0, 0, None, None, None, None)
    )

    def fe_population_update(coeffs_prev, score_prev, offsets_pop, l2, l1, rates, keep_u, data, norm):
        return vmapped(
            coeffs_prev, score_prev, offsets_pop, l2, l1, rates, keep_u, None,
            data, norm,
        )

    return fe_population_update


@functools.lru_cache(maxsize=None)
def fe_population_update_program(
    task: TaskType,
    opt_config: OptimizerConfig,
    has_l1: bool,
    down_sampling: bool = False,
    with_active: bool = False,
):
    """Population fixed-effect coordinate update: one donated XLA program
    trains P settings' fixed-effect solves over ONE shared design matrix and
    produces each lane's ``[N]`` training score and divergence flag, with the
    reject applied in-program (photon_ml_tpu/sweep/).

    ``update(coeffs_prev [P,D], score_prev [P,N], offsets_plus_scores [P,N],
    l2 [P], l1 [P], rates [P], keep_u [N], data, norm) -> (coeffs [P,D],
    score [P,N], coefs_ok [P], value_ok [P], values [P], iters [P],
    reasons [P])`` — ``with_active=True`` inserts a per-lane ``active [P]``
    bool argument after ``keep_u`` (the early-exit program family).

    - ``coeffs_prev`` are ORIGINAL-space warm starts (the model contract);
      the in-program conversion to the solver's transformed space and back
      mirrors ``GLMOptimizationProblem.run`` exactly. ``coeffs_prev`` and
      ``score_prev`` are DONATED population state.
    - ``down_sampling=True`` adds a per-lane down-sampling-rate axis: the
      caller supplies ONE shared uniform draw ``keep_u [N]``
      (sampling/down_sampler.per_sample_uniform — pure function of seed,
      call index and sample position, so replays are deterministic) and the
      program derives each lane's weights with the task's reweighting rule
      (classification: positives kept, negatives kept w.p. rate at weight
      1/rate; regression: uniform keep, no re-scaling) — the
      ``DownSampler`` semantics expressed as a traced lane axis.
    - the divergence guard mirrors the host loop's two checks
      (``_guard_cause``): non-finite final objective, then non-finite
      coefficients; either rejects the lane in-program (previous
      coefficients/score kept bit for bit).
    """
    update = _fe_population_update_fn(
        task, opt_config, has_l1, down_sampling, with_active
    )
    return jax.jit(update, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def fe_coordinate_update_program(
    task: TaskType,
    opt_config: OptimizerConfig,
    has_l1: bool,
    shardings: tuple = None,
    allow_fused: bool = True,
):
    """ONE jitted, donated XLA program for a fixed-effect coordinate update:
    the GLM solve, the original-space conversion, this coordinate's ``[N]``
    score and the divergence guard's select — the fused-protocol analog of
    ``re_coordinate_update_program`` for the single global GLM
    (algorithm/coordinate.FixedEffectCoordinate.update_and_score).

    ``update(coeffs_prev, score_prev, offsets_plus_scores, l2, l1, data,
    norm) -> (coeffs, score, ok, value, iters, reason)``

    - ``coeffs_prev`` ``[D]`` (ORIGINAL-space warm start — the model
      contract; converted in-program like ``GLMOptimizationProblem.run``)
      and ``score_prev`` ``[N]`` are DONATED: feed the outputs forward.
    - the divergence guard mirrors the host loop's two checks
      (``coordinate_descent._guard_cause``): non-finite final objective,
      non-finite coefficients — either rejects IN-PROGRAM, returning the
      previous coefficients/score bit for bit; ``ok`` is the combined
      device flag the descent loop's fused protocol requires
      (tracker.guard_ok).
    - ``data`` is a traced LabeledData pytree whose design matrix may be
      DENSE or SPARSE — the pytree structure is part of jit's cache key, so
      the program family dispatches on storage class with no code fork: the
      objective's matvec/rmatvec/Gram calls lower to the storage's kernels
      (segment-sum / scatter for padded COO, MXU dots for dense).
    - ``shardings``: None on the host backend; on a 2-D ("data", "model")
      mesh the ``(coef_sharding, score_sharding)`` pair — coefficients (and
      every [D] optimizer-state vector) ``P(model)``, the matrix
      ``P(data, model)``, scores ``P(data)``. The explicit out-constraints
      pin the donated state's placement so iteration N+1 consumes iteration
      N's buffers with no resharding; ``parallel/hlo_guards.
      assert_feature_axis_profile`` audits the compiled module's
      feature/data-axis collectives (1411.6520's margin-exchange pattern).
    - ``allow_fused``: the Pallas fast-path switch; mesh callers pass False
      (GSPMD cannot partition an opaque pallas_call), and sparse storage is
      never Pallas-eligible regardless.
    """
    task = TaskType(task)
    loss = loss_for_task(task)
    minimize = build_minimizer(opt_config)
    use_hvp = OptimizerType(opt_config.optimizer_type) == OptimizerType.TRON
    use_hess = OptimizerType(opt_config.optimizer_type) == OptimizerType.NEWTON

    def fe_coordinate_update(coeffs_prev, score_prev, offsets_plus_scores, l2, l1, data, norm):
        d2 = data.with_offsets(offsets_plus_scores)
        obj = GLMObjective(loss, norm, allow_fused=allow_fused)
        x0 = norm.to_transformed_space_device(coeffs_prev)

        def vg(w):
            return obj.value_and_gradient(d2, w, l2)

        kwargs = {}
        if use_hvp:
            kwargs["hvp"] = lambda w, v: obj.hessian_vector(d2, w, v, l2)
        if use_hess:
            kwargs["hess"] = lambda w: obj.hessian_matrix(d2, w, l2)
        if has_l1:
            kwargs["l1_weight"] = l1
        res = minimize(vg, x0, **kwargs)
        means = norm.to_original_space_device(res.coefficients)
        score = data.X.matvec(means)
        # same two checks, same order, as the host loop's divergence guard
        value_ok = jnp.isfinite(res.value)
        coefs_ok = jnp.isfinite(means).all()
        ok = jnp.logical_and(value_ok, coefs_ok)
        coeffs_out = jnp.where(ok, means, coeffs_prev)
        score_out = jnp.where(ok, score, score_prev)
        if shardings is not None:
            coef_sharding, score_sharding = shardings
            coeffs_out = jax.lax.with_sharding_constraint(coeffs_out, coef_sharding)
            score_out = jax.lax.with_sharding_constraint(score_out, score_sharding)
        return (
            coeffs_out, score_out, ok,
            res.value, res.iterations, res.convergence_reason,
        )

    return jax.jit(fe_coordinate_update, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def sharded_glm_solver(
    task: TaskType,
    opt_config: OptimizerConfig,
    has_l1: bool,
    mesh,
    has_lower: bool = False,
    has_upper: bool = False,
):
    """glm_solver variant with replicated output shardings over ``mesh``
    (coefficients replicated, gradient reductions psum'd by XLA — the
    treeAggregate analog of ValueAndGradientAggregator.scala:240-255).
    ``solve(data, x0, l2, l1, lower, upper, norm)``: absent bounds occupy a
    dummy argument slot, exactly like glm_solver."""
    from photon_ml_tpu.parallel.mesh import replicated_sharding

    task = TaskType(task)
    loss = loss_for_task(task)
    minimize = build_minimizer(opt_config)
    use_hvp = OptimizerType(opt_config.optimizer_type) == OptimizerType.TRON
    use_hess = OptimizerType(opt_config.optimizer_type) == OptimizerType.NEWTON

    def fe_sharded_solve(data, x0, l2, l1, lower, upper, norm):
        # Multi-device mesh path: GSPMD cannot partition an opaque pallas_call,
        # so the fused kernel stays off here regardless of the global switch.
        obj = GLMObjective(loss, norm, allow_fused=False)

        def vg(w):
            return obj.value_and_gradient(data, w, l2)

        kwargs = {}
        if use_hvp:
            kwargs["hvp"] = lambda w, v: obj.hessian_vector(data, w, v, l2)
        if use_hess:
            kwargs["hess"] = lambda w: obj.hessian_matrix(data, w, l2)
        if has_l1:
            kwargs["l1_weight"] = l1
        if has_lower:
            kwargs["lower_bounds"] = lower
        if has_upper:
            kwargs["upper_bounds"] = upper
        return minimize(vg, x0, **kwargs)

    return jax.jit(fe_sharded_solve, out_shardings=replicated_sharding(mesh))


@functools.lru_cache(maxsize=None)
def shard_mapped_glm_solver(
    task: TaskType,
    opt_config: OptimizerConfig,
    has_l1: bool,
    mesh,
    axis_name: str = "data",
):
    """GLM solve with EXPLICIT SPMD: the whole optimizer loop runs inside
    ``shard_map`` over the mesh's sample axis, each device evaluating the
    objective on its own [N/m, D] block with ``lax.psum`` combining the data
    sums (GLMObjective.psum_axis). Mathematically identical to the GSPMD
    lowering — the [D]-vector optimizer state is device-invariant because it
    only ever consumes psum'd quantities.

    This exists because GSPMD cannot partition an opaque ``pallas_call``:
    inside shard_map each device's block is an ordinary dense array, so the
    fused Pallas kernels (ops/pallas_glm.py) are legal on a MULTI-chip mesh —
    lifting the single-chip restriction the round-2 review flagged. With the
    kernels off it is simply the explicit-collective form of
    sharded_glm_solver (treeAggregate made explicit,
    ValueAndGradientAggregator.scala:240-255).

    ``solve(data, x0, l2, l1) -> OptResult`` — dense X, identity
    normalization, no bounds/variances (the fused GAME-pass regime).
    """
    from jax.sharding import PartitionSpec as P

    task = TaskType(task)
    loss = loss_for_task(task)
    minimize = build_minimizer(opt_config)
    use_hvp = OptimizerType(opt_config.optimizer_type) == OptimizerType.TRON
    use_hess = OptimizerType(opt_config.optimizer_type) == OptimizerType.NEWTON

    def solve_block(data, x0, l2, l1):
        obj = GLMObjective(loss, psum_axis=axis_name)

        def vg(w):
            return obj.value_and_gradient(data, w, l2)

        kwargs = {}
        if use_hvp:
            kwargs["hvp"] = lambda w, v: obj.hessian_vector(data, w, v, l2)
        if use_hess:
            kwargs["hess"] = lambda w: obj.hessian_matrix(data, w, l2)
        if has_l1:
            kwargs["l1_weight"] = l1
        return minimize(vg, x0, **kwargs)

    def specs_like(tree, sharded: bool):
        return jax.tree_util.tree_map(
            lambda a: P(axis_name, *(None,) * (a.ndim - 1)) if sharded else P(),
            tree,
        )

    def fe_shard_mapped_solve(data, x0, l2, l1):
        from photon_ml_tpu.data.matrix import DenseDesignMatrix

        if not isinstance(data.X, DenseDesignMatrix):
            # a COO matrix sharded by nnz gives each device PARTIAL margins
            # for every row — the per-block objective would psum loss sums of
            # incomplete margins, silently wrong. The sparse path's GSPMD
            # lowering (parallel/glm.py) psums the margins themselves.
            raise TypeError(
                "shard_mapped_glm_solver requires a dense sample-sharded "
                "design matrix; sparse problems take the GSPMD path"
            )
        # psum'd sums make every [D] optimizer state device-invariant, but the
        # while_loop obstructs shard_map's replication inference — disable the
        # check.
        mapped = jax.shard_map(
            solve_block,
            mesh=mesh,
            in_specs=(specs_like(data, True), P(), P(), P()),
            out_specs=P(),
            check_vma=False,
        )
        return mapped(data, x0, l2, l1)

    return jax.jit(fe_shard_mapped_solve)


_extra_caches: list = []


def register_cache(cache_clear) -> None:
    """Register another module's trace cache to be dropped by clear() — e.g.
    the fused-pass step cache, whose traced programs also bake in the
    trace-time Pallas fuse decision that enable_pallas() invalidates."""
    _extra_caches.append(cache_clear)


def clear():
    """Drop all cached solvers (tests / long-running sweeps with many configs)."""
    glm_solver.cache_clear()
    re_bucket_solver.cache_clear()
    re_coordinate_update_program.cache_clear()
    re_chunk_update_program.cache_clear()
    re_chunk_score_program.cache_clear()
    re_population_update_program.cache_clear()
    fe_population_update_program.cache_clear()
    fe_coordinate_update_program.cache_clear()
    sharded_glm_solver.cache_clear()
    shard_mapped_glm_solver.cache_clear()
    for cache_clear in _extra_caches:
        cache_clear()
