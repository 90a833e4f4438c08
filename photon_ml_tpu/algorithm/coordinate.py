"""GAME coordinates: one optimization sub-problem per (effect, feature shard).

Re-designs photon-lib algorithm/Coordinate.scala:28-81 and the concrete photon-api
coordinates (FixedEffectCoordinate.scala:35-166, RandomEffectCoordinate.scala:39-232,
FixedEffectModelCoordinate.scala:44, RandomEffectModelCoordinate.scala:44) for TPU.

The reference's ``updateModel(model, partialScore)`` joins scores back into the
dataset (`dataset.addScoresToOffsets`); here every coordinate's score is a dense
``[N]`` array over the global sample axis, so "adding scores to offsets" is an
elementwise add and the shuffle joins disappear entirely. Training happens in a
jitted solve: one sharded LBFGS/TRON run for the fixed effect, one vmap-ed bucket
solve per shape class for random effects.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.algorithm.random_effect import RandomEffectTracker, train_random_effect
from photon_ml_tpu.data.dataset import FixedEffectDataset
from photon_ml_tpu.data.random_effect import RandomEffectDataset
from photon_ml_tpu.models.game import FixedEffectModel, RandomEffectModel
from photon_ml_tpu.normalization import NO_NORMALIZATION, NormalizationContext
from photon_ml_tpu.optimization.config import GLMOptimizationConfiguration
from photon_ml_tpu.optimization.problem import GLMOptimizationProblem
from photon_ml_tpu.sampling.down_sampler import DownSampler
from photon_ml_tpu.types import (
    ConvergenceReason,
    OptimizerType,
    TaskType,
    VarianceComputationType,
)
from photon_ml_tpu.util.timed import count as timed_count

Array = jnp.ndarray


@dataclasses.dataclass
class FixedEffectOptimizationTracker:
    """Wraps the single OptResult of a fixed-effect solve
    (FixedEffectOptimizationTracker.scala:31).

    Fields may initially hold DEVICE scalars: update_model no longer blocks on
    a per-update ``device_get`` (the sync-free descent loop pipelines
    dispatches across coordinates). ``materialize()`` — called by
    ``summary()``, and by run_coordinate_descent on every tracker before its
    result is returned, restoring the str/int/float field contract for
    downstream consumers — converts them to host values in one transfer,
    idempotently, and publishes the solve's evaluation count to the program's
    recorder (util/timed) under the coordinate's id."""

    convergence_reason: object  # str once materialized; device/int code before
    iterations: object
    final_value: object
    # device bool on the fused update-program path (the descent loop's fused
    # protocol reads it for the in-program divergence select); None on the
    # update_model path, whose guard the loop computes itself
    guard_ok: object = None
    # value-and-gradient evaluations of the solve (OptResult.evaluations);
    # None where the minimiser, or the fused program, does not count them
    evaluations: object = None
    coordinate_id: Optional[str] = None
    # NEWTON assembles one full Hessian per iteration: the solve's count of
    # them is its iteration count (published as ``solver.hessians``)
    newton: bool = False

    def device_values(self):
        """What ``materialize`` reads from the device (None once it has): the
        descent loop fetches every tracker's in ONE batched transfer."""
        if isinstance(self.convergence_reason, str):
            return None
        return (
            self.convergence_reason,
            self.iterations,
            self.final_value,
            self.guard_ok,
            self.evaluations,
        )

    def materialize(self, host=None) -> "FixedEffectOptimizationTracker":
        """``host``: ``device_values()`` already fetched by the caller."""
        if not isinstance(self.convergence_reason, str):
            if host is None:
                host = jax.device_get(self.device_values())
            reason_h, iters_h, value_h, ok_h, evals_h = host
            self.convergence_reason = ConvergenceReason(int(reason_h)).name
            self.iterations = int(iters_h)
            self.final_value = float(value_h)
            if ok_h is not None:
                self.guard_ok = bool(ok_h)
            if evals_h is not None:
                self.evaluations = int(evals_h)
                timed_count(
                    "solver.evaluations", self.evaluations,
                    cid=self.coordinate_id, kind="fe",
                )
            if self.newton:
                timed_count(
                    "solver.hessians", self.iterations,
                    cid=self.coordinate_id, kind="fe",
                )
        return self

    def summary(self) -> str:
        self.materialize()
        evals = "" if self.evaluations is None else f" evals={self.evaluations}"
        return (
            f"reason={self.convergence_reason} iters={self.iterations}{evals} "
            f"value={self.final_value:.6g}"
        )


class Coordinate:
    """Abstract GAME coordinate (Coordinate.scala:28-81).

    ``update_model(initial, partial_scores)`` trains against offsets + the other
    coordinates' scores; ``score(model)`` returns this coordinate's [N] score
    (margins WITHOUT base offsets, so scores sum across coordinates).
    """

    coordinate_id: str

    @property
    def is_locked(self) -> bool:
        return False

    def update_model(self, initial_model, partial_scores: Array):
        raise NotImplementedError

    def update_and_score(
        self, initial_model, partial_scores: Array, prev_score: Array,
        donate: bool = False,
    ):
        """Fused update protocol: train AND produce this coordinate's new [N]
        score in one program, with the divergence guard applied DEVICE-SIDE
        (returned model/score already hold the previous values when the update
        diverged; the tracker's ``guard_ok`` device flag says which — the
        flag is REQUIRED, the descent loop refuses trackers without it).

        Returns ``(model, score, tracker)`` or None when this coordinate has
        no fused path — the descent loop then falls back to
        ``update_model`` + ``score``.

        ``donate=True`` is the caller's promise that ``initial_model``'s
        coefficient buffers and ``prev_score`` are exactly this coordinate's
        previous outputs and nothing else aliases them: the program then
        CONSUMES them (XLA buffer donation) and the caller must use the
        returned model/score instead. With ``donate=False`` the inputs are
        defensively copied and stay valid."""
        return None

    def score(self, model) -> Array:
        raise NotImplementedError

    def initialize_model(self):
        raise NotImplementedError

    def zero_model_score(self) -> Optional[Array]:
        """The [N] score ``self.score(self.initialize_model())`` would return
        — same shape, dtype, placement and bits — WITHOUT computing it, or
        None when this coordinate cannot promise that its initial model
        scores zero (the descent loop then scores it). Only the coordinate
        knows what ``initialize_model`` hands out: a locked coordinate's
        initial model is its trained one. A NEW array every call: the fused
        update protocol may consume (donate) its score input."""
        return None

    def prepare_initial_model(self, model):
        """Adapt an externally supplied warm-start model to this coordinate's
        (possibly mesh-placed) dataset. Default: unchanged."""
        return model


def pad_fixed_effect_model(model, dataset):
    """Place a fixed-effect model's [D] coefficients where the dataset's
    solves will leave them. On a feature-sharded dataset (the 2-D mesh
    backend, parallel/feature_sharded.py) that pads them to the padded dim
    under ``coef_sharding``; on a sample-sharded (1-D mesh) dataset it
    replicates them over the mesh — the solve returns its coefficients that
    way, and an initial vector typed without the mesh would give the first
    update its own jit cache key (one more trace + compile of the whole
    solver on the second update). No-op for single-device datasets."""
    import jax

    from photon_ml_tpu.models.glm import Coefficients

    sharding = getattr(dataset, "coef_sharding", None)
    if sharding is None:
        mesh = getattr(dataset.data.labels.sharding, "mesh", None)
        if mesh is None or mesh.devices.size == 1:
            return model
        from photon_ml_tpu.parallel.mesh import replicated_sharding

        sharding = replicated_sharding(mesh)

    means = model.model.coefficients.means
    if means.shape[0] < dataset.dim:
        means = jnp.concatenate(
            [means, jnp.zeros((dataset.dim - means.shape[0],), dtype=means.dtype)]
        )
    means = jax.device_put(means, sharding)
    from photon_ml_tpu.models.glm import model_class_for_task

    glm = model_class_for_task(model.model.task)(Coefficients(means=means))
    return dataclasses.replace(model, model=glm)


@dataclasses.dataclass
class FixedEffectCoordinate(Coordinate):
    """Global GLM over one feature shard (FixedEffectCoordinate.scala:35-166).

    The reference broadcasts coefficients and treeAggregates gradients each
    iteration; here the solve is one jitted optimizer run whose input arrays may be
    batch-sharded over the mesh (psum inside — see parallel/).
    """

    coordinate_id: str
    dataset: FixedEffectDataset
    task: TaskType
    configuration: GLMOptimizationConfiguration
    normalization: NormalizationContext = NO_NORMALIZATION
    variance_computation: VarianceComputationType = VarianceComputationType.NONE
    down_sampler: Optional[DownSampler] = None
    # (lower[D], upper[D]) per-feature box bounds (constraint maps); enforced
    # natively by the optimizers (LBFGS projection / LBFGSB / TRON)
    box_constraints: Optional[tuple] = None
    # Route updates through the single-program fused path (solver_cache.
    # fe_coordinate_update_program): solve + [N] score + divergence select in
    # ONE donated XLA dispatch per update. None = auto: on for feature-sharded
    # datasets (coef_sharding stamped by the 2-D mesh backend — the fused
    # program is what pins the donated P("model") coefficient state across
    # iterations), off on host/1-D datasets (bitwise status quo: update_model
    # + score). Explicit True/False overrides; True is rejected at
    # construction when a knob the program cannot express is set
    # (down-sampling, box constraints, variance computation).
    use_update_program: object = None

    def __post_init__(self):
        self.task = TaskType(self.task)
        if self.box_constraints is not None and not self.normalization.is_identity:
            # the reference rejects this combination outright (Params.scala:211-214):
            # bounds are specified in original feature space, solves run in
            # normalized space, and the clamp cannot be guaranteed in both
            raise ValueError(
                "Box constraints and normalization cannot be combined"
            )
        if self.use_update_program:
            blockers = [
                name
                for name, bad in (
                    ("down_sampler", self.down_sampler is not None),
                    ("box_constraints", self.box_constraints is not None),
                    (
                        "variance_computation",
                        VarianceComputationType(self.variance_computation)
                        != VarianceComputationType.NONE,
                    ),
                )
                if bad
            ]
            if blockers:
                raise ValueError(
                    "use_update_program=True: the fused fixed-effect update "
                    "program cannot express " + ", ".join(blockers)
                    + "; leave use_update_program unset (auto) or False"
                )
        # donation ownership: the exact output buffers of our last update
        # program call — only those are fed back donated (see
        # RandomEffectCoordinate.__post_init__)
        self._owned: dict = {}
        self._problem = GLMOptimizationProblem(
            task=self.task,
            configuration=self.configuration,
            normalization=self.normalization,
            variance_computation=VarianceComputationType(self.variance_computation),
        )

    def initialize_model(self) -> FixedEffectModel:
        model = self._problem.initialize_zero_model(
            self.dataset.dim, dtype=self.dataset.data.labels.dtype
        )
        return self.prepare_initial_model(
            FixedEffectModel(model=model, feature_shard_id=self.dataset.feature_shard_id)
        )

    def prepare_initial_model(self, model: FixedEffectModel) -> FixedEffectModel:
        return pad_fixed_effect_model(model, self.dataset)

    def update_model(
        self, initial_model: Optional[FixedEffectModel], partial_scores: Array
    ) -> tuple[FixedEffectModel, FixedEffectOptimizationTracker]:
        """Train with offsets := base offsets + other coordinates' scores
        (Coordinate.scala:60-63 / FixedEffectCoordinate.updateModel:91-147)."""
        data = self.dataset.data.add_scores_to_offsets(partial_scores)
        if self.down_sampler is not None:
            data = self.down_sampler.down_sample(data)
        lower = upper = None
        if self.box_constraints is not None:
            lower, upper = self.box_constraints
        glm, result = self._problem.run(
            data,
            self.prepare_initial_model(initial_model).model
            if initial_model is not None
            else None,
            lower_bounds=lower,
            upper_bounds=upper,
        )
        # Tracker scalars stay ON DEVICE: a device_get here would block the
        # descent loop between coordinate updates (the round trip the sync-free
        # loop removes). They materialize lazily — in the loop's once-per-
        # iteration batched transfer, or on first summary()/field read.
        tracker = FixedEffectOptimizationTracker(
            convergence_reason=result.convergence_reason,
            iterations=result.iterations,
            final_value=result.value,
            evaluations=result.evaluations,
            coordinate_id=self.coordinate_id,
            newton=OptimizerType(self.configuration.optimizer_config.optimizer_type)
            == OptimizerType.NEWTON,
        )
        return (
            FixedEffectModel(model=glm, feature_shard_id=self.dataset.feature_shard_id),
            tracker,
        )

    def _update_program_enabled(self) -> bool:
        if self.use_update_program is not None:
            return bool(self.use_update_program)
        # auto: the fused program is how feature-sharded (2-D mesh) datasets
        # keep donated P("model") state across iterations; host datasets keep
        # update_model + score (bitwise status quo). Knobs the program cannot
        # express demote auto back to the generic path silently.
        if getattr(self.dataset, "coef_sharding", None) is None:
            return False
        return (
            self.down_sampler is None
            and self.box_constraints is None
            and VarianceComputationType(self.variance_computation)
            == VarianceComputationType.NONE
        )

    def _resolve_update_program(self):
        """``(program, shardings)`` — the cached fused update program at this
        coordinate's static configuration and placement. The ONE owner of
        program resolution: ``update_and_score`` dispatches it and
        ``compiled_update_hlo`` lowers it, so the collective audit always
        inspects exactly the program training runs."""
        from photon_ml_tpu.optimization.solver_cache import (
            fe_coordinate_update_program,
        )

        sharding = getattr(self.dataset, "coef_sharding", None)
        shardings = None
        allow_fused = True
        if sharding is not None:
            from photon_ml_tpu.parallel.feature_sharded import sample_sharding

            # donated state keeps these across iterations: coefficients (and
            # every [D] optimizer-state vector) P("model"), the [N] score
            # P("data") — the explicit out-constraints in solver_cache pin
            # them so no resharding ever lands between updates
            shardings = (sharding, sample_sharding(sharding.mesh))
            # GSPMD cannot partition an opaque pallas_call
            allow_fused = False
        program = fe_coordinate_update_program(
            self.task,
            self.configuration.optimizer_config,
            bool(self.configuration.l1_weight),
            shardings,
            allow_fused,
        )
        return program, shardings

    def update_and_score(
        self,
        initial_model: Optional[FixedEffectModel],
        partial_scores: Array,
        prev_score: Array,
        donate: bool = False,
    ):
        """One donated XLA program per update (solver_cache.
        fe_coordinate_update_program): the GLM solve, the original-space
        conversion, this coordinate's [N] score and the divergence guard's
        select — no host round trip between them. On a feature-sharded
        dataset the same program compiles as ONE SPMD module over the 2-D
        ("data", "model") mesh, dense or sparse (the design matrix's storage
        class dispatches through the LabeledData pytree structure). Returns
        None (update_model + score fallback) when the program path is off or
        the warm start carries state the program does not thread."""
        if not self._update_program_enabled() or initial_model is None:
            return None
        if initial_model.model.coefficients.variances is not None:
            # the program threads coefficients only; a variance-carrying warm
            # start must keep the generic path, or an in-program reject would
            # silently drop the previous model's variances
            from photon_ml_tpu.analysis.fallbacks import log_fallback_once

            log_fallback_once(
                "fe_coordinate_update_program",
                f"coordinate {self.coordinate_id!r} "
                f"({self.dataset.feature_shard_id}, "
                f"{self.dataset.n} samples x {self.dataset.dim} features)",
                "warm-start model carries variances the fused program does "
                "not thread; using update_model + score",
            )
            return None
        from photon_ml_tpu.models.glm import Coefficients

        program, _ = self._resolve_update_program()
        data = self.dataset.data
        dtype = data.labels.dtype

        def owned_or_copy(key, arr):
            # donation safety: only with the caller's donate promise AND when
            # the buffer is identically OUR previous output is it consumed in
            # place; anything else (external warm start, the loop's initial
            # score) is copied so the caller's array survives our donation
            # (see RandomEffectCoordinate.update_and_score)
            if donate and arr is self._owned.get(key):
                return arr
            return jnp.array(arr, copy=True)

        means = self.prepare_initial_model(initial_model).model.coefficients.means
        if means.dtype != dtype:
            means = means.astype(dtype)
        cfg = self.configuration
        coeffs_out, score_out, ok, value, iters, reason = program(
            owned_or_copy("coeffs", means),
            owned_or_copy("score", prev_score),
            data.offsets + partial_scores,
            jnp.asarray(cfg.l2_weight, dtype=dtype),
            jnp.asarray(cfg.l1_weight or 0.0, dtype=dtype),
            data,
            self.normalization,
        )
        self._owned = {"coeffs": coeffs_out, "score": score_out}
        model = FixedEffectModel(
            model=self._problem.create_model(Coefficients(means=coeffs_out)),
            feature_shard_id=self.dataset.feature_shard_id,
        )
        tracker = FixedEffectOptimizationTracker(
            convergence_reason=reason,
            iterations=iters,
            final_value=value,
            guard_ok=ok,
        )
        return model, score_out, tracker

    def compiled_update_hlo(self) -> str:
        """Compiled (post-SPMD-partitioning) HLO text of this coordinate's
        fused update program at the dataset's placement — the collective-
        audit hook. On a 2-D mesh, ``parallel/hlo_guards.
        assert_feature_axis_profile`` runs over this text to audit exactly
        which collectives cross the feature axis: the per-iteration margin
        all-reduce is the one legal payload-bearing loop collective
        (1411.6520's communication pattern), bounded in count and payload.
        Program resolution shares ONE owner with ``update_and_score``
        (``_resolve_update_program``), so the audit always lowers exactly
        the program training dispatches."""
        program, shardings = self._resolve_update_program()
        ds = self.dataset
        data = ds.data
        dtype = data.labels.dtype
        coeffs = jnp.zeros((ds.dim,), dtype=dtype)
        score = jnp.zeros((ds.n,), dtype=dtype)
        offs = jnp.zeros((ds.n,), dtype=dtype)
        if shardings is not None:
            coef_sharding, score_sharding = shardings
            coeffs = jax.device_put(coeffs, coef_sharding)
            score = jax.device_put(score, score_sharding)
            offs = jax.device_put(offs, score_sharding)
        cfg = self.configuration
        lowered = program.lower(
            coeffs,
            score,
            offs,
            jnp.asarray(cfg.l2_weight, dtype=dtype),
            jnp.asarray(cfg.l1_weight or 0.0, dtype=dtype),
            data,
            self.normalization,
        )
        return lowered.compile().as_text()

    def score(self, model: FixedEffectModel) -> Array:
        return model.score_dataset(self.dataset)


@dataclasses.dataclass
class RandomEffectCoordinate(Coordinate):
    """Per-entity GLMs (RandomEffectCoordinate.scala:39-232). The reference's
    activeData.join(problems).leftOuterJoin(models) -> mapValues(local solve)
    becomes vmap-ed bucket solves with zero comm during the solve."""

    coordinate_id: str
    dataset: RandomEffectDataset
    task: TaskType
    configuration: GLMOptimizationConfiguration
    base_offsets: Array  # [N] global base offsets (gathered per bucket at solve time)
    normalization: Optional[NormalizationContext] = None
    variance_computation: VarianceComputationType = VarianceComputationType.NONE
    # {entity_id: l2} or [E] array: per-entity L2 overrides (the reference's
    # envisioned per-entity regularization, RandomEffectOptimizationProblem:34-37)
    per_entity_reg_weights: Optional[object] = None
    # Route updates through the single-program path (solver_cache.
    # re_coordinate_update_program): one donated XLA dispatch per update
    # instead of one program per bucket with eager glue between them. False
    # reproduces the per-bucket loop (the parity/bench denominator). Mesh-
    # sharded datasets compile the SAME program as one SPMD module: tables
    # and bucket solves partition over the entity axis, scores over the
    # sample axis, with donated state keeping its sharding across updates.
    use_update_program: bool = True
    # Inner bucket solver (optimization/normal_equations.py): None (left to
    # the task: "direct" for a quadratic sub-problem, "lbfgs" otherwise —
    # resolve_re_solver), "lbfgs" (the configured optimizer), "direct"
    # (batched Gram/Cholesky Newton solves), "auto" (measured per bucket).
    re_solver: Optional[str] = None
    # Storage/accumulation precision for the fused update program's device
    # tables and feature blocks (optimization/precision.py): None/"f32" is
    # the bitwise reference; "bf16"/"f16" store tables + features reduced
    # with f32 accumulation (tolerance-gated, requires use_update_program).
    precision: object = None
    # Device-resident working set (data/working_set.py): None = all-resident
    # (status quo); an int bounds the device-resident table ROWS — hot
    # entities stay resident across passes, cold chunks stream
    # host -> device -> host through re_chunk_update_program; "auto" =
    # all-resident whenever the tables fit the backend's memory limit.
    # Demotions back to all-resident are logged (analysis/fallbacks).
    working_set_rows: object = None
    # Optional [E] admission priorities (the continuous trainer feeds the
    # random_effect_gradient_norms screen / recency here); None ranks by
    # per-entity data mass.
    working_set_priorities: Optional[object] = None
    # False serializes chunk staging onto the training thread instead of the
    # double-buffered prefetch — the bench's unoverlapped denominator for the
    # overlap-speedup gate; an execution-strategy knob, bitwise-neutral.
    working_set_overlap: bool = True

    def __post_init__(self):
        self.task = TaskType(self.task)
        from photon_ml_tpu.optimization.normal_equations import resolve_re_solver
        from photon_ml_tpu.optimization.precision import resolve_precision

        self.re_solver = resolve_re_solver(
            self.re_solver,
            self.task,
            k=self.dataset.max_k,
            has_l1=bool(self.configuration.l1_weight),
        )
        self.precision = resolve_precision(self.precision)
        if not self.precision.is_reference:
            if not self.use_update_program:
                raise ValueError(
                    "reduced-precision storage rides the single-program update "
                    "path; set use_update_program=True (the per-bucket loop "
                    "stays f32-only)"
                )
            # storage dtype is orthogonal to placement: mesh-sharded datasets
            # cast their (entity-sharded) tables and bucket blocks the same
            # way the host path does — the reduced bytes just live sharded
        if self.working_set_rows is not None:
            if isinstance(self.working_set_rows, str):
                if self.working_set_rows != "auto":
                    raise ValueError(
                        f"working_set_rows={self.working_set_rows!r}: expected "
                        'None, a positive row budget, or "auto"'
                    )
            elif int(self.working_set_rows) < 1:
                raise ValueError(
                    f"working_set_rows={self.working_set_rows!r} must be a "
                    "positive row budget"
                )
            if not self.use_update_program:
                raise ValueError(
                    "the working set streams chunks through the update-program "
                    "machinery; working_set_rows requires use_update_program="
                    "True (the per-bucket loop has no streamed form)"
                )
            if not self.precision.is_reference:
                raise ValueError(
                    "working_set_rows keeps the host-authoritative tables at "
                    "reference precision; reduced storage precision is not "
                    "supported on the streamed path"
                )
        # donation ownership: the exact output buffers of our last update
        # program call. Only those are fed back donated; foreign arrays
        # (external warm starts, first iteration) are defensively copied so a
        # caller-held model can never be invalidated by our donation.
        self._owned: dict = {}
        self._fused_static = None
        self._ws = None
        self._ws_resolved = False
        self._ws_l1 = None
        # re_solver="auto": the measured per-bucket-shape record
        # (optimization/normal_equations.AutoSolverDecision), filled by the
        # first update's probe — or seeded from a restored checkpoint's
        # extra_state so a crash replay never re-measures against warm
        # tables (a re-probe could flip a choice and break bitwise replay)
        self._auto_decision = None

    def initialize_model(self) -> RandomEffectModel:
        E, K = self.dataset.n_entities, self.dataset.max_k
        dtype = self.dataset.sample_vals.dtype
        if self._working_set() is not None:
            # a working-set coordinate never materializes the [E, K] table on
            # device — the initial model's zeros live on the host tier
            coeffs = np.zeros((E, K), dtype=np.dtype(dtype))
            return RandomEffectModel(
                re_type=self.dataset.re_type,
                feature_shard_id=self.dataset.feature_shard_id,
                task=self.task,
                entity_ids=self.dataset.entity_ids,
                coeffs=coeffs,
                proj_indices=self.dataset.proj_indices,
                projector=self.dataset.projector,
            )
        rows = getattr(self.dataset, "coeffs_rows", None) or E
        coeffs = jnp.zeros((rows, K), dtype=dtype)
        sharding = getattr(self.dataset, "coeffs_sharding", None)
        if sharding is not None:
            import jax

            coeffs = jax.device_put(coeffs, sharding)
        return RandomEffectModel(
            re_type=self.dataset.re_type,
            feature_shard_id=self.dataset.feature_shard_id,
            task=self.task,
            entity_ids=self.dataset.entity_ids,
            coeffs=coeffs,
            proj_indices=self.dataset.proj_indices,
            projector=self.dataset.projector,
        )

    def zero_model_score(self) -> Array:
        # initialize_model() is a zero table by construction, and the view
        # kernel's sum of 0 * v over finite values is +0.0: the [N] zeros it
        # would return, without its [N, K] gather. Shape, dtype and (on a
        # mesh) sharding are the kernel's own output's, so the first
        # update_and_score resolves the program it resolves after a
        # kernel-made score.
        ds = self.dataset
        shardings = self._state_shardings()
        return jnp.zeros(
            (int(ds.sample_entity_rows.shape[0]),),
            dtype=ds.sample_vals.dtype,
            device=None if shardings is None else shardings[1],
        )

    def prepare_initial_model(self, model: RandomEffectModel) -> RandomEffectModel:
        # re-align entity rows to this dataset (warm start across rebuilt or
        # differently ordered datasets), then adopt the dataset's TABLE
        # layout: mesh-placed datasets pad the table height to a device
        # multiple and shard it over the entity axis — a host-height warm
        # start must come in padded + placed, or every downstream select/
        # donate against the trained [coeffs_rows, K] tables shape-mismatches
        if hasattr(model, "aligned_to"):
            model = model.aligned_to(self.dataset)
        if not hasattr(model, "coeffs"):  # duck-typed stand-ins: untouched
            return model
        from photon_ml_tpu.parallel.mesh import pad_rows_and_place

        ds = self.dataset
        sharding = getattr(ds, "coeffs_sharding", None)
        rows = getattr(ds, "coeffs_rows", None) or ds.n_entities
        coeffs = pad_rows_and_place(model.coeffs, rows, sharding)
        variances = (
            None
            if model.variances is None
            else pad_rows_and_place(model.variances, rows, sharding)
        )
        if coeffs is not model.coeffs or variances is not model.variances:
            model = dataclasses.replace(
                model, coeffs=coeffs, variances=variances
            )
        return model

    def _solver_plan(self, offsets_plus_scores=None, initial_model=None):
        """Resolve ``re_solver`` for this update. Explicit strings pass
        through untouched (bitwise status quo). ``"auto"`` resolves to a
        MEASURED per-bucket plan: the first update probes BOTH solvers per
        bucket shape on its actual inputs
        (algorithm/random_effect.measure_auto_solvers) and every later
        update replays the recorded choice — the plan tuple keys new cached
        programs (solver_cache), never a retrace of an old one. With no
        offsets in hand (the compiled-HLO audit path) the probe runs
        against the base offsets alone, which then IS the run's decision —
        one measurement per coordinate lifetime, restorable via
        ``seed_solver_decision``."""
        if self.re_solver != "auto":
            return self.re_solver
        from photon_ml_tpu.algorithm.random_effect import (
            _bucket_shape,
            measure_auto_solvers,
        )

        if self._auto_decision is None:
            ops = (
                offsets_plus_scores
                if offsets_plus_scores is not None
                else self.base_offsets
            )
            self._auto_decision = measure_auto_solvers(
                self.dataset,
                self.task,
                self.configuration,
                ops,
                initial_model=initial_model,
                normalization=self.normalization,
                per_entity_reg_weights=self.per_entity_reg_weights,
            )
        return tuple(
            self._auto_decision.choice_for(*_bucket_shape(b))
            for b in self.dataset.buckets
        )

    def re_solver_stats(self):
        """The measured ``"auto"`` record (dict form) — None until the first
        update measured (or a restore seeded) it. Rides the checkpoint
        manifest's ``extra_state`` (fingerprint-ADJACENT: the estimator
        fingerprint pins ``re_solver="auto"`` the string, never the measured
        outcome)."""
        return (
            None
            if self._auto_decision is None
            else self._auto_decision.to_dict()
        )

    def seed_solver_decision(self, d) -> None:
        """Restore a measured ``"auto"`` record (``re_solver_stats`` form)
        so a resumed run replays the original run's per-bucket choices
        bitwise instead of re-measuring against restored warm tables."""
        if d is None:
            return
        from photon_ml_tpu.optimization.normal_equations import (
            AutoSolverDecision,
        )

        self._auto_decision = AutoSolverDecision.from_dict(d)

    def update_model(
        self, initial_model: Optional[RandomEffectModel], partial_scores: Array
    ) -> tuple[RandomEffectModel, RandomEffectTracker]:
        offsets_plus_scores = self.base_offsets + partial_scores
        model, tracker = train_random_effect(
            self.dataset,
            self.task,
            self.configuration,
            offsets_plus_scores,
            initial_model=initial_model,
            normalization=self.normalization,
            variance_computation=self.variance_computation,
            per_entity_reg_weights=self.per_entity_reg_weights,
            re_solver=self._solver_plan(offsets_plus_scores, initial_model),
        )
        tracker.publish(self.coordinate_id)
        return model, tracker

    def update_model_active(
        self,
        initial_model: RandomEffectModel,
        partial_scores: Array,
        active_mask,
    ) -> tuple[RandomEffectModel, RandomEffectTracker]:
        """Active-set delta update (continuous training): re-solve ONLY the
        entities in ``active_mask`` (host bool [E]) over their full
        accumulated data, warm-started from ``initial_model``; every inactive
        entity keeps its previous coefficients bit for bit
        (algorithm/random_effect.train_random_effect_delta). The stats of the
        last delta update land on ``self.last_active_stats``."""
        from photon_ml_tpu.algorithm.random_effect import train_random_effect_delta

        if initial_model is None:
            raise ValueError(
                "active-set updates need the previous generation's model to "
                "warm-start from (initial_model is None)"
            )
        offsets_plus_scores = self.base_offsets + partial_scores
        model, tracker, stats = train_random_effect_delta(
            self.dataset,
            self.task,
            self.configuration,
            offsets_plus_scores,
            initial_model,
            active_mask,
            normalization=self.normalization,
            variance_computation=self.variance_computation,
            per_entity_reg_weights=self.per_entity_reg_weights,
            re_solver=self._solver_plan(offsets_plus_scores, initial_model),
        )
        self.last_active_stats = stats
        return model, tracker

    def _working_set(self):
        """Resolve ONCE whether this coordinate streams through a device-
        resident working set (data/working_set.py), building the host tier on
        first engagement. Every demotion back to the all-resident path goes
        through ``log_fallback_once`` — a silent demotion could fake the
        bounded-device-memory claim."""
        if self._ws_resolved:
            return self._ws
        self._ws_resolved = True
        knob = self.working_set_rows
        if knob is None:
            return None
        from photon_ml_tpu.analysis.fallbacks import log_fallback_once
        from photon_ml_tpu.data.working_set import MIN_CHUNK_LANES, WorkingSet

        ds = self.dataset
        fingerprint = (
            f"coordinate {self.coordinate_id!r} ({ds.re_type}/"
            f"{ds.feature_shard_id}, {ds.n_entities} entities, "
            f"working_set_rows={knob!r})"
        )

        def demote(cause):
            log_fallback_once("re_working_set", fingerprint, cause)
            return None

        if getattr(ds, "coeffs_sharding", None) is not None:
            return demote(
                "mesh-sharded dataset: the entity axis is already partitioned "
                "across devices and the donated state must keep its placement "
                "— staying all-resident (sharded)"
            )
        if ds.projector is not None:
            return demote(
                "projector-bearing coordinate: projected scoring addresses "
                "the full table on device — staying all-resident"
            )
        if getattr(ds, "n_passive_samples", 0) > 0:
            return demote(
                "the active-data cap left passive samples outside the "
                "training buckets; the streamed score covers bucket samples "
                "only — staying all-resident"
            )
        variance_on = (
            VarianceComputationType(self.variance_computation)
            != VarianceComputationType.NONE
        )
        dtype = ds.sample_vals.dtype
        if knob == "auto":
            stats = getattr(
                jax.local_devices()[0], "memory_stats", lambda: None
            )() or {}
            limit = stats.get("bytes_limit")
            if limit is None:
                return demote(
                    "auto: the backend exposes no memory limit; assuming the "
                    "tables fit — staying all-resident"
                )
            itemsize = np.dtype(dtype).itemsize
            tables = 2 if variance_on else 1
            resident_bytes = ds.n_entities * ds.max_k * itemsize * tables
            for b in ds.buckets:
                resident_bytes += int(np.prod(b.X.shape)) * itemsize
            if resident_bytes <= 0.5 * limit:
                return demote(
                    "auto: tables + bucket blocks fit device memory — "
                    "staying all-resident"
                )
            row_bytes = max(ds.max_k * itemsize * tables, 1)
            budget = max(int(0.25 * limit) // row_bytes, 2 * MIN_CHUNK_LANES)
        else:
            budget = int(knob)
        if budget >= ds.n_entities:
            return demote(
                f"tables fit: the configured working set ({budget} rows) "
                f"covers every entity ({ds.n_entities}) — staying all-resident"
            )
        if not WorkingSet.schedule_feasible(budget, len(ds.buckets)):
            return demote(
                f"budget {budget} rows is below the minimal double-buffered "
                f"schedule (2 x {MIN_CHUNK_LANES} lanes) — staying "
                "all-resident"
            )
        from photon_ml_tpu.algorithm.random_effect import (
            build_l2_rows,
            precompute_norm_tables,
        )

        l2_host = np.asarray(
            jax.device_get(
                build_l2_rows(
                    ds,
                    self.configuration.l2_weight,
                    self.per_entity_reg_weights,
                    dtype,
                    ds.n_entities,
                )
            )
        )
        norm_host = tuple(
            None
            if tbl is None
            else tuple(
                None if a is None else np.asarray(jax.device_get(a))
                for a in tbl
            )
            for tbl in precompute_norm_tables(ds, self.normalization, dtype)
        )
        ws = WorkingSet(
            ds,
            budget,
            dtype,
            variance_on=variance_on,
            l2_host=l2_host,
            norm_host=norm_host,
            priorities=self.working_set_priorities,
            overlap=self.working_set_overlap,
        )
        # the host tier takes ownership of the bucket blocks: re-pointing the
        # dataset at the host copies releases the device ones
        ds.buckets = list(ws.host_buckets)
        self._ws_l1 = jnp.asarray(
            self.configuration.l1_weight or 0.0, dtype=dtype
        )
        self._ws = ws
        return ws

    def reselect_working_set(self, priorities=None) -> bool:
        """Admission/eviction churn between descent runs: re-rank residency
        with fresh priorities (the continuous trainer's gradient-norm screen
        / recency). Host tables carry all state, so churn moves no
        coefficients. Returns False when the working set is off/demoted."""
        ws = self._working_set()
        if ws is None:
            return False
        self.working_set_priorities = priorities
        ws.reselect(priorities)
        return True

    def working_set_stats(self):
        """Live working-set counters (data/working_set.py stats()): measured
        peak device table bytes, H2D/stall seconds, overlap efficiency.
        None when the coordinate is all-resident (knob off or demoted)."""
        ws = self._working_set()
        return None if ws is None else ws.stats()

    def _fused_update_static(self):
        """Descent-iteration-invariant inputs of the update program, built
        once per coordinate: validations, the per-entity L2 table, the
        per-bucket normalization gathers, the bucket tuple, the scoring view
        and the slot index (None where the program scores through the view)."""
        if self._fused_static is None:
            from photon_ml_tpu.algorithm.random_effect import (
                build_l2_rows,
                precompute_norm_tables,
                update_program_data,
            )
            from photon_ml_tpu.function.losses import loss_for_task

            ds = self.dataset
            loss = loss_for_task(self.task)
            opt_type = OptimizerType(self.configuration.optimizer_config.optimizer_type)
            if opt_type in (OptimizerType.TRON, OptimizerType.NEWTON) and not loss.has_hessian:
                raise ValueError(f"{opt_type.value} requires a twice-differentiable loss")
            dtype = ds.sample_vals.dtype
            buckets, view, sample_slots = update_program_data(
                ds, self.precision, self.normalization
            )
            sharding = getattr(ds, "coeffs_sharding", None)
            table_rows = getattr(ds, "coeffs_rows", None) or ds.n_entities
            l2_rows = build_l2_rows(
                ds,
                self.configuration.l2_weight,
                self.per_entity_reg_weights,
                dtype,
                table_rows,
            )
            l1 = jnp.asarray(self.configuration.l1_weight or 0.0, dtype=dtype)
            norm_tables = precompute_norm_tables(ds, self.normalization, dtype)
            if sharding is not None:
                # placed to match the solves: the small L2/L1 tables REPLICATE
                # (each entity shard gathers its own rows locally — no
                # collective in the solve region), the per-bucket norm tables
                # shard over the entity axis like the bucket arrays they are
                # consumed alongside
                from photon_ml_tpu.parallel.mesh import (
                    batch_sharding,
                    replicated_sharding,
                )

                mesh = sharding.mesh
                rep = replicated_sharding(mesh)
                ent2 = batch_sharding(mesh, ndim=2)
                l2_rows = jax.device_put(l2_rows, rep)
                l1 = jax.device_put(l1, rep)
                norm_tables = tuple(
                    None
                    if tbl is None
                    else tuple(
                        None if a is None else jax.device_put(a, ent2)
                        for a in tbl
                    )
                    for tbl in norm_tables
                )
            # mesh-placement padding lanes (entity_rows == n_entities) must
            # not pollute the tracker's convergence stats — the per-bucket
            # path filters rows < E, the fused tracker filters lazily with
            # these host masks (None when no bucket carries padding)
            tracker_masks = None
            if sharding is not None:
                masks = [
                    np.asarray(jax.device_get(b.entity_rows)) < ds.n_entities
                    for b in buckets
                ]
                if not all(m.all() for m in masks):
                    tracker_masks = tuple(masks)
            self._fused_static = dict(
                dtype=dtype,
                l2_rows=l2_rows,
                l1=l1,
                norm_tables=norm_tables,
                buckets=buckets,
                view=view,
                sample_slots=sample_slots,
                tracker_masks=tracker_masks,
                # padded rows per lane of each bucket: the tracker's lane waste
                lane_rows=tuple(b.shape[0] for b in buckets),
            )
        return self._fused_static

    @property
    def score_path(self) -> str:
        """How ``update_and_score`` computes the ``[N]`` training score, the
        ``score_path`` attribute of this coordinate's ``descent.update``
        spans: ``"bucket"`` where the single program scores from the blocks
        it solved, through the dataset's ``sample_slots``; ``"view"`` where
        it (or the streamed chunks, or the per-bucket loop's ``score``) goes
        through ``random_effect_view_score``. Read from the dataset and the
        policy by the rule the program's inputs are built with
        (``bucket_score_slots``); it builds none of them, so the descent loop
        may ask before the update's span opens."""
        from photon_ml_tpu.algorithm.random_effect import bucket_score_slots

        if (
            not self.use_update_program
            or bucket_score_slots(self.dataset, self.precision, self.normalization) is None
            or self._working_set() is not None
        ):
            return "view"
        return "bucket"

    @property
    def solve_loop(self) -> str:
        """How the bucket solves run their L-BFGS loops, the ``loop``
        attribute of this coordinate's ``descent.update`` spans:
        ``"lanes"`` where plain L-BFGS buckets run both loops over the
        entity lanes (``solver_cache.lane_loop``; under ``"auto"`` the
        buckets its plan gives the direct solve are vmapped), ``"vmap"``
        where every bucket is ``jax.vmap`` of a one-entity solve (the
        direct solve, OWLQN, TRON, NEWTON, LBFGSB)."""
        from photon_ml_tpu.optimization.solver_cache import lane_loop

        cfg = self.configuration
        lanes = self.re_solver != "direct" and lane_loop(
            cfg.optimizer_config, bool(cfg.l1_weight)
        )
        return "lanes" if lanes else "vmap"

    def _state_shardings(self):
        """``(table, score)`` shardings of the state a mesh-placed dataset's
        updates donate, None off a mesh: the table (and variances)
        entity-sharded, the [N] score sample-sharded — the explicit
        out-constraints in solver_cache pin them so no resharding ever lands
        between updates, and ``zero_model_score`` places the first score
        under the same one."""
        sharding = getattr(self.dataset, "coeffs_sharding", None)
        if sharding is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        return (
            sharding,
            NamedSharding(sharding.mesh, PartitionSpec(sharding.spec[0])),
        )

    def _resolve_update_program(self):
        """``(program, table_dtype, table_rows, table_sharding, shardings)``
        — the cached update program at this coordinate's static
        configuration and placement. The ONE owner of program resolution:
        ``update_and_score`` dispatches it and ``compiled_update_hlo``
        lowers it, so the collective audit always inspects exactly the
        program training runs."""
        from photon_ml_tpu.optimization.solver_cache import (
            re_coordinate_update_program,
        )

        ds = self.dataset
        st = self._fused_update_static()
        # the coefficient/variance TABLES live at the policy's storage dtype
        # (the donated state the program reads and writes every update); the
        # reference policy keeps the dataset dtype — bitwise status quo
        dtype = (
            st["dtype"]
            if self.precision.is_reference
            else self.precision.storage_dtype
        )
        sharding = getattr(ds, "coeffs_sharding", None)
        # mesh placement pads the table height to a device multiple (rows
        # >= n_entities are always-zero padding the program re-zeroes)
        rows = getattr(ds, "coeffs_rows", None) or ds.n_entities
        shardings = self._state_shardings()
        program = re_coordinate_update_program(
            self.task,
            self.configuration.optimizer_config,
            bool(self.configuration.l1_weight),
            VarianceComputationType(self.variance_computation),
            ds.n_entities,
            self._solver_plan(),
            self.precision,
            shardings,
        )
        return program, dtype, rows, sharding, shardings

    def update_and_score(
        self,
        initial_model: Optional[RandomEffectModel],
        partial_scores: Array,
        prev_score: Array,
        donate: bool = False,
    ):
        """One donated XLA program per update (solver_cache.
        re_coordinate_update_program): gathers, every bucket solve, the table
        scatter, the [N] score and the divergence guard — no host round trip.
        Mesh-sharded datasets compile the same program as ONE SPMD module
        (tables entity-sharded, scores sample-sharded, donated state keeping
        its sharding across updates). Returns None (per-bucket fallback)
        only when ``use_update_program`` is off."""
        from photon_ml_tpu.parallel.mesh import pad_rows_and_place

        ds = self.dataset
        if not self.use_update_program:
            from photon_ml_tpu.analysis.fallbacks import log_fallback_once

            log_fallback_once(
                "re_coordinate_update_program",
                f"coordinate {self.coordinate_id!r} "
                f"({ds.re_type}/{ds.feature_shard_id}, "
                f"{ds.n_samples} samples x {ds.n_entities} entities)",
                "use_update_program=False: the per-bucket host loop runs "
                "one program per bucket with eager glue between them",
            )
            return None
        ws = self._working_set()
        if ws is not None:
            return self._update_and_score_streamed(
                ws, initial_model, partial_scores, prev_score
            )
        from photon_ml_tpu.algorithm.random_effect import LazyRandomEffectTracker

        st = self._fused_update_static()
        if self.re_solver == "auto" and self._auto_decision is None:
            # measure against THIS update's actual inputs (not the audit
            # path's base-offsets fallback) before program resolution
            self._solver_plan(
                self.base_offsets + partial_scores, initial_model
            )
        program, dtype, rows, sharding, _ = self._resolve_update_program()
        E, K_all = ds.n_entities, ds.max_k

        def place_table(table):
            return pad_rows_and_place(table, rows, sharding)

        def owned_or_copy(key, arr):
            # donation safety: only with the caller's donate promise AND when
            # the buffer is identically OUR previous output is it consumed in
            # place; anything else (external warm start, the loop's initial
            # score, a reused coordinate across runs) is copied so the
            # caller's array survives our donation. jnp.array(copy=True)
            # preserves sharding (computation follows data), so mesh state
            # never bounces through the host here.
            if donate and arr is self._owned.get(key):
                return arr
            return jnp.array(arr, copy=True)

        variance_on = (
            VarianceComputationType(self.variance_computation)
            != VarianceComputationType.NONE
        )
        if initial_model is None:
            coeffs_prev = place_table(jnp.zeros((E, K_all), dtype=dtype))
            var_prev = (
                place_table(jnp.zeros((E, K_all), dtype=dtype))
                if variance_on
                else None
            )
        else:
            aligned = (
                initial_model.aligned_to(ds)
                if hasattr(initial_model, "aligned_to")
                else initial_model
            )
            coeffs_prev = aligned.coeffs
            if coeffs_prev.dtype != dtype:
                coeffs_prev = coeffs_prev.astype(dtype)
            coeffs_prev = owned_or_copy("coeffs", place_table(coeffs_prev))
            var_prev = None
            if variance_on:
                if aligned.variances is None:
                    var_prev = place_table(jnp.zeros((E, K_all), dtype=dtype))
                else:
                    v = aligned.variances
                    if v.dtype != dtype:
                        v = v.astype(dtype)
                    var_prev = owned_or_copy("var", place_table(v))

        score_prev = owned_or_copy("score", prev_score)
        offsets_plus_scores = self.base_offsets + partial_scores

        coeffs_out, score_out, var_out, ok, reasons, iters, evals = program(
            coeffs_prev,
            score_prev,
            var_prev,
            offsets_plus_scores,
            st["l2_rows"],
            st["l1"],
            st["buckets"],
            st["norm_tables"],
            st["view"],
            st["sample_slots"],
        )
        self._owned = {"coeffs": coeffs_out, "score": score_out, "var": var_out}
        model = RandomEffectModel(
            re_type=ds.re_type,
            feature_shard_id=ds.feature_shard_id,
            task=self.task,
            entity_ids=ds.entity_ids,
            coeffs=coeffs_out,
            proj_indices=ds.proj_indices,
            variances=var_out,
            projector=ds.projector,
        )
        tracker = LazyRandomEffectTracker(
            reasons, iters, guard_ok=ok, real_masks=st["tracker_masks"],
            evals_parts=evals, lane_rows=st["lane_rows"],
            coordinate_id=self.coordinate_id,
            direct_buckets=self._direct_buckets(st["buckets"]),
        )
        return model, score_out, tracker

    def _direct_buckets(self, buckets) -> tuple:
        """Per bucket, whether its solve is the direct Gram/Cholesky one:
        the ``solver.direct_steps`` counter reads those buckets' steps."""
        from photon_ml_tpu.optimization.normal_equations import use_direct

        plan = self._solver_plan()
        plan = plan if isinstance(plan, tuple) else (plan,) * len(buckets)
        has_l1 = bool(self.configuration.l1_weight)
        return tuple(
            use_direct(p, k=b.shape[1], has_l1=has_l1) for p, b in zip(plan, buckets)
        )

    def _update_and_score_streamed(
        self, ws, initial_model, partial_scores, prev_score
    ):
        """Streamed working-set update: the host tier stays authoritative,
        the device never holds more table rows than the configured budget,
        and every chunk runs through ``re_chunk_update_program`` — the same
        vmapped bucket solve as the all-resident program, so lbfgs-family
        coefficients and variances are bitwise identical
        (tests/test_working_set.py; the direct solver's Gram accumulation is
        batch-shape-sensitive at the last ulp and is tolerance-gated). The
        chunks score through the view kernel; the all-resident program scores
        from its bucket blocks where ``bucket_score_slots`` gives it the
        dataset's ``sample_slots`` (raw float32 blocks), bitwise the same
        score there (gated), and through the view kernel otherwise.

        The fused protocol is preserved: a divergence reject returns the
        PREVIOUS model/score (the staged host commit is discarded) and the
        tracker carries the device ``guard_ok`` flag the descent loop
        requires. The caller's ``donate`` promise is a no-op here — streamed
        updates never consume caller-held buffers."""
        from photon_ml_tpu.algorithm.random_effect import LazyRandomEffectTracker
        from photon_ml_tpu.optimization.solver_cache import re_chunk_update_program

        ds = self.dataset
        dtype = ds.sample_vals.dtype
        # foreign warm starts (checkpoint restore, an external model) seed
        # the host tier; our own committed tables round-trip untouched
        if initial_model is not None and hasattr(initial_model, "coeffs"):
            aligned = (
                initial_model.aligned_to(ds)
                if hasattr(initial_model, "aligned_to")
                else initial_model
            )
            if not ws.owns(aligned.coeffs):
                ws.seed_tables(
                    np.asarray(aligned.coeffs),
                    None
                    if aligned.variances is None
                    else np.asarray(aligned.variances),
                )
        offsets_plus_scores = self.base_offsets + partial_scores
        # a measured-"auto" plan assigns each BUCKET a solver; every chunk
        # of a bucket solves with its bucket's program (one cached program
        # per distinct solver — the chunk program's key includes the solver
        # string, so a changed plan resolves new programs, never a retrace)
        from photon_ml_tpu.algorithm.random_effect import _bucket_solver_plan

        plan = _bucket_solver_plan(
            self._solver_plan(offsets_plus_scores, initial_model),
            len(ds.buckets),
        )
        programs = {
            solver: re_chunk_update_program(
                self.task,
                self.configuration.optimizer_config,
                bool(self.configuration.l1_weight),
                VarianceComputationType(self.variance_computation),
                ds.max_k,
                solver,
            )
            for solver in sorted(set(plan))
        }
        view_cols, view_vals = ds.sample_local_cols, ds.sample_vals
        l1 = self._ws_l1

        def solve_chunk(chunk, staged, score_partial):
            return programs[plan[chunk.bucket]](
                staged["init"],
                score_partial,
                *staged["data"],
                staged["l2"],
                l1,
                staged["norm"],
                offsets_plus_scores,
                view_cols,
                view_vals,
            )

        score0 = jnp.zeros((ds.n_samples,), dtype=dtype)
        score_new, ok_dev, reasons, iters, masks = ws.stream_pass(
            solve_chunk, score0
        )
        if not ws.tail_ok:
            # the all-resident guard sees the WHOLE table, including tail
            # columns the chunks never rewrite — a non-finite warm start
            # there must reject here too
            ok_dev = jnp.logical_and(ok_dev, False)
        # the commit decision needs the flag host-side regardless (swap or
        # drop the staged host tables); the per-chunk harvests already
        # synchronized, so this read adds no stall
        ok_host = bool(jax.device_get(ok_dev))
        ws.commit_pass(ok_host)
        score_out = score_new if ok_host else prev_score
        model = RandomEffectModel(
            re_type=ds.re_type,
            feature_shard_id=ds.feature_shard_id,
            task=self.task,
            entity_ids=ds.entity_ids,
            coeffs=ws.host_coeffs,
            proj_indices=ds.proj_indices,
            variances=ws.host_vars,
            projector=ds.projector,
        )
        tracker = LazyRandomEffectTracker(
            reasons, iters, guard_ok=ok_dev, real_masks=masks
        )
        return model, score_out, tracker

    def compiled_update_hlo(self) -> str:
        """Compiled (post-SPMD-partitioning) HLO text of this coordinate's
        update program at the dataset's placement — the collective-audit
        hook. On a mesh, ``parallel/hlo_guards.assert_entity_solves_
        collective_free`` runs over this text to prove the entity-sharded
        bucket solves compile free of DATA collectives (the embarrassingly-
        parallel contract; only the scalar convergence-predicate consensus
        remains), and ``assert_collective_profile`` bounds the gather/scatter
        collectives around them. Program resolution shares ONE owner with
        ``update_and_score`` (``_resolve_update_program``), so this audit
        always lowers exactly the program training dispatches."""
        return self.lowered_update_program().compile().as_text()

    def lowered_update_program(self):
        """The update program lowered (not compiled) at a fresh fit's
        arguments: ``.as_text()`` is the program as traced, before any
        backend rewrites it (tests count its gathers by result shape)."""
        ds = self.dataset
        st = self._fused_update_static()
        program, dtype, rows, sharding, _ = self._resolve_update_program()
        K_all = ds.max_k
        variance_on = (
            VarianceComputationType(self.variance_computation)
            != VarianceComputationType.NONE
        )
        coeffs = jnp.zeros((rows, K_all), dtype=dtype)
        var = jnp.zeros((rows, K_all), dtype=dtype) if variance_on else None
        score = self.zero_model_score()
        if sharding is not None:
            coeffs = jax.device_put(coeffs, sharding)
            if var is not None:
                var = jax.device_put(var, sharding)
        return program.lower(
            coeffs,
            score,
            var,
            self.base_offsets,
            st["l2_rows"],
            st["l1"],
            st["buckets"],
            st["norm_tables"],
            st["view"],
            st["sample_slots"],
        )

    def score(self, model: RandomEffectModel) -> Array:
        ws = self._working_set()
        if ws is None:
            return model.score_dataset(self.dataset)
        from photon_ml_tpu.optimization.solver_cache import re_chunk_score_program

        ds = self.dataset
        coeffs = np.asarray(model.coeffs)
        if not coeffs.any():
            # a warm table that happens to be all zero scores zero
            # everywhere — bitwise-equal to the full-table kernel, without
            # streaming a pass (a fresh fit never gets here: the descent
            # loop asks zero_model_score)
            return self.zero_model_score()
        return ws.score_streamed(
            re_chunk_score_program(),
            coeffs,
            ds.n_samples,
            ds.sample_local_cols,
            ds.sample_vals,
        )


@dataclasses.dataclass
class ModelCoordinate(Coordinate):
    """Locked, score-only coordinate for partial retraining: never re-optimized
    (FixedEffectModelCoordinate.scala:44, RandomEffectModelCoordinate.scala:44,
    CoordinateDescent.scala:45)."""

    coordinate_id: str
    dataset: object  # FixedEffectDataset | RandomEffectDataset
    model: object  # FixedEffectModel | RandomEffectModel

    @property
    def is_locked(self) -> bool:
        return True

    def prepare_initial_model(self, model):
        if isinstance(model, FixedEffectModel):
            return pad_fixed_effect_model(model, self.dataset)
        if hasattr(model, "aligned_to") and hasattr(self.dataset, "entity_ids"):
            return model.aligned_to(self.dataset)
        return model

    def initialize_model(self):
        return self.model

    def update_model(self, initial_model, partial_scores: Array):
        raise RuntimeError(
            f"Coordinate {self.coordinate_id} is locked (partial retrain); "
            "updateModel must never be called on a ModelCoordinate"
        )

    def score(self, model=None) -> Array:
        return (model if model is not None else self.model).score_dataset(self.dataset)


def coefficient_arrays(model) -> list:
    """The device arrays whose finiteness defines a healthy coordinate update
    (the divergence guard's input, algorithm/coordinate_descent.py): a solver
    that emits NaN/Inf here has diverged and its update must be rejected.
    Variance estimates are deliberately excluded — scoring never consumes
    them, and a singular-Hessian variance failure should not discard an
    otherwise-converged mean update."""
    if isinstance(model, FixedEffectModel):
        return [model.model.coefficients.means]
    if isinstance(model, RandomEffectModel):
        return [model.coeffs]
    raise TypeError(f"Unknown model type: {type(model).__name__}")


def score_model_on_dataset(model, dataset) -> Array:
    """Generic scoring dispatch used for validation data
    (DatumScoringModel.scoreForCoordinateDescent)."""
    if isinstance(model, FixedEffectModel):
        if not isinstance(dataset, FixedEffectDataset):
            raise TypeError("FixedEffectModel requires a FixedEffectDataset")
        return model.score_dataset(dataset)
    if isinstance(model, RandomEffectModel):
        if not isinstance(dataset, RandomEffectDataset):
            raise TypeError("RandomEffectModel requires a RandomEffectDataset")
        return model.score_dataset(dataset)
    raise TypeError(f"Cannot score model of type {type(model).__name__}")
