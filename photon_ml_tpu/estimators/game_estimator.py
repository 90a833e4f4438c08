"""GameEstimator: the "fit like Spark ML" GAME training API.

Re-designs photon-api estimators/GameEstimator.scala:55-801 for TPU. The reference
pipeline (DataFrame -> GameDatum RDD -> per-coordinate datasets -> CoordinateFactory
-> CoordinateDescent per optimization configuration, warm-started) becomes:

- GameInput (host arrays) -> per-coordinate device datasets, built ONCE and shared
  across every configuration in the sweep (prepareTrainingDatasets:454-557);
- per-config coordinates assembled by ``build_coordinate`` (CoordinateFactory.build,
  photon-api algorithm/CoordinateFactory.scala:51-115);
- one ``run_coordinate_descent`` per expanded configuration, each warm-started from
  the previous configuration's model (GameEstimator.fit:344-360);
- validation datasets + EvaluationSuite prepared once
  (prepareValidationDatasetAndEvaluators:568-595).
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.algorithm.coordinate import (
    Coordinate,
    FixedEffectCoordinate,
    ModelCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.algorithm.coordinate_descent import (
    CoordinateDescentResult,
    run_coordinate_descent,
)
from photon_ml_tpu.data.dataset import FixedEffectDataset, LabeledData
from photon_ml_tpu.data.game_data import (
    GameInput,
    as_csr,
    build_fixed_effect_scoring_dataset,
    build_random_effect_scoring_dataset,
)
from photon_ml_tpu.data.projector import make_projector
from photon_ml_tpu.data.random_effect import RandomEffectDataset, build_random_effect_dataset
from photon_ml_tpu.estimators.config import (
    CoordinateConfiguration,
    FixedEffectDataConfiguration,
    RandomEffectDataConfiguration,
    expand_game_configurations,
)
from photon_ml_tpu.evaluation.evaluators import (
    EvaluationSuite,
    Evaluator,
    EvaluatorType,
    MultiEvaluator,
    evaluator_for_type,
    evaluator_spec_name,
    resolve_evaluator,
)
from photon_ml_tpu.models.game import GameModel
from photon_ml_tpu.normalization import NO_NORMALIZATION, NormalizationContext
from photon_ml_tpu.optimization.config import GLMOptimizationConfiguration
from photon_ml_tpu.sampling.down_sampler import down_sampler_for_task
from photon_ml_tpu.types import TaskType, VarianceComputationType
from photon_ml_tpu.util.timed import count as timed_count, span

logger = logging.getLogger(__name__)


def default_evaluator_type(task: TaskType) -> EvaluatorType:
    """Task -> default validation evaluator (GameEstimator defaultEvaluator)."""
    task = TaskType(task)
    return {
        TaskType.LOGISTIC_REGRESSION: EvaluatorType.AUC,
        TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM: EvaluatorType.AUC,
        TaskType.LINEAR_REGRESSION: EvaluatorType.RMSE,
        TaskType.POISSON_REGRESSION: EvaluatorType.POISSON_LOSS,
    }[task]




@dataclasses.dataclass
class GameResult:
    """One trained configuration (reference GameResult: model, evaluations, configs)."""

    model: GameModel
    best_model: GameModel
    configuration: dict[str, GLMOptimizationConfiguration]
    evaluations: Optional[dict[str, float]]  # metrics of best_model
    best_metric: Optional[float]
    descent: CoordinateDescentResult


@dataclasses.dataclass
class GameEstimator:
    """GAME training over an ordered set of coordinates.

    ``coordinate_configurations`` order IS the coordinate update sequence
    (GameEstimator coordinateUpdateSequence param).
    """

    task: TaskType
    coordinate_configurations: Mapping[str, CoordinateConfiguration]
    n_iterations: int = 1
    normalization_contexts: Optional[Mapping[str, NormalizationContext]] = None
    variance_computation: VarianceComputationType = VarianceComputationType.NONE
    validation_evaluators: Sequence = ()
    partial_retrain_locked_coordinates: Sequence[str] = ()
    down_sampling_seed: int = 0
    dtype: object = jnp.float32
    # SPMD backend: a jax.sharding.Mesh places every dataset/score/model array
    # over the device mesh and the SAME coordinate-descent implementation runs
    # as sharded XLA programs (psum gradient reductions, entity-sharded
    # random-effect solves and coefficient tables). None = single-device host
    # placement. Matches GameEstimator.fit:299-380 driving the distributed
    # coordinates in the reference — here distribution is array placement.
    mesh: Optional[object] = None
    # Iteration-level failure recovery (io/checkpoint.py): per sweep config i,
    # coordinate descent saves models after every checkpoint_interval-th
    # iteration under <checkpoint_directory>/config_<i> and a rerun resumes
    # from the last completed iteration. The reference has no equivalent — it
    # leans on Spark lineage recomputation (CoordinateDescent.scala:130-160).
    checkpoint_directory: Optional[str] = None
    checkpoint_interval: int = 1
    # how many checkpoint generations restore() can roll back through when the
    # newest fails integrity verification (io/checkpoint.py)
    checkpoint_keep_generations: int = 3
    # Store dense fixed-effect design matrices in a lower dtype (bfloat16):
    # matvecs read half the HBM bytes and hit the MXU natively while labels,
    # scores, coefficients and accumulation keep `dtype`
    # (DenseDesignMatrix._mxu_dot). Validate quality before relying on it —
    # bench.py gates its bf16 variant on 1% objective parity.
    fe_storage_dtype: Optional[object] = None
    # Random-effect updates as ONE donated XLA program per coordinate update
    # (optimization/solver_cache.re_coordinate_update_program) instead of one
    # program per bucket: no per-bucket dispatch, no host sync between
    # buckets. False restores the per-bucket loop. Mesh-sharded datasets
    # compile the same program as ONE SPMD module (entity-sharded solves,
    # sample-sharded scores).
    re_update_program: bool = True
    # Random-effect inner bucket solver (optimization/normal_equations.py):
    # "lbfgs" runs the configured optimizer (bitwise status quo), "direct"
    # replaces it with batched Gram/Cholesky Newton solves, "auto" picks
    # direct for buckets with K <= DIRECT_AUTO_K_MAX and no L1 — the regime
    # the roofline says dominates the hot loop.
    re_solver: str = "lbfgs"
    # Storage precision for the random-effect update program's device state
    # (optimization/precision.py): None/"f32" is the bitwise reference;
    # "bf16"/"f16" store coefficient tables + bucket features reduced with
    # f32 accumulation. Tolerance-gated (bench.py --host-loop measures the
    # held-out quality drift); requires re_update_program=True. Placement-
    # orthogonal: mesh-sharded tables store reduced the same way.
    re_precision: object = None
    # Device-resident working set for random-effect tables (data/
    # working_set.py): None = all-resident (status quo); an int bounds the
    # device-resident table ROWS per coordinate — hot entities stay resident
    # across CD passes, cold chunks stream host -> device -> host; "auto" =
    # all-resident whenever the tables fit the backend's memory limit.
    # Coordinates that can't stream (mesh-sharded, projector-bearing,
    # passive samples, tables that fit) demote to all-resident with a
    # logged fallback (analysis/fallbacks). Requires re_update_program.
    # Deliberately NOT part of the checkpoint fingerprint: like
    # max_files_per_pass, it is an execution strategy, bitwise-neutral on
    # the lbfgs-family solve.
    re_working_set_rows: object = None
    # Optional {coordinate_id: [E] priorities} admission ranking for the
    # working set (the continuous trainer feeds gradient norms / recency);
    # unlisted coordinates rank by per-entity data mass.
    re_working_set_priorities: Optional[Mapping] = None

    def __post_init__(self):
        self.task = TaskType(self.task)
        self.variance_computation = VarianceComputationType(self.variance_computation)
        from photon_ml_tpu.optimization.precision import resolve_precision

        self.re_precision = resolve_precision(self.re_precision)
        if not self.re_precision.is_reference:
            if not self.re_update_program:
                raise ValueError(
                    "re_precision requires re_update_program=True (reduced "
                    "storage rides the single-program update path)"
                )
            # a mesh is fine: storage dtype is orthogonal to placement — the
            # sharded update program stores its entity-sharded tables/blocks
            # reduced exactly like the host path does. Checkpointing is fine
            # too: io/checkpoint.py encodes reduced dtypes as uint16 bit
            # patterns with self-describing markers, so a bf16 deployment's
            # generations round-trip bit-exactly across restart.
        if self.re_working_set_rows is not None:
            if not self.re_update_program:
                raise ValueError(
                    "re_working_set_rows requires re_update_program=True "
                    "(the per-bucket loop has no streamed form)"
                )
            if not self.re_precision.is_reference:
                raise ValueError(
                    "re_working_set_rows keeps host-authoritative tables at "
                    "reference precision; combine with re_precision is not "
                    "supported"
                )
        locked = set(self.partial_retrain_locked_coordinates)
        unknown = locked - set(self.coordinate_configurations)
        if unknown:
            raise ValueError(f"Locked coordinates not in configurations: {sorted(unknown)}")
        if locked == set(self.coordinate_configurations) and locked:
            raise ValueError("All coordinates locked; nothing to train")

    # ------------------------------------------------------------- warm-up

    @staticmethod
    def warm_up_backend():
        """Kick off XLA backend init + a pilot compile on a background thread
        (data/pipeline.start_xla_warmup) so that latency overlaps host-side
        ingest instead of stacking in front of the first coordinate update.
        Idempotent; returns the BackgroundTask for callers that want to join
        it (the ingest bench does — time_to_first_update accounting)."""
        from photon_ml_tpu.data import pipeline

        return pipeline.start_xla_warmup()

    # ------------------------------------------------------------- data prep

    def _normalization_for(self, shard: str) -> NormalizationContext:
        if not self.normalization_contexts:
            return NO_NORMALIZATION
        return self.normalization_contexts.get(shard, NO_NORMALIZATION)

    def prepare_training_datasets(
        self,
        data: GameInput,
        entity_orders: Optional[Mapping] = None,
        exclude_entities: Optional[Mapping] = None,
    ) -> dict[str, object]:
        """GameInput -> per-coordinate device datasets
        (GameEstimator.prepareTrainingDatasets:454-557). Built once per fit.

        ``entity_orders`` ({coordinate_id: previous entity_ids sequence})
        pins random-effect entity ROW order across incremental rebuilds:
        known entities keep their previous rows, new ones append at the tail
        — the stable-growth contract of continuous training
        (data/random_effect.build_random_effect_dataset).

        ``exclude_entities`` ({coordinate_id: set of entity ids}) drops the
        listed entities' training buckets and model rows entirely — the
        entity-eviction surface of continuous training: an evicted entity's
        samples score 0 from that coordinate, exactly the missing-entity
        contract."""
        if not data.has_labels:
            raise ValueError("Training data must carry labels")
        datasets: dict[str, object] = {}
        for cid, cfg in self.coordinate_configurations.items():
            dc = cfg.data_config
            if isinstance(dc, FixedEffectDataConfiguration):
                from photon_ml_tpu.data.matrix import as_design_matrix_with_storage

                # the fixed effect's placement, synced like the random
                # effects' (data/random_effect.py): set-up's H2D is a number
                with span("ingest.h2d", cid=cid):
                    X = as_design_matrix_with_storage(
                        data.shard(dc.feature_shard_id),
                        self.fe_storage_dtype,
                        self.dtype,
                    )
                    datasets[cid] = FixedEffectDataset(
                        LabeledData.build(
                            X,
                            data.labels,
                            offsets=data.offsets,
                            weights=data.weights,
                            dtype=self.dtype,
                        ),
                        feature_shard_id=dc.feature_shard_id,
                    )
                    jax.block_until_ready(datasets[cid].data)
            elif isinstance(dc, RandomEffectDataConfiguration):
                norm = self._normalization_for(dc.feature_shard_id)
                X = as_csr(data.shard(dc.feature_shard_id))
                projector = self._projector_for(dc, X.shape[1], norm)
                datasets[cid] = build_random_effect_dataset(
                    X,
                    data.ids(dc.random_effect_type),
                    dc.random_effect_type,
                    feature_shard_id=dc.feature_shard_id,
                    active_data_upper_bound=dc.active_data_upper_bound,
                    active_data_lower_bound=dc.active_data_lower_bound,
                    features_max=dc.features_max,
                    labels=data.labels,
                    weights=data.weights,
                    intercept_index=norm.intercept_index if not norm.is_identity else None,
                    # with a projector, normalization rides ON the projector
                    normalization=(
                        None if norm.is_identity or projector is not None else norm
                    ),
                    dtype=self.dtype,
                    projector=projector,
                    entity_order=(
                        None if entity_orders is None else entity_orders.get(cid)
                    ),
                    exclude_entities=(
                        None if exclude_entities is None else exclude_entities.get(cid)
                    ),
                )
                timed_count(
                    "ingest.padding_waste", datasets[cid].padding_waste,
                    cid=cid, rows=datasets[cid].n_active_samples,
                )
            else:
                raise TypeError(f"Unknown data configuration {type(dc).__name__}")
        return datasets

    def prepare_scoring_datasets(self, data: GameInput) -> dict[str, object]:
        """Validation/scoring datasets: same shapes, no caps/selection, no training
        buckets (the reference scores validation data without active-data policies)."""
        datasets: dict[str, object] = {}
        for cid, cfg in self.coordinate_configurations.items():
            dc = cfg.data_config
            if isinstance(dc, FixedEffectDataConfiguration):
                datasets[cid] = build_fixed_effect_scoring_dataset(
                    data, dc.feature_shard_id, dtype=self.dtype
                )
            else:
                norm = self._normalization_for(dc.feature_shard_id)
                datasets[cid] = build_random_effect_scoring_dataset(
                    data, dc.random_effect_type, dc.feature_shard_id, dtype=self.dtype,
                    projector=self._projector_for(
                        dc, data.shard(dc.feature_shard_id).shape[1], norm
                    ),
                )
        return datasets

    def _projector_for(self, dc, original_dim: int, norm: NormalizationContext):
        """RandomProjector for a RANDOM_PROJECTION coordinate, else None. Built
        deterministically from (config seed, dim) so training and scoring datasets
        share the same matrix without threading state; any non-identity
        normalization rides on the projector so every consumer folds it."""
        if dc.projector is None:
            return None
        return make_projector(
            dc.projector,
            original_dim,
            intercept_index=norm.intercept_index if not norm.is_identity else None,
            normalization=None if norm.is_identity else norm,
        )

    def prepare_evaluation_suite(self, validation: GameInput) -> EvaluationSuite:
        """prepareValidationDatasetAndEvaluators:568-595: default task evaluator
        first unless the caller supplied evaluators (first = primary)."""
        if not validation.has_labels:
            raise ValueError("Validation data must carry labels")
        specs = list(self.validation_evaluators) or [default_evaluator_type(self.task)]
        evaluators = [resolve_evaluator(s) for s in specs]
        return EvaluationSuite(
            evaluators=evaluators,
            labels=np.asarray(validation.labels, dtype=np.float64),
            offsets=np.asarray(validation.offsets, dtype=np.float64),
            weights=np.asarray(validation.weights, dtype=np.float64),
            id_columns={t: np.asarray(c) for t, c in validation.id_columns.items()},
        )

    # ------------------------------------------------------------ coordinates

    def build_coordinate(
        self,
        cid: str,
        dataset,
        opt_config: GLMOptimizationConfiguration,
        base_offsets,
        initial_model=None,
    ) -> Coordinate:
        """CoordinateFactory.build (photon-api algorithm/CoordinateFactory.scala:51-115)."""
        cfg = self.coordinate_configurations[cid]
        if cid in set(self.partial_retrain_locked_coordinates):
            if initial_model is None:
                raise ValueError(
                    f"Locked coordinate {cid!r} needs a model from initial_model"
                )
            from photon_ml_tpu.algorithm.coordinate import pad_fixed_effect_model
            from photon_ml_tpu.models.game import FixedEffectModel

            if isinstance(initial_model, FixedEffectModel):
                # feature-sharded datasets pad D; the locked model must match
                initial_model = pad_fixed_effect_model(initial_model, dataset)
            return ModelCoordinate(coordinate_id=cid, dataset=dataset, model=initial_model)
        dc = cfg.data_config
        if isinstance(dc, FixedEffectDataConfiguration):
            sampler = None
            if 0.0 < cfg.down_sampling_rate < 1.0:
                sampler = down_sampler_for_task(
                    self.task, cfg.down_sampling_rate, self.down_sampling_seed
                )
            norm = self._normalization_for(dc.feature_shard_id)
            bounds = cfg.box_constraints
            if getattr(dataset, "coef_sharding", None) is not None:
                # feature-axis sharding padded D with all-zero columns: extend
                # [D]-shaped normalization (identity entries) and box bounds
                # (unbounded entries) to match
                norm = norm.padded_to(dataset.dim)
                if bounds is not None:
                    lo, hi = bounds
                    extra = dataset.dim - len(lo)
                    if extra > 0:
                        lo = np.concatenate([np.asarray(lo), np.full(extra, -np.inf)])
                        hi = np.concatenate([np.asarray(hi), np.full(extra, np.inf)])
                        bounds = (lo, hi)
            return FixedEffectCoordinate(
                coordinate_id=cid,
                dataset=dataset,
                task=self.task,
                configuration=opt_config,
                normalization=norm,
                variance_computation=self.variance_computation,
                down_sampler=sampler,
                box_constraints=bounds,
            )
        norm = self._normalization_for(dc.feature_shard_id)
        return RandomEffectCoordinate(
            coordinate_id=cid,
            dataset=dataset,
            task=self.task,
            configuration=opt_config,
            base_offsets=base_offsets,
            normalization=None if norm.is_identity else norm,
            variance_computation=self.variance_computation,
            per_entity_reg_weights=cfg.per_entity_reg_weights,
            use_update_program=self.re_update_program,
            re_solver=self.re_solver,
            precision=self.re_precision,
            working_set_rows=self.re_working_set_rows,
            working_set_priorities=(
                None
                if self.re_working_set_priorities is None
                else self.re_working_set_priorities.get(cid)
            ),
        )

    # ---------------------------------------------------------------- fit

    def fit(
        self,
        data: GameInput,
        validation_data: Optional[GameInput] = None,
        initial_model: Optional[GameModel] = None,
    ) -> list[GameResult]:
        """Train one GAME model per expanded optimization configuration, chaining
        warm starts (GameEstimator.fit:299-380). Returns results in sweep order."""
        with span("fit"):
            locked = set(self.partial_retrain_locked_coordinates)
            if locked and initial_model is None:
                raise ValueError("partial retrain requires initial_model")

            with span("fit.prepare"):
                datasets = self.prepare_training_datasets(data)
            with span("fit.build"):  # what every configuration of the sweep shares
                base_offsets = jnp.asarray(np.asarray(data.offsets), dtype=self.dtype)
                if self.mesh is not None:
                    from photon_ml_tpu.parallel.placement import (
                        pad_and_shard_vector,
                        place_game_datasets,
                    )

                    datasets = place_game_datasets(datasets, self.mesh)
                    base_offsets = pad_and_shard_vector(
                        np.asarray(data.offsets), self.mesh, dtype=self.dtype
                    )

            validation_datasets = None
            suite = None
            if validation_data is not None:
                with span("fit.prepare"):
                    validation_datasets = self.prepare_scoring_datasets(validation_data)
                    if self.mesh is not None:
                        from photon_ml_tpu.parallel.placement import place_game_datasets

                        validation_datasets = place_game_datasets(validation_datasets, self.mesh)
                    suite = self.prepare_evaluation_suite(validation_data)

            sweep = expand_game_configurations(self.coordinate_configurations)
            logger.info(
                "GAME sweep: %d configurations x %d coordinates",
                len(sweep),
                len(self.coordinate_configurations),
            )

            results: list[GameResult] = []
            warm: Optional[GameModel] = initial_model
            for i, opt_configs in enumerate(sweep):
                with span("fit.build", configuration=i):
                    coordinates: dict[str, Coordinate] = {}
                    init_models: dict[str, object] = {}
                    for cid in self.coordinate_configurations:
                        init = warm.get_model(cid) if warm is not None else None
                        coordinates[cid] = self.build_coordinate(
                            cid, datasets[cid], opt_configs[cid], base_offsets, initial_model=init
                        )
                        if init is not None:
                            init_models[cid] = (
                                init.aligned_to(datasets[cid])
                                if isinstance(datasets[cid], RandomEffectDataset)
                                and hasattr(init, "aligned_to")
                                else init
                            )
                    checkpointer = None
                    if self.checkpoint_directory is not None:
                        from photon_ml_tpu.io.checkpoint import CoordinateDescentCheckpointer

                        # fingerprint ties the checkpoint to (task, this config, data
                        # size): a rerun with changed hyperparameters or data rejects
                        # the stale checkpoint instead of silently resuming from it
                        fp_parts = [
                            str(TaskType(self.task).value),
                            str(data.n),
                            # validation identity: best_metric restored from a
                            # checkpoint must be comparable to metrics of this run.
                            # Spec NAMES, not str(): Evaluator dataclasses render
                            # their fn field as a per-process function address, which
                            # made a cross-PROCESS rerun reject its own checkpoint
                            f"val={validation_data.n if validation_data is not None else 0}",
                            f"evals={[evaluator_spec_name(e) for e in self.validation_evaluators]}",
                            # solver identity: resuming an lbfgs-trained checkpoint
                            # into a direct-solver run (or vice versa) would produce
                            # a model that is neither path's contract
                            f"re_solver={self.re_solver}",
                            # storage-precision identity, same stale-restore class: a
                            # bf16-trained checkpoint must not warm-start an f32 run
                            # (or vice versa) pretending nothing changed
                            f"re_precision={self.re_precision.name}",
                        ]
                        for cid in sorted(self.coordinate_configurations):
                            fp_parts.append(f"{cid}={opt_configs[cid]!r}")
                        checkpointer = CoordinateDescentCheckpointer(
                            os.path.join(self.checkpoint_directory, f"config_{i}"),
                            interval=self.checkpoint_interval,
                            dtype=self.dtype,
                            fingerprint="|".join(fp_parts),
                            keep_generations=self.checkpoint_keep_generations,
                        )
                descent = run_coordinate_descent(
                    coordinates,
                    n_iterations=self.n_iterations,
                    initial_models=init_models or None,
                    validation_datasets=validation_datasets,
                    evaluation_suite=suite,
                    checkpointer=checkpointer,
                )
                evaluations = None
                if suite is not None and (descent.metrics_history or descent.best_metrics):
                    # metrics of the best snapshot = the history row that set best_metric
                    evaluations = _metrics_of_best(descent)
                results.append(
                    GameResult(
                        model=descent.model,
                        best_model=descent.best_model,
                        configuration=opt_configs,
                        evaluations=evaluations,
                        best_metric=descent.best_metric,
                        descent=descent,
                    )
                )
                warm = descent.best_model  # chain warm starts across the sweep
            return results

    def select_best_model(self, results: Sequence[GameResult]) -> GameResult:
        """Best result by primary validation metric (GameTrainingDriver
        selectBestModel:683-748); without validation, the last result."""
        with_metric = [r for r in results if r.best_metric is not None]
        if not with_metric:
            return results[-1]
        primary = resolve_evaluator(
            (list(self.validation_evaluators) or [default_evaluator_type(self.task)])[0]
        )
        best = with_metric[0]
        for r in with_metric[1:]:
            if primary.better_than(r.best_metric, best.best_metric):
                best = r
        return best


def _metrics_of_best(descent: CoordinateDescentResult):
    # best_metrics is recorded whenever best_metric is set; the fallback covers
    # only the degenerate no-best case (all metrics non-comparable)
    if descent.best_metrics is not None:
        return descent.best_metrics
    return descent.metrics_history[-1][2] if descent.metrics_history else None

