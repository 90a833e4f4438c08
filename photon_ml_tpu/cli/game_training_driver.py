"""GAME training CLI driver.

Parity target: photon-client cli/game/training/GameTrainingDriver.scala:55-855 —
the end-to-end training pipeline: feature maps -> Avro read -> validation ->
stats/normalization -> coordinate-config grid -> GameEstimator.fit (warm-started
sweep) -> hyperparameter tuning -> model selection -> model + metadata save.
Flag names mirror the reference's scopt parser (param name with spaces ->
dashes), so reference invocations translate 1:1; Spark-only flags
(min.partitions, tree aggregate depth) are accepted and ignored.

Output layout (GameTrainingDriver.scala:71-73, 768-825):
    <root>/best/...            best model by validation metric (or last config)
    <root>/models/<i>/...      one dir per trained configuration (OUTPUT mode ALL)
    each model dir: model files (model_io layout) + model-spec.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional

import numpy as np

from photon_ml_tpu.cli.parsers import (
    add_version_argument,
    ModelOutputMode,
    coordinate_configuration_to_string,
    parse_coordinate_configuration,
    parse_evaluator_spec,
    parse_feature_shard_configuration,
)
from photon_ml_tpu.data import avro_io
from photon_ml_tpu.data.index_map import IndexMap
from photon_ml_tpu.data.readers import read_merged_avro
from photon_ml_tpu.data.validators import DataValidationType, sanity_check_data
from photon_ml_tpu.estimators.config import RandomEffectDataConfiguration
from photon_ml_tpu.estimators.evaluation_function import GameEstimatorEvaluationFunction
from photon_ml_tpu.estimators.game_estimator import GameEstimator
from photon_ml_tpu.hyperparameter.tuner import build_tuner
from photon_ml_tpu.io.model_io import load_game_model, save_game_model
from photon_ml_tpu.normalization import FeatureDataStatistics, NormalizationContext
from photon_ml_tpu.types import (
    HyperparameterTuningMode,
    NormalizationType,
    TaskType,
    VarianceComputationType,
)
from photon_ml_tpu.util import Event, EventEmitter, PhotonLogger, Timed
from photon_ml_tpu.util.timed import summary as span_summary
from photon_ml_tpu.util.date_range import resolve_input_paths

BEST_DIR = "best"
MODELS_DIR = "models"
MODEL_SPEC_FILE = "model-spec.json"
SUMMARY_FILE = "feature-summary.avro"


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="game-training-driver",
        description="Train a GAME (GLMix) model on TPU.",
    )
    add_version_argument(p)
    # GameDriver shared params (GameDriver.scala:56-131)
    p.add_argument("--input-data-directories", required=True,
                   help="Comma-separated training data paths (Avro files/dirs)")
    p.add_argument("--validation-data-directories", default=None)
    p.add_argument("--input-data-date-range", default=None,
                   help="yyyyMMdd-yyyyMMdd inclusive; expands each input dir to "
                        "its <dir>/yyyy/MM/dd day partitions")
    p.add_argument("--input-data-days-range", default=None,
                   help="START-END in days ago (START >= END), e.g. 90-1")
    p.add_argument("--validation-data-date-range", default=None)
    p.add_argument("--validation-data-days-range", default=None)
    p.add_argument("--off-heap-index-map-directory", default=None,
                   help="Directory of per-shard saved index maps (<shard>.npz)")
    p.add_argument("--model-input-directory", default=None,
                   help="Warm-start / partial-retrain model directory")
    p.add_argument("--evaluators", default=None,
                   help="Comma-separated evaluators, e.g. AUC,RMSE,PRECISION@5:userId")
    p.add_argument("--root-output-directory", required=True)
    p.add_argument("--override-output-directory", action="store_true")
    p.add_argument("--feature-shard-configurations", action="append", required=True,
                   help='e.g. "name=shardA,feature.bags=features,intercept=true"')
    p.add_argument("--data-validation", default="VALIDATE_DISABLED",
                   choices=[m.value for m in DataValidationType])
    p.add_argument("--log-level", default="INFO")
    p.add_argument("--application-name", default="game-training")
    # GameTrainingDriver params (GameTrainingDriver.scala:82-173)
    p.add_argument("--training-task", required=True,
                   choices=[t.value for t in TaskType])
    p.add_argument("--coordinate-configurations", action="append", required=True)
    p.add_argument("--coordinate-update-sequence", required=True,
                   help="Comma-separated coordinate names, update order")
    p.add_argument("--coordinate-descent-iterations", type=int, default=1)
    p.add_argument("--partial-retrain-locked-coordinates", default=None)
    p.add_argument("--normalization", default="NONE",
                   choices=[n.value for n in NormalizationType])
    p.add_argument("--data-summary-directory", default=None)
    p.add_argument("--output-mode", default="BEST",
                   choices=[m.value for m in ModelOutputMode])
    p.add_argument("--hyper-parameter-tuner", default="ATLAS")
    p.add_argument("--hyper-parameter-tuning", default="NONE",
                   choices=[m.value for m in HyperparameterTuningMode])
    p.add_argument("--hyper-parameter-tuning-iterations", type=int, default=10)
    p.add_argument("--variance-computation-type", default="NONE",
                   choices=[v.value for v in VarianceComputationType])
    p.add_argument("--model-sparsity-threshold", type=float, default=0.0)
    p.add_argument("--ignore-threshold-for-new-models", action="store_true")
    p.add_argument("--coefficient-box-constraints", default=None,
                   help='JSON array of {"name","term","lowerBound","upperBound"} '
                        "maps; wildcard '*' in term (or name+term) supported. "
                        "Applies to fixed-effect coordinates.")
    p.add_argument("--compute-backend", default="host",
                   choices=["host", "mesh"],
                   help="'mesh' places datasets/models over a jax.sharding.Mesh "
                        "so the coordinate-descent pass runs as sharded SPMD "
                        "programs (the reference's distributed path)")
    p.add_argument("--mesh-devices", type=int, default=None,
                   help="Device count for --compute-backend=mesh "
                        "(default: all)")
    from photon_ml_tpu.cli.runtime import add_distributed_arguments, add_ingest_arguments

    add_distributed_arguments(
        p, "multi-host training (jax.distributed runtime init)"
    )
    add_ingest_arguments(p)
    p.add_argument("--mesh-model-devices", type=int, default=1,
                   help="Shard the dense fixed-effect FEATURE axis over this many "
                        "devices (2-D data x model mesh; coefficients and optimizer "
                        "state live distributed). 1 = pure data/entity parallelism")
    p.add_argument("--checkpoint-directory", default=None,
                   help="Enable iteration-level checkpoint/resume: coordinate "
                        "descent saves models here after each iteration and a "
                        "rerun with the same directory resumes from the last "
                        "completed iteration")
    p.add_argument("--checkpoint-interval", type=int, default=1,
                   help="Save every k-th coordinate-descent iteration")
    p.add_argument("--checkpoint-keep-generations", type=int, default=3,
                   help="Checkpoint generations retained for integrity "
                        "rollback: restore verifies checksums and falls back "
                        "to the newest valid generation")
    p.add_argument("--fault-plan", default=None,
                   help="Deterministic fault injection plan, e.g. "
                        "'checkpoint.write.manifest:crash:2' (also via the "
                        "PHOTON_FAULT_PLAN env var; resilience/faultpoints.py)")
    p.add_argument("--fe-storage-dtype", default=None, choices=["bf16"],
                   help="Store dense fixed-effect features in bfloat16 (half "
                        "the HBM traffic; f32 accumulation on the MXU). "
                        "Validate metric parity for your workload first")
    p.add_argument("--profile-output-directory", default=None,
                   help="Capture an XLA/TPU profiler trace of the training "
                        "phase (open with TensorBoard or xprof) — the "
                        "TPU-native analog of the reference's Timed sections")
    # Spark-isms accepted for 1:1 invocation compatibility (no-ops here)
    p.add_argument("--min-validation-partitions", type=int, default=None,
                   help=argparse.SUPPRESS)
    p.add_argument("--tree-aggregate-depth", type=int, default=None,
                   help=argparse.SUPPRESS)
    p.add_argument("--timezone", default=None, help=argparse.SUPPRESS)
    return p


def _load_index_maps(directory: Optional[str], shard_ids) -> dict:
    """Per-shard saved index maps (GameDriver.prepareFeatureMapsDefault:
    185-205), trying each store format the feature-indexing driver can emit:
    this framework's <dir>/<shard>.npz, the mmap off-heap store
    (<dir>/<shard>/meta, data/offheap_index.py), or partitioned PalDB stores
    (paldb-partition-<shard>-<i>.dat) — including reference-built ones,
    decoded natively by data/paldb.py so reference index directories work
    unchanged."""
    if directory is None:
        return {}
    from photon_ml_tpu.data import paldb
    from photon_ml_tpu.data.offheap_index import OffHeapIndexMap

    out = {}
    for shard in shard_ids:
        path = os.path.join(directory, f"{shard}.npz")
        if os.path.exists(path):
            out[shard] = IndexMap.load(path)
        elif os.path.exists(os.path.join(directory, shard, "meta")):
            out[shard] = OffHeapIndexMap(os.path.join(directory, shard))
        else:
            partitions = paldb.discover_partitions(directory, shard)
            if partitions:
                out[shard] = paldb.load_paldb_index_map(directory, shard, partitions)
    return out


def _write_feature_summary(path: str, shard_id: str, imap: IndexMap,
                           stats: FeatureDataStatistics) -> None:
    """FeatureSummarizationResultAvro records per feature
    (ModelProcessingUtils.writeBasicStatistics:516-606)."""
    from photon_ml_tpu.io.model_io import _split_key

    def records():
        for j in range(len(stats.mean)):
            name, term = _split_key(imap.get_feature_name(j) or str(j))
            yield {
                "featureName": name,
                "featureTerm": term,
                "metrics": {
                    "count": float(stats.count),
                    "mean": float(stats.mean[j]),
                    "variance": float(stats.variance[j]),
                    "min": float(stats.min[j]),
                    "max": float(stats.max[j]),
                    "numNonzeros": float(stats.num_nonzeros[j]),
                    "meanAbs": float(stats.mean_abs[j]),
                },
            }

    os.makedirs(os.path.dirname(path), exist_ok=True)
    avro_io.write_container(path, avro_io.FEATURE_SUMMARIZATION_SCHEMA, records())


def _save_result(out_dir: str, result, index_maps_by_coord, coord_configs,
                 sparsity_threshold, logger):
    import dataclasses as _dc

    os.makedirs(out_dir, exist_ok=True)
    save_game_model(
        out_dir,
        result.best_model,
        index_maps_by_coord,
        sparsity_threshold=sparsity_threshold,
        extra_metadata={
            "evaluations": result.evaluations,
            "bestMetric": result.best_metric,
        },
    )
    # model-spec records the EXPANDED config actually trained, keeping each
    # coordinate's REAL data configuration (shard, random-effect type, bounds)
    # so the recorded spec round-trips through the parser
    spec = {
        cid: coordinate_configuration_to_string(
            cid,
            _dc.replace(
                coord_configs[cid],
                optimization_config=result.configuration[cid],
                reg_weights=(result.configuration[cid].regularization_weight,)
                if result.configuration[cid].regularization_weight
                else (),
            ),
        )
        for cid in result.configuration
    }
    with open(os.path.join(out_dir, MODEL_SPEC_FILE), "w") as f:
        json.dump(spec, f, indent=2)
    logger.info("saved model to %s", out_dir)


def run(args: argparse.Namespace, emitter: Optional[EventEmitter] = None) -> dict:
    """Full training pipeline (GameTrainingDriver.run:346-482). Returns a summary
    dict {"results": [...], "best_index": i, "output_directory": ...}."""
    # Multi-host init must precede EVERY other JAX touch (model loading,
    # data placement): jax.distributed.initialize after backend init either
    # errors or silently leaves the "global" mesh host-local.
    from photon_ml_tpu.cli.runtime import (
        arm_fault_plan_from_args,
        configure_compilation_cache,
        initialize_distributed_from_args,
        prepare_output_root,
    )

    # fault plan first: distributed.init is itself an injectable fault point
    arm_fault_plan_from_args(args)
    rank, nproc = initialize_distributed_from_args(args)
    configure_compilation_cache()
    emitter = emitter or EventEmitter()
    root = args.root_output_directory
    prepare_output_root(root, args.override_output_directory, rank, nproc)
    logger = PhotonLogger(
        os.path.join(
            root, "logs", "photon.log" if nproc == 1 else f"photon-r{rank}.log"
        ),
        level=args.log_level,
    )
    emitter.send_event(Event("PhotonSetupEvent", {"applicationName": args.application_name}))
    if rank == 0:
        # printForCommandLine parity (ScoptParser.scala:40): the run's exact
        # re-launchable command line, recorded next to its outputs
        from photon_ml_tpu.cli.parsers import write_command_line_artifact

        write_command_line_artifact(
            os.path.join(root, "command-line.txt"), args, build_arg_parser()
        )

    try:
        task = TaskType(args.training_task)

        shard_configs = dict(
            parse_feature_shard_configuration(a) for a in args.feature_shard_configurations
        )
        coord_configs = dict(
            parse_coordinate_configuration(a) for a in args.coordinate_configurations
        )
        update_sequence = [c for c in args.coordinate_update_sequence.split(",") if c]
        unknown = set(update_sequence) - set(coord_configs)
        if unknown:
            raise ValueError(f"Update sequence references unknown coordinates: {sorted(unknown)}")
        # estimator trains in coordinate_configurations insertion order = sequence
        coord_configs = {c: coord_configs[c] for c in update_sequence}
        # parse evaluator specs ONCE (reused for the suite below); per-group
        # evaluators' id tags must be read from the VALIDATION data even for
        # fixed-effect-only configs (AUC:userId needs the userId column) —
        # but only there: training data doesn't need them
        from photon_ml_tpu.evaluation.evaluators import MultiEvaluator

        evaluator_specs = (
            [parse_evaluator_spec(e) for e in args.evaluators.split(",") if e.strip()]
            if args.evaluators
            else []
        )
        evaluator_tags = sorted({
            ev.id_tag for ev in evaluator_specs if isinstance(ev, MultiEvaluator)
        })
        id_tags = sorted(
            {
                cfg.data_config.random_effect_type
                for cfg in coord_configs.values()
                if isinstance(cfg.data_config, RandomEffectDataConfiguration)
            }
        )

        index_maps = _load_index_maps(args.off_heap_index_map_directory, shard_configs)

        if nproc > 1:
            # multi-process training: fixed-effect-only configs run
            # per-process sharded ingest + global collectives; GAME configs
            # route through the entity exchange (docs/DISTRIBUTED.md) —
            # anything either path cannot reproduce fails loudly with reasons
            from photon_ml_tpu.cli.distributed_training import (
                run_multiprocess_fixed_effect,
                run_multiprocess_game,
            )

            has_re = any(
                isinstance(c.data_config, RandomEffectDataConfiguration)
                for c in coord_configs.values()
            )
            runner = run_multiprocess_game if has_re else run_multiprocess_fixed_effect
            emitter.send_event(Event("TrainingStartEvent"))
            summary = runner(
                args, rank, nproc, logger, root,
                task, coord_configs, shard_configs, index_maps,
            )
            emitter.send_event(
                Event("TrainingFinishEvent", {"bestIndex": summary["best_index"]})
            )
            return summary

        # date-partitioned inputs (GameDriver inputDataDateRange/DaysRange params;
        # IOUtils.getInputPathsWithinDateRange path expansion)
        train_paths = resolve_input_paths(
            args.input_data_directories,
            getattr(args, "input_data_date_range", None),
            getattr(args, "input_data_days_range", None),
        )

        # XLA backend init + pilot compile on a background thread: that
        # latency hides behind the host-side ingest below instead of adding
        # to time-to-first-update (estimator warm-up hook, data/pipeline.py)
        GameEstimator.warm_up_backend()
        ingest_workers = getattr(args, "ingest_workers", None)
        with Timed("read training data", logger):
            train_input, index_maps, _uids = read_merged_avro(
                train_paths, shard_configs, index_maps, id_tags,
                ingest_workers=ingest_workers,
            )
        logger.info("training data: %d samples, shards %s",
                    train_input.n, {s: m.shape[1] for s, m in train_input.features.items()})

        validation_input = None
        if args.validation_data_directories:
            validation_paths = resolve_input_paths(
                args.validation_data_directories,
                getattr(args, "validation_data_date_range", None),
                getattr(args, "validation_data_days_range", None),
            )
            with Timed("read validation data", logger):
                validation_input, _, _ = read_merged_avro(
                    validation_paths, shard_configs, index_maps,
                    sorted(set(id_tags) | set(evaluator_tags)),
                    ingest_workers=ingest_workers,
                )

        with Timed("data validation", logger):
            sanity_check_data(
                task,
                train_input.labels,
                offsets=train_input.offsets,
                weights=train_input.weights,
                feature_shards=train_input.features,
                validation_type=DataValidationType(args.data_validation),
            )

        # -- statistics + normalization (GameTrainingDriver.run:430-436) --------
        normalization_contexts = None
        norm_type = NormalizationType(args.normalization)
        if norm_type != NormalizationType.NONE or args.data_summary_directory:
            normalization_contexts = {}
            for shard, X in train_input.features.items():
                icpt = index_maps[shard].intercept_index
                with Timed(f"feature statistics [{shard}]", logger):
                    stats = FeatureDataStatistics.compute(X, intercept_index=icpt)
                if args.data_summary_directory:
                    _write_feature_summary(
                        os.path.join(args.data_summary_directory, f"{shard}-{SUMMARY_FILE}"),
                        shard, index_maps[shard], stats,
                    )
                if norm_type != NormalizationType.NONE:
                    normalization_contexts[shard] = NormalizationContext.build(norm_type, stats)
            if norm_type == NormalizationType.NONE:
                normalization_contexts = None

        # -- per-feature box constraints (COEFFICIENT_BOX_CONSTRAINTS param;
        # GLMSuite.createConstraintFeatureMap -> optimizer-native bounds) -------
        if args.coefficient_box_constraints:
            import dataclasses as _dc

            from photon_ml_tpu.estimators.config import FixedEffectDataConfiguration
            from photon_ml_tpu.optimization.constraints import build_bound_vectors

            coord_configs = {
                cid: (
                    _dc.replace(
                        cfg,
                        box_constraints=build_bound_vectors(
                            args.coefficient_box_constraints,
                            index_maps[cfg.data_config.feature_shard_id],
                        ),
                    )
                    if isinstance(cfg.data_config, FixedEffectDataConfiguration)
                    else cfg
                )
                for cid, cfg in coord_configs.items()
            }

        # -- warm start / partial retrain (GameTrainingDriver.scala:370-409) ----
        initial_model = None
        index_maps_by_coord = {
            cid: index_maps[cfg.data_config.feature_shard_id]
            for cid, cfg in coord_configs.items()
        }
        if args.model_input_directory:
            with Timed("load initial model", logger):
                initial_model = load_game_model(args.model_input_directory, index_maps_by_coord)
        locked = (
            [c for c in args.partial_retrain_locked_coordinates.split(",") if c]
            if args.partial_retrain_locked_coordinates
            else []
        )

        fe_storage_dtype = None
        if getattr(args, "fe_storage_dtype", None) == "bf16":
            import jax.numpy as jnp

            fe_storage_dtype = jnp.bfloat16

        mesh = None
        backend = getattr(args, "compute_backend", "host")
        if backend == "mesh":
            n_model = getattr(args, "mesh_model_devices", 1) or 1
            if n_model > 1:
                import jax

                from photon_ml_tpu.parallel import make_mesh2

                total = args.mesh_devices or len(jax.devices())
                if total % n_model:
                    raise ValueError(
                        f"--mesh-model-devices={n_model} must divide the device "
                        f"count {total}"
                    )
                mesh = make_mesh2(total // n_model, n_model)
            else:
                from photon_ml_tpu.parallel.mesh import make_mesh

                mesh = make_mesh(args.mesh_devices)

        estimator = GameEstimator(
            task=task,
            coordinate_configurations=coord_configs,
            n_iterations=args.coordinate_descent_iterations,
            normalization_contexts=normalization_contexts,
            variance_computation=VarianceComputationType(args.variance_computation_type),
            validation_evaluators=evaluator_specs,
            partial_retrain_locked_coordinates=locked,
            mesh=mesh,
            checkpoint_directory=args.checkpoint_directory,
            checkpoint_interval=args.checkpoint_interval,
            checkpoint_keep_generations=getattr(
                args, "checkpoint_keep_generations", 3
            ),
            fe_storage_dtype=fe_storage_dtype,
        )

        emitter.send_event(Event("TrainingStartEvent"))
        import contextlib

        profile_dir = getattr(args, "profile_output_directory", None)
        if profile_dir:
            import jax

            profiler_cm = jax.profiler.trace(profile_dir)
        else:
            profiler_cm = contextlib.nullcontext()
        with profiler_cm:
            with Timed("train", logger):
                results = estimator.fit(
                    train_input, validation_data=validation_input, initial_model=initial_model
                )
        # the program's spans (util/timed), once: where the time of set-up,
        # ingest and training went, by span name
        for name, (n, seconds) in sorted(span_summary().items()):
            logger.info("span %s: %d x, %.3f s", name, n, seconds)

        # -- hyperparameter tuning (GameTrainingDriver.runHyperparameterTuning) --
        tuning_mode = HyperparameterTuningMode(args.hyper_parameter_tuning)
        tuned_results = []
        if tuning_mode != HyperparameterTuningMode.NONE:
            if validation_input is None:
                raise ValueError("Hyperparameter tuning requires validation data")
            base_configs = results[-1].configuration
            primary = estimator.prepare_evaluation_suite(validation_input).evaluators[0]
            is_max = getattr(primary, "larger_is_better", True)
            fn = GameEstimatorEvaluationFunction(
                estimator=estimator,
                base_configs=base_configs,
                data=train_input,
                validation_data=validation_input,
                is_opt_max=is_max,
            )
            observations = fn.convert_observations(results)
            tuner = build_tuner(args.hyper_parameter_tuner)
            with Timed("hyperparameter tuning", logger):
                tuned_results = tuner.search(
                    args.hyper_parameter_tuning_iterations,
                    fn.num_params,
                    tuning_mode,
                    fn,
                    observations,
                )
            results = results + list(tuned_results)

        # -- model selection (GameTrainingDriver.selectBestModel:683-748) -------
        evaluated = [i for i, r in enumerate(results) if r.best_metric is not None]
        if evaluated:
            primary = estimator.prepare_evaluation_suite(validation_input).evaluators[0]
            bigger_better = getattr(primary, "larger_is_better", True)
            pick = max if bigger_better else min
            best_index = int(pick(evaluated, key=lambda i: results[i].best_metric))
        else:
            best_index = len(results) - 1  # no validation: last trained config
        logger.info("selected model %d of %d", best_index, len(results))

        # -- save (GameTrainingDriver.scala:759-826) -----------------------------
        output_mode = ModelOutputMode(args.output_mode)
        if output_mode != ModelOutputMode.NONE:
            _save_result(
                os.path.join(root, BEST_DIR), results[best_index], index_maps_by_coord,
                coord_configs, args.model_sparsity_threshold, logger,
            )
            if output_mode in (ModelOutputMode.ALL, ModelOutputMode.EXPLICIT, ModelOutputMode.TUNED):
                to_save = (
                    range(len(results))
                    if output_mode == ModelOutputMode.ALL
                    else range(len(results) - len(tuned_results), len(results))
                    if output_mode == ModelOutputMode.TUNED
                    else range(len(results) - len(tuned_results))
                )
                for i in to_save:
                    _save_result(
                        os.path.join(root, MODELS_DIR, str(i)), results[i],
                        index_maps_by_coord, coord_configs,
                        args.model_sparsity_threshold, logger,
                    )
            # persist index maps next to the models for scoring-time reuse
            for shard, imap in index_maps.items():
                imap.save(os.path.join(root, "index-maps", shard))

        # -- incident report: survived failures (rejected divergent updates,
        # checkpoint rollbacks) are an artifact, not just log lines ----------
        incidents = [
            inc.to_dict()
            for r in results
            if getattr(r, "descent", None) is not None
            for inc in getattr(r.descent, "incidents", [])
        ]
        if incidents:
            for inc in incidents:
                logger.warning("incident: %s", inc)
            with open(os.path.join(root, "incidents.json"), "w") as f:
                json.dump(incidents, f, indent=2)

        emitter.send_event(Event("TrainingFinishEvent", {"bestIndex": best_index}))
        return {
            "results": results,
            "best_index": best_index,
            "output_directory": root,
            "incidents": incidents,
        }
    finally:
        logger.close()


def main(argv=None) -> int:
    args = build_arg_parser().parse_args(argv)
    run(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
