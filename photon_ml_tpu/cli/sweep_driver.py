"""Model-selection sweep CLI driver.

Drives ``photon_ml_tpu/sweep``: ingest the training + validation data once,
then run the batched Bayesian hyperparameter sweep — every round trains a
POPULATION of candidate settings as one vmapped coordinate-descent run over
the shared device-resident datasets, scores them on the validation data, and
feeds the results to the GP + Expected Improvement search. The winner commits
as a generational checkpoint (``--checkpoint-directory``) the serving
hot-swap watcher can pick up directly, plus a reference-format model export
under the output root.

Axis grammar (``--sweep-axis``, repeatable)::

    coordinate=global,parameter=l2,min=0.01,max=100,transform=LOG
    coordinate=per-user,parameter=l2,min=0.001,max=10,transform=LOG
    coordinate=global,parameter=down_sampling_rate,min=0.2,max=0.9

Parameters: ``l2`` (any coordinate), ``l1`` (coordinates whose base config
carries an L1 term), ``down_sampling_rate`` (fixed-effect coordinates with a
down-sampling base rate). Transforms: LOG, SQRT, or none.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from photon_ml_tpu.cli.parsers import (
    _pop,
    add_version_argument,
    parse_coordinate_configuration,
    parse_evaluator_spec,
    parse_feature_shard_configuration,
    parse_kv_args,
)
from photon_ml_tpu.data.readers import read_merged_avro
from photon_ml_tpu.estimators.config import RandomEffectDataConfiguration
from photon_ml_tpu.estimators.game_estimator import GameEstimator
from photon_ml_tpu.sweep import SweepAxis, SweepConfig, SweepRunner, SweepSpec
from photon_ml_tpu.types import HyperparameterTuningMode, TaskType
from photon_ml_tpu.util import PhotonLogger, Timed
from photon_ml_tpu.util.date_range import resolve_input_paths

STATS_FILE = "sweep-stats.json"
EXPORT_DIR = "export"


def parse_sweep_axis(spec: str) -> SweepAxis:
    """``coordinate=...,parameter=...,min=...,max=...[,transform=...]`` —
    the shared composite grammar (parse_kv_args: duplicate keys rejected)."""
    kv = parse_kv_args(spec)
    axis = SweepAxis(
        coordinate_id=_pop(kv, "coordinate", required=True),
        parameter=_pop(kv, "parameter", required=True),
        min=float(_pop(kv, "min", required=True)),
        max=float(_pop(kv, "max", required=True)),
        transform=_pop(kv, "transform") or None,
    )
    if kv:
        raise ValueError(f"Unknown sweep-axis keys {sorted(kv)} in {spec!r}")
    return axis


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="sweep-driver",
        description="Batched (vmapped) hyperparameter sweep for GAME training.",
    )
    add_version_argument(p)
    p.add_argument("--input-data-directories", required=True,
                   help="Comma-separated training data paths (Avro files/dirs)")
    p.add_argument("--validation-data-directories", required=True,
                   help="Held-out data the candidates are selected on")
    p.add_argument("--input-data-date-range", default=None)
    p.add_argument("--input-data-days-range", default=None)
    p.add_argument("--root-output-directory", required=True)
    p.add_argument("--override-output-directory", action="store_true")
    p.add_argument("--feature-shard-configurations", action="append", required=True)
    p.add_argument("--training-task", required=True,
                   choices=[t.value for t in TaskType])
    p.add_argument("--coordinate-configurations", action="append", required=True)
    p.add_argument("--coordinate-update-sequence", required=True)
    p.add_argument("--evaluators", default=None,
                   help="Comma-separated; the FIRST is the selection metric")
    p.add_argument("--sweep-axis", action="append", required=True,
                   help="coordinate=...,parameter=l2|l1|down_sampling_rate,"
                        "min=...,max=...[,transform=LOG|SQRT]")
    p.add_argument("--sweep-rounds", type=int, default=3,
                   help="Bayesian search rounds (each trains one population)")
    p.add_argument("--sweep-population", type=int, default=8,
                   help="Settings trained per round as one vmapped program")
    p.add_argument("--sweep-mode", default="BAYESIAN",
                   choices=["BAYESIAN", "RANDOM"])
    p.add_argument("--sweep-seed", type=int, default=0)
    p.add_argument("--sweep-iterations", type=int, default=1,
                   help="Coordinate-descent passes per candidate")
    p.add_argument("--sweep-path", default="auto",
                   choices=["auto", "vmapped", "sequential", "fused"],
                   help="Population execution path (auto follows the spec: "
                        "dict per-entity L2 overrides need sequential; "
                        "fused = one jit per train call covering all "
                        "settings x coordinates x iterations)")
    p.add_argument("--sweep-warm-start", action="store_true",
                   help="Seed each round's lanes from the committed table "
                        "of the nearest previous-round setting (glmnet-style "
                        "paths across Bayesian rounds; implies the fused "
                        "path)")
    p.add_argument("--sweep-freeze-tol", type=float, default=None,
                   help="Per-lane early exit: freeze a lane whose total "
                        "training score moved at most tol*(1+max|score|) "
                        "across a pass (implies the fused path; frozen "
                        "lanes carry their committed state bitwise)")
    p.add_argument("--sweep-freeze-min-iterations", type=int, default=1,
                   help="Completed passes before any lane may freeze")
    p.add_argument("--sweep-domination-bound", type=float, default=None,
                   help="Freeze lanes whose training loss exceeds this "
                        "bound (requires --sweep-freeze-tol to arm early "
                        "exit; use a negative --sweep-freeze-tol for "
                        "domination-only freezing)")
    p.add_argument("--checkpoint-directory", required=True,
                   help="Winner commits here as a generational checkpoint "
                        "(the layout serving/hotswap.GenerationWatcher polls)")
    p.add_argument("--checkpoint-keep-generations", type=int, default=4)
    p.add_argument("--fault-plan", default=None,
                   help="Deterministic fault injection plan "
                        "(resilience/faultpoints.py; also PHOTON_FAULT_PLAN)")
    from photon_ml_tpu.cli.runtime import add_ingest_arguments

    add_ingest_arguments(p)
    return p


def run(args: argparse.Namespace) -> dict:
    """Ingest → sweep → winner commit + export. Returns a summary dict."""
    from photon_ml_tpu.cli.runtime import (
        arm_fault_plan_from_args,
        configure_compilation_cache,
        prepare_output_root,
    )

    arm_fault_plan_from_args(args)
    configure_compilation_cache()
    root = args.root_output_directory
    prepare_output_root(root, args.override_output_directory, 0, 1)
    logger = PhotonLogger(os.path.join(root, "logs", "photon.log"))
    try:
        task = TaskType(args.training_task)
        shard_configs = dict(
            parse_feature_shard_configuration(a)
            for a in args.feature_shard_configurations
        )
        coord_configs = dict(
            parse_coordinate_configuration(a) for a in args.coordinate_configurations
        )
        update_sequence = [c for c in args.coordinate_update_sequence.split(",") if c]
        unknown = set(update_sequence) - set(coord_configs)
        if unknown:
            raise ValueError(
                f"Update sequence references unknown coordinates: {sorted(unknown)}"
            )
        coord_configs = {c: coord_configs[c] for c in update_sequence}
        from photon_ml_tpu.evaluation.evaluators import MultiEvaluator

        evaluator_specs = (
            [parse_evaluator_spec(e) for e in args.evaluators.split(",") if e.strip()]
            if args.evaluators
            else []
        )
        evaluator_tags = sorted(
            {ev.id_tag for ev in evaluator_specs if isinstance(ev, MultiEvaluator)}
        )
        id_tags = sorted(
            {
                cfg.data_config.random_effect_type
                for cfg in coord_configs.values()
                if isinstance(cfg.data_config, RandomEffectDataConfiguration)
            }
        )

        GameEstimator.warm_up_backend()
        ingest_workers = getattr(args, "ingest_workers", None)
        train_paths = resolve_input_paths(
            args.input_data_directories,
            getattr(args, "input_data_date_range", None),
            getattr(args, "input_data_days_range", None),
        )
        with Timed("read training data", logger):
            train_input, index_maps, _uids = read_merged_avro(
                train_paths, shard_configs, {}, id_tags,
                ingest_workers=ingest_workers,
            )
        validation_paths = resolve_input_paths(
            args.validation_data_directories, None, None
        )
        with Timed("read validation data", logger):
            validation_input, _, _ = read_merged_avro(
                validation_paths, shard_configs, index_maps,
                sorted(set(id_tags) | set(evaluator_tags)),
                ingest_workers=ingest_workers,
            )
        logger.info(
            "sweep data: %d train / %d validation samples",
            train_input.n,
            validation_input.n,
        )

        estimator = GameEstimator(
            task=task,
            coordinate_configurations=coord_configs,
            n_iterations=args.sweep_iterations,
            validation_evaluators=evaluator_specs,
        )
        spec = SweepSpec(axes=tuple(parse_sweep_axis(a) for a in args.sweep_axis))
        vmapped: object = "auto"
        fused: object = "auto"
        if args.sweep_path == "fused":
            fused = True
        elif args.sweep_path != "auto":
            vmapped = args.sweep_path == "vmapped"
            fused = False
        early_exit = None
        if args.sweep_freeze_tol is not None:
            from photon_ml_tpu.sweep import EarlyExitConfig

            early_exit = EarlyExitConfig(
                freeze_tol=args.sweep_freeze_tol,
                min_iterations=args.sweep_freeze_min_iterations,
                domination_bound=args.sweep_domination_bound,
            )
        elif args.sweep_domination_bound is not None:
            raise ValueError(
                "--sweep-domination-bound needs --sweep-freeze-tol to arm "
                "early exit (use a negative tol for domination-only)"
            )
        config = SweepConfig(
            checkpoint_directory=args.checkpoint_directory,
            rounds=args.sweep_rounds,
            population=args.sweep_population,
            mode=HyperparameterTuningMode(args.sweep_mode),
            seed=args.sweep_seed,
            n_iterations=args.sweep_iterations,
            vmapped=vmapped,
            fused=fused,
            early_exit=early_exit,
            warm_start=args.sweep_warm_start,
            export_directory=os.path.join(root, EXPORT_DIR),
            keep_generations=args.checkpoint_keep_generations,
        )
        runner = SweepRunner(estimator, spec, config)
        index_maps_by_coord = {
            cid: index_maps[cfg.data_config.feature_shard_id]
            for cid, cfg in coord_configs.items()
        }
        with Timed("sweep", logger):
            result = runner.run(
                train_input, validation_input, index_maps=index_maps_by_coord
            )

        stats = {
            "task": task.value,
            "axes": spec.describe(),
            "mode": config.mode.value,
            "rounds": config.rounds,
            "population": config.population,
            "seed": config.seed,
            "path": result.path,
            "warm_start": config.warm_start,
            "early_exit": (
                None
                if early_exit is None
                else {
                    "freeze_tol": early_exit.freeze_tol,
                    "min_iterations": early_exit.min_iterations,
                    "domination_bound": early_exit.domination_bound,
                }
            ),
            "restored": result.restored,
            "models_evaluated": result.models_evaluated,
            "winner": {
                "settings": result.winner_settings,
                "metric": result.winner_metric,
                "metrics": result.winner_metrics,
                "round": result.winner_round,
                "lane": result.winner_lane,
            },
            "history": [r.to_dict() for r in result.rounds],
            "incidents": result.incidents,
            "checkpoint_path": result.checkpoint_path,
            "export_path": result.export_path,
            # per-lane observability: the history rows above carry each
            # round's lane_iterations / frozen_at / freeze_fraction; these
            # are the sweep-level rollups + per-round acquisition seconds
            "total_solver_iterations": result.total_solver_iterations,
            "freeze_fraction": result.freeze_fraction,
            "timings": result.timings,
        }
        with open(os.path.join(root, STATS_FILE), "w") as f:
            json.dump(stats, f, indent=2)
        logger.info(
            "sweep winner %s (%s) -> %s",
            result.winner_settings,
            result.winner_metrics,
            result.checkpoint_path,
        )
        return stats
    finally:
        logger.close()


def main(argv=None) -> int:
    args = build_arg_parser().parse_args(argv)
    run(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
