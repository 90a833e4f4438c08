"""GAME scoring CLI driver.

Parity target: photon-client cli/game/scoring/GameScoringDriver.scala:39-284 —
read data, load a saved GAME model, score through GameTransformer, write
ScoringResultAvro files, optionally evaluate when the data has labels.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

from photon_ml_tpu.cli.game_training_driver import _load_index_maps
from photon_ml_tpu.cli.parsers import (
    add_version_argument,
    parse_evaluator_spec,
    parse_feature_shard_configuration,
)
from photon_ml_tpu.data import avro_io
from photon_ml_tpu.data.readers import read_merged_avro
from photon_ml_tpu.io.model_io import load_game_model
from photon_ml_tpu.models.game import RandomEffectModel
from photon_ml_tpu.transformers.game_transformer import GameTransformer
from photon_ml_tpu.util import PhotonLogger, Timed
from photon_ml_tpu.util.date_range import resolve_input_paths

SCORES_DIR = "scores"


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="game-scoring-driver", description="Score data with a saved GAME model."
    )
    add_version_argument(p)
    p.add_argument("--input-data-directories", required=True)
    p.add_argument("--input-data-date-range", default=None,
                   help="yyyyMMdd-yyyyMMdd inclusive; expands each input dir to "
                        "its <dir>/yyyy/MM/dd day partitions")
    p.add_argument("--input-data-days-range", default=None,
                   help="START-END in days ago (START >= END), e.g. 90-1")
    p.add_argument("--model-input-directory", required=True)
    p.add_argument("--root-output-directory", required=True)
    p.add_argument("--override-output-directory", action="store_true")
    p.add_argument("--feature-shard-configurations", action="append", required=True)
    p.add_argument("--off-heap-index-map-directory", default=None)
    p.add_argument("--evaluators", default=None)
    p.add_argument("--model-id", default=None, help="ID to tag scores with")
    p.add_argument("--compute-backend", default="host", choices=["host", "mesh"],
                   help="'mesh' scores with datasets sharded over the device mesh")
    p.add_argument("--scoring-engine", default="fused", choices=["fused", "eager"],
                   help="'fused' (default) compiles the whole scoring pipeline "
                        "into one jit-cached XLA program per batch bucket with "
                        "device-resident coefficient tables; 'eager' keeps the "
                        "per-coordinate dataset-rebuild path")
    p.add_argument("--mesh-devices", type=int, default=None,
                   help="Device count for --compute-backend=mesh (default: all)")
    from photon_ml_tpu.cli.runtime import add_distributed_arguments, add_ingest_arguments

    add_ingest_arguments(p)
    add_distributed_arguments(
        p,
        "multi-process scoring: each process scores its round-robin slice of "
        "the input part files and writes its own output part file (the "
        "executor-parallel form of GameScoringDriver)",
    )
    p.add_argument("--log-data-and-model-stats", action="store_true")
    p.add_argument("--log-level", default="INFO")
    p.add_argument("--application-name", default="game-scoring")
    # Spark-isms, accepted and ignored
    p.add_argument("--spill-scores-to-disk", action="store_true", help=argparse.SUPPRESS)
    return p


def run(args: argparse.Namespace) -> dict:
    from photon_ml_tpu.cli.runtime import initialize_distributed_from_args

    rank, nproc = initialize_distributed_from_args(args)
    if nproc > 1:
        if args.evaluators:
            raise ValueError(
                "evaluators need globally sorted scores; run them single-process "
                "on the written score files instead of multi-process scoring"
            )
        if getattr(args, "compute_backend", "host") == "mesh":
            raise ValueError(
                "--compute-backend=mesh and multi-process scoring are exclusive: "
                "each process already scores its own input slice host-locally"
            )

    from photon_ml_tpu.cli.runtime import configure_compilation_cache

    configure_compilation_cache()
    root = args.root_output_directory
    from photon_ml_tpu.cli.runtime import prepare_output_root

    prepare_output_root(root, args.override_output_directory, rank, nproc)
    logger = PhotonLogger(
        os.path.join(
            root, "logs", "photon.log" if nproc == 1 else f"photon-r{rank}.log"
        ),
        level=args.log_level,
    )
    try:
        shard_configs = dict(
            parse_feature_shard_configuration(a) for a in args.feature_shard_configurations
        )
        # prefer index maps saved by the training driver at <root>/index-maps —
        # the model may live at <root>/best (one level up) or <root>/models/<i>
        # (two levels up) — then the explicit off-heap dir
        # farthest first so the NEAREST directory wins the dict.update
        index_maps = {}
        for rel in (os.path.join("..", ".."), ".."):
            index_maps.update(
                _load_index_maps(
                    os.path.join(args.model_input_directory, rel, "index-maps"),
                    shard_configs,
                )
            )
        index_maps.update(
            _load_index_maps(args.off_heap_index_map_directory, shard_configs) or {}
        )
        maps_for_load = dict(index_maps)

        # model first: its coordinates define the id tags the data needs.
        # load_game_model keys index maps by COORDINATE id; model dirs carry
        # the shard id in id-info, so map via an initial listing pass.
        coord_shards = _coordinate_shards(args.model_input_directory)
        missing = sorted({s for s in coord_shards.values() if s not in maps_for_load})
        if missing:
            raise FileNotFoundError(
                f"No saved index maps found for shard(s) {missing}; expected "
                f"<model-dir>/../index-maps/<shard>.npz (training driver output) "
                f"or --off-heap-index-map-directory"
            )
        with Timed("load model", logger):
            model = load_game_model(
                args.model_input_directory,
                {cid: maps_for_load[shard] for cid, shard in coord_shards.items()},
            )
        id_tags = sorted(
            {m.re_type for _, m in model if isinstance(m, RandomEffectModel)}
        )

        input_paths = resolve_input_paths(
            args.input_data_directories,
            getattr(args, "input_data_date_range", None),
            getattr(args, "input_data_days_range", None),
        )
        if nproc > 1:
            # file-level round-robin: every process reads and scores only its
            # slice of the part files (index maps come from the saved training
            # maps, so processes agree on the feature space by construction)
            all_files = avro_io.container_files(input_paths)
            input_paths = all_files[rank::nproc]
            logger.info(
                "process %d/%d scoring %d of %d part files",
                rank, nproc, len(input_paths), len(all_files),
            )
            if not input_paths:
                logger.info("no part files for this process; nothing to score")
                return {"scores": np.zeros(0), "metrics": {}, "output_directory": root}
        # scoring-program compile latency hides behind ingest (pipeline.py)
        from photon_ml_tpu.estimators.game_estimator import GameEstimator

        GameEstimator.warm_up_backend()
        with Timed("read data", logger):
            data, index_maps, uids = read_merged_avro(
                input_paths, shard_configs, index_maps, id_tags,
                ingest_workers=getattr(args, "ingest_workers", None),
            )
        logger.info("scoring %d samples", data.n)

        evaluator_specs = (
            [parse_evaluator_spec(e) for e in args.evaluators.split(",") if e]
            if args.evaluators
            else []
        )
        mesh = None
        if getattr(args, "compute_backend", "host") == "mesh":
            from photon_ml_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(getattr(args, "mesh_devices", None))
        transformer = GameTransformer(
            model=model, evaluators=evaluator_specs, mesh=mesh,
            engine=getattr(args, "scoring_engine", "fused"),
        )
        with Timed("score", logger):
            scores, metrics = transformer.transform(data)
        if metrics:
            for name, value in metrics.items():
                logger.info("metric %s = %.6f", name, value)

        with Timed("write scores", logger):
            _write_scores(
                os.path.join(root, SCORES_DIR, f"part-{rank:05d}.avro"),
                uids, scores, data, args.model_id or "",
            )
        return {"scores": scores, "metrics": metrics, "output_directory": root}
    finally:
        logger.close()


def _coordinate_shards(model_dir: str) -> dict[str, str]:
    """coordinate id -> feature shard id from the saved model's id-info files
    (both this framework's JSON dialect and the reference's plain-text one —
    model_io._read_id_info)."""
    from photon_ml_tpu.io.model_io import _read_id_info

    out: dict[str, str] = {}
    for section, is_re in (("fixed-effect", False), ("random-effect", True)):
        base = os.path.join(model_dir, section)
        if not os.path.isdir(base):
            continue
        for cid in os.listdir(base):
            info = os.path.join(base, cid, "id-info")  # model_io.ID_INFO
            if os.path.exists(info):
                out[cid] = _read_id_info(info, random_effect=is_re).get(
                    "featureShardId", "global"
                )
    return out


def _write_scores(path, uids, scores, data, model_id: str, use_native: bool = True) -> None:
    """ScoringResultAvro records (GameScoringDriver.saveScoresToHDFS:229-256).

    The record payloads are encoded natively (photon_ml_tpu/native/avro_block_decoder.cpp
    photon_encode_scores — the output analog of the ingest decoder) when the
    library is available, falling back to the pure-Python encoder otherwise;
    both produce the same records (block boundaries differ: 65536 records per
    native block vs write_container's 4096)."""
    import numpy as np

    has_labels = data.has_labels
    os.makedirs(os.path.dirname(path), exist_ok=True)

    n = len(scores)
    from photon_ml_tpu.data import native_avro

    if use_native and native_avro.available():
        labels = np.asarray(data.labels, dtype=np.float64) if has_labels else None
        weights = np.asarray(data.weights, dtype=np.float64)
        scores_arr = np.asarray(scores, dtype=np.float64)

        def blocks(block_count=65536):
            for start in range(0, n, block_count):
                stop = min(start + block_count, n)
                uid_slice = (
                    uids[start:stop]
                    if uids is not None
                    else (str(i) for i in range(start, stop))
                )
                payload = native_avro.encode_scores(
                    uid_slice,
                    None if labels is None else labels[start:stop],
                    model_id,
                    scores_arr[start:stop],
                    weights[start:stop],
                )
                if payload is None:  # lib vanished mid-write: surface loudly
                    raise RuntimeError("native encoder failed mid-write")
                yield stop - start, payload

        avro_io.write_container_raw(path, avro_io.SCORING_RESULT_SCHEMA, blocks())
        return

    def records():
        for i in range(n):
            yield {
                "uid": str(uids[i]) if uids is not None else str(i),
                "label": float(data.labels[i]) if has_labels else None,
                "modelId": model_id,
                "predictionScore": float(scores[i]),
                "weight": float(data.weights[i]),
                "metadataMap": None,
            }

    avro_io.write_container(path, avro_io.SCORING_RESULT_SCHEMA, records())


def main(argv=None) -> int:
    run(build_arg_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    sys.exit(main())
