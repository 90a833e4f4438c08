"""CLI driver integration tests: full train -> score pipeline over generated
Avro fixtures, parser round-trips, validators, feature indexing. Mirrors the
reference's GameTrainingDriverIntegTest / GameScoringDriverIntegTest /
FeatureIndexingDriverIntegTest pattern (photon-client src/integTest) on the
simulated CPU platform.
"""

import json
import os

import numpy as np
import pytest

from photon_ml_tpu.cli import game_scoring_driver, game_training_driver
from photon_ml_tpu.cli import feature_indexing_driver, name_and_term_bags_driver
from photon_ml_tpu.cli.parsers import (
    coordinate_configuration_to_string,
    parse_coordinate_configuration,
    parse_evaluator_spec,
    parse_feature_shard_configuration,
)
from photon_ml_tpu.data import avro_io
from photon_ml_tpu.data.validators import DataValidationType, sanity_check_data
from photon_ml_tpu.estimators.config import (
    FixedEffectDataConfiguration,
    RandomEffectDataConfiguration,
)
from photon_ml_tpu.evaluation.evaluators import Evaluator, MultiEvaluator
from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType


# --------------------------------------------------------------- fixtures


def write_glmix_avro(path, rng, n=500, d=5, n_users=8, w=None, bias=None):
    """TrainingExampleAvro files with a global bag + per-user ids in metadataMap.
    Pass w/bias to share the ground truth across train/validation splits."""
    w = rng.normal(size=d) if w is None else w
    bias = rng.normal(size=n_users) * 1.5 if bias is None else bias
    X = rng.normal(size=(n, d))
    users = rng.integers(0, n_users, size=n)
    z = X @ w + bias[users]
    y = (z + 0.3 * rng.normal(size=n) > 0).astype(np.float64)

    def records():
        for i in range(n):
            yield {
                "uid": f"s{i}",
                "label": float(y[i]),
                "features": [
                    {"name": f"f{j}", "term": "", "value": float(X[i, j])}
                    for j in range(d)
                ],
                "metadataMap": {"userId": f"u{users[i]}"},
                "weight": 1.0,
                "offset": 0.0,
            }

    avro_io.write_container(path, avro_io.TRAINING_EXAMPLE_SCHEMA, records())
    return X, y, users, w, bias


FE_COORD = (
    "name=global,feature.shard=shardA,min.partitions=1,optimizer=LBFGS,"
    "max.iter=50,tolerance=1e-8,regularization=L2,reg.weights=1.0"
)
RE_COORD = (
    "name=per-user,random.effect.type=userId,feature.shard=shardA,"
    "min.partitions=1,optimizer=LBFGS,max.iter=50,tolerance=1e-8,"
    "regularization=L2,reg.weights=1.0"
)


# --------------------------------------------------------------- parsers


class TestParsers:
    def test_feature_shard_configuration(self):
        name, cfg = parse_feature_shard_configuration(
            "name=shardA,feature.bags=features|userFeatures,intercept=false"
        )
        assert name == "shardA"
        assert cfg.feature_bags == ("features", "userFeatures")
        assert not cfg.has_intercept

    def test_feature_shard_rejects_unknown_keys(self):
        with pytest.raises(ValueError, match="Unknown"):
            parse_feature_shard_configuration("name=a,feature.bags=f,bogus=1")

    def test_fixed_effect_coordinate(self):
        name, cfg = parse_coordinate_configuration(FE_COORD)
        assert name == "global"
        assert isinstance(cfg.data_config, FixedEffectDataConfiguration)
        oc = cfg.optimization_config
        assert oc.optimizer_config.optimizer_type == OptimizerType.LBFGS
        assert oc.optimizer_config.max_iterations == 50
        assert oc.regularization_context.regularization_type == RegularizationType.L2
        assert cfg.reg_weights == (1.0,)

    def test_random_effect_coordinate(self):
        arg = (
            "name=per-user,random.effect.type=userId,feature.shard=shardA,"
            "min.partitions=4,optimizer=TRON,max.iter=30,tolerance=1e-6,"
            "active.data.lower.bound=2,active.data.upper.bound=100,"
            "reg.weights=0.1|1|10"
        )
        name, cfg = parse_coordinate_configuration(arg)
        dc = cfg.data_config
        assert isinstance(dc, RandomEffectDataConfiguration)
        assert dc.random_effect_type == "userId"
        assert dc.active_data_lower_bound == 2
        assert dc.active_data_upper_bound == 100
        assert cfg.reg_weights == (0.1, 1.0, 10.0)

    def test_random_only_keys_rejected_for_fixed(self):
        with pytest.raises(ValueError, match="random-effect"):
            parse_coordinate_configuration(
                "name=a,feature.shard=s,optimizer=LBFGS,max.iter=5,tolerance=1e-3,"
                "active.data.upper.bound=10"
            )

    def test_down_sampling_rejected_for_random(self):
        with pytest.raises(ValueError, match="fixed-effect"):
            parse_coordinate_configuration(
                "name=a,random.effect.type=u,feature.shard=s,optimizer=LBFGS,"
                "max.iter=5,tolerance=1e-3,down.sampling.rate=0.5"
            )

    def test_round_trip(self):
        for arg in (FE_COORD, RE_COORD):
            name, cfg = parse_coordinate_configuration(arg)
            printed = coordinate_configuration_to_string(name, cfg)
            name2, cfg2 = parse_coordinate_configuration(printed)
            assert name2 == name
            assert cfg2 == cfg

    def test_projected_dim_extension(self):
        _, cfg = parse_coordinate_configuration(
            "name=a,random.effect.type=u,feature.shard=s,optimizer=LBFGS,"
            "max.iter=5,tolerance=1e-3,projected.dim=16,projection.seed=3"
        )
        assert cfg.data_config.projector.projected_dim == 16
        assert cfg.data_config.projector.seed == 3

    def test_evaluator_specs(self):
        e = parse_evaluator_spec("AUC")
        assert isinstance(e, Evaluator) and e.name == "AUC"
        m = parse_evaluator_spec("AUC:userId")
        assert isinstance(m, MultiEvaluator)
        p = parse_evaluator_spec("PRECISION@5:userId")
        assert isinstance(p, MultiEvaluator) and "5" in p.base.name


# --------------------------------------------------------------- validators


class TestValidators:
    def test_passes_clean_data(self):
        sanity_check_data(
            TaskType.LOGISTIC_REGRESSION,
            labels=np.array([0.0, 1.0, 1.0]),
            offsets=np.zeros(3),
            weights=np.ones(3),
            feature_shards={"s": np.ones((3, 2))},
        )

    def test_rejects_non_binary_labels_for_logistic(self):
        with pytest.raises(ValueError, match="non-binary"):
            sanity_check_data(TaskType.LOGISTIC_REGRESSION, labels=np.array([0.0, 2.0]))

    def test_rejects_negative_labels_for_poisson(self):
        with pytest.raises(ValueError, match="negative"):
            sanity_check_data(TaskType.POISSON_REGRESSION, labels=np.array([1.0, -2.0]))

    def test_rejects_nan_features(self):
        with pytest.raises(ValueError, match="non-finite feature"):
            sanity_check_data(
                TaskType.LINEAR_REGRESSION,
                labels=np.array([0.5, 1.5]),
                feature_shards={"s": np.array([[1.0, np.nan], [0.0, 1.0]])},
            )

    def test_disabled_mode_skips(self):
        sanity_check_data(
            TaskType.LOGISTIC_REGRESSION,
            labels=np.array([5.0]),  # invalid, but skipped
            validation_type=DataValidationType.VALIDATE_DISABLED,
        )


# --------------------------------------------------------------- drivers


class TestTrainScorePipeline:
    @pytest.fixture(scope="class")
    def fixture_dir(self, tmp_path_factory):
        base = tmp_path_factory.mktemp("cli")
        rng = np.random.default_rng(0)
        _, _, _, w, bias = write_glmix_avro(str(base / "train.avro"), rng)
        write_glmix_avro(str(base / "validate.avro"), rng, n=300, w=w, bias=bias)
        return base

    @pytest.fixture(scope="class")
    def trained(self, fixture_dir):
        out = fixture_dir / "output"
        rc = game_training_driver.main([
            "--training-task", "LOGISTIC_REGRESSION",
            "--input-data-directories", str(fixture_dir / "train.avro"),
            "--validation-data-directories", str(fixture_dir / "validate.avro"),
            "--root-output-directory", str(out),
            "--feature-shard-configurations", "name=shardA,feature.bags=features",
            "--coordinate-configurations", FE_COORD,
            "--coordinate-configurations", RE_COORD,
            "--coordinate-update-sequence", "global,per-user",
            "--coordinate-descent-iterations", "2",
            "--evaluators", "AUC",
            "--data-validation", "VALIDATE_FULL",
            "--output-mode", "ALL",
            # generational checkpoints: the serving-driver test consumes them
            "--checkpoint-directory", str(fixture_dir / "ckpt"),
        ])
        assert rc == 0
        return out

    def test_training_outputs(self, trained):
        assert (trained / "best" / "model-metadata.json").exists()
        assert (trained / "best" / "model-spec.json").exists()
        assert (trained / "best" / "fixed-effect" / "global").is_dir()
        assert (trained / "best" / "random-effect" / "per-user").is_dir()
        assert (trained / "models" / "0").is_dir()
        assert (trained / "index-maps" / "shardA.npz").exists()
        assert (trained / "logs" / "photon.log").exists()
        meta = json.loads((trained / "best" / "model-metadata.json").read_text())
        assert meta["bestMetric"] is not None and meta["bestMetric"] > 0.7  # AUC

    def test_scoring_pipeline(self, fixture_dir, trained):
        out = fixture_dir / "scores-out"
        rc = game_scoring_driver.main([
            "--input-data-directories", str(fixture_dir / "validate.avro"),
            "--model-input-directory", str(trained / "best"),
            "--root-output-directory", str(out),
            "--feature-shard-configurations", "name=shardA,feature.bags=features",
            "--evaluators", "AUC",
        ])
        assert rc == 0
        recs = list(avro_io.read_container_dir(str(out / "scores")))
        assert len(recs) == 300
        scores = np.array([r["predictionScore"] for r in recs])
        labels = np.array([r["label"] for r in recs])
        pos, neg = scores[labels == 1], scores[labels == 0]
        auc = (pos[:, None] > neg[None, :]).mean()
        assert auc > 0.7

    def test_serving_driver_replays_through_frontend(self, fixture_dir, trained):
        """End-to-end serving replay: newest checkpoint generation served
        through the micro-batching frontend, scores BITWISE equal to direct
        per-request scoring of that generation's model, no sheds, scores avro
        written."""
        from photon_ml_tpu.cli import serving_driver
        from photon_ml_tpu.data.readers import read_merged_avro
        from photon_ml_tpu.io.checkpoint import list_generations, load_generation
        from photon_ml_tpu.serving import clear_engine_cache
        from photon_ml_tpu.serving.hotswap import model_from_state
        from photon_ml_tpu.transformers import GameTransformer

        clear_engine_cache()
        ckpt_root = str(fixture_dir / "ckpt" / "config_0")
        out = fixture_dir / "serving-out"
        chunk = 64
        result = serving_driver.run(serving_driver.build_arg_parser().parse_args([
            "--checkpoint-directory", ckpt_root,
            "--input-data-directories", str(fixture_dir / "validate.avro"),
            "--root-output-directory", str(out),
            "--feature-shard-configurations", "name=shardA,feature.bags=features",
            "--index-map-directory", str(trained / "index-maps"),
            "--serving-request-batch", str(chunk),
            "--serving-max-wait-ms", "1.0",
        ]))
        stats = result["stats"]
        assert stats["requests_shed"] == 0
        assert stats["requests_served"] == -(-300 // chunk)
        scores = result["scores"]
        assert scores.shape == (300,) and not np.isnan(scores).any()

        # reference: chunk-wise direct scoring of the served generation
        gens = list_generations(ckpt_root)
        assert stats["generations_served"] == [gens[-1][0]]
        model = model_from_state(load_generation(gens[-1][1]))
        from photon_ml_tpu.cli.game_training_driver import _load_index_maps

        shard_cfg = dict([parse_feature_shard_configuration(
            "name=shardA,feature.bags=features")])
        index_maps = _load_index_maps(str(trained / "index-maps"), shard_cfg)
        data, _, _ = read_merged_avro(
            [str(fixture_dir / "validate.avro")], shard_cfg, index_maps, ["userId"]
        )
        transformer = GameTransformer(model=model)
        expected = np.concatenate([
            transformer.score(data.select(np.arange(s, min(s + chunk, data.n))))
            for s in range(0, data.n, chunk)
        ])
        assert scores.dtype == expected.dtype
        np.testing.assert_array_equal(scores, expected)
        # scores avro landed in the batch-scoring format
        recs = list(avro_io.read_container_dir(str(out / "scores")))
        assert len(recs) == 300

    def test_serving_driver_fleet_mode_replays_bitwise(self, fixture_dir, trained):
        """--fleet-replicas 2 --fleet-http-port 0: the replay runs through the
        ModelRouter's replica set with the HTTP endpoint live; scores are
        BITWISE identical to the single-frontend replay of the same
        generation, and the stats JSON carries the sheds-by-cause breakout,
        per-generation served counts, and the HTTP endpoint address."""
        from photon_ml_tpu.cli import serving_driver
        from photon_ml_tpu.serving import clear_engine_cache

        clear_engine_cache()
        ckpt_root = str(fixture_dir / "ckpt" / "config_0")
        out = fixture_dir / "serving-fleet-out"
        chunk = 64
        result = serving_driver.run(serving_driver.build_arg_parser().parse_args([
            "--checkpoint-directory", ckpt_root,
            "--input-data-directories", str(fixture_dir / "validate.avro"),
            "--root-output-directory", str(out),
            "--feature-shard-configurations", "name=shardA,feature.bags=features",
            "--index-map-directory", str(trained / "index-maps"),
            "--serving-request-batch", str(chunk),
            "--serving-max-wait-ms", "1.0",
            "--fleet-replicas", "2",
            "--fleet-http-port", "0",
        ]))
        stats = result["stats"]
        assert stats["requests_shed"] == 0
        assert stats["requests_served"] == -(-300 // chunk)
        assert stats["sheds_by_cause"] == {
            "overload": 0, "deadline": 0, "quota": 0, "shutdown": 0,
        }
        gen = stats["generations_served"][-1]
        assert stats["served_by_generation"].get(gen) == stats["requests_served"]
        assert ":" in stats["http_endpoint"]
        scores = result["scores"]
        assert not np.isnan(scores).any()

        # bitwise vs the single-frontend replay of the same generation
        clear_engine_cache()
        ref = serving_driver.run(serving_driver.build_arg_parser().parse_args([
            "--checkpoint-directory", ckpt_root,
            "--input-data-directories", str(fixture_dir / "validate.avro"),
            "--root-output-directory", str(out) + "-ref",
            "--feature-shard-configurations", "name=shardA,feature.bags=features",
            "--index-map-directory", str(trained / "index-maps"),
            "--serving-request-batch", str(chunk),
            "--serving-max-wait-ms", "1.0",
        ]))
        assert scores.dtype == ref["scores"].dtype
        np.testing.assert_array_equal(scores, ref["scores"])

    def test_serving_driver_requires_index_maps(self, fixture_dir, trained, tmp_path):
        from photon_ml_tpu.cli import serving_driver

        with pytest.raises(FileNotFoundError, match="index maps"):
            serving_driver.run(serving_driver.build_arg_parser().parse_args([
                "--checkpoint-directory", str(fixture_dir / "ckpt" / "config_0"),
                "--input-data-directories", str(fixture_dir / "validate.avro"),
                "--root-output-directory", str(tmp_path / "serving-out"),
                "--feature-shard-configurations", "name=shardA,feature.bags=features",
            ]))

    def test_warm_start_retrain(self, fixture_dir, trained):
        out = fixture_dir / "warm-out"
        rc = game_training_driver.main([
            "--training-task", "LOGISTIC_REGRESSION",
            "--input-data-directories", str(fixture_dir / "train.avro"),
            "--root-output-directory", str(out),
            "--feature-shard-configurations", "name=shardA,feature.bags=features",
            "--coordinate-configurations", FE_COORD,
            "--coordinate-configurations", RE_COORD,
            "--coordinate-update-sequence", "global,per-user",
            "--model-input-directory", str(trained / "best"),
            "--off-heap-index-map-directory", str(trained / "index-maps"),
            "--partial-retrain-locked-coordinates", "global",
        ])
        assert rc == 0
        # locked coordinate carried over unchanged from the input model
        spec = json.loads((out / "best" / "model-spec.json").read_text())
        assert set(spec) == {"global", "per-user"}

    def test_output_dir_collision(self, fixture_dir, trained):
        with pytest.raises(FileExistsError):
            game_training_driver.main([
                "--training-task", "LOGISTIC_REGRESSION",
                "--input-data-directories", str(fixture_dir / "train.avro"),
                "--root-output-directory", str(trained),
                "--feature-shard-configurations", "name=shardA,feature.bags=features",
                "--coordinate-configurations", FE_COORD,
                "--coordinate-update-sequence", "global",
            ])


class TestCommandLineRoundTrip:
    def test_args_to_command_line_exact_roundtrip(self):
        """printForCommandLine parity (ScoptParser.scala:40): parse -> print
        -> parse reproduces the namespace EXACTLY, including composite
        configurations, append args, flag pairs, and numeric types."""
        from photon_ml_tpu.cli.game_training_driver import build_arg_parser
        from photon_ml_tpu.cli.parsers import args_to_command_line

        parser = build_arg_parser()
        argv = [
            "--training-task", "LOGISTIC_REGRESSION",
            "--input-data-directories", "/data/train",
            "--validation-data-directories", "/data/val",
            "--root-output-directory", "/out",
            "--feature-shard-configurations",
            "name=global,feature.bags=features|extra",
            "--feature-shard-configurations",
            "name=per-user,feature.bags=userFeatures,intercept=false",
            "--coordinate-configurations",
            "name=global,feature.shard=global,optimizer=LBFGS,max.iter=50,"
            "tolerance=1e-08,regularization=L2,reg.weights=0.1|1.0|10.0",
            "--coordinate-update-sequence", "global",
            "--coordinate-descent-iterations", "3",
            "--override-output-directory",
        ]
        ns1 = parser.parse_args(argv)
        tokens = args_to_command_line(ns1, parser)
        ns2 = parser.parse_args(tokens)
        assert vars(ns1) == vars(ns2)
        # idempotent: printing the re-parsed namespace gives identical tokens
        assert args_to_command_line(ns2, parser) == tokens

    def test_command_line_artifact_written_and_relaunchable(self, tmp_path):
        import shlex

        from photon_ml_tpu.cli.game_training_driver import build_arg_parser

        rng = np.random.default_rng(9)
        write_glmix_avro(str(tmp_path / "train.avro"), rng, n=80, d=4)
        out = tmp_path / "out"
        rc = game_training_driver.main([
            "--training-task", "LOGISTIC_REGRESSION",
            "--input-data-directories", str(tmp_path / "train.avro"),
            "--root-output-directory", str(out),
            "--feature-shard-configurations", "name=shardA,feature.bags=features",
            "--coordinate-configurations", FE_COORD,
            "--coordinate-update-sequence", "global",
        ])
        assert rc == 0
        line = (out / "command-line.txt").read_text().strip()
        ns = build_arg_parser().parse_args(shlex.split(line))
        assert ns.training_task == "LOGISTIC_REGRESSION"
        assert ns.root_output_directory == str(out)
        assert ns.coordinate_configurations == [FE_COORD]


class TestIndexingDrivers:
    def test_feature_indexing_driver(self, tmp_path):
        rng = np.random.default_rng(1)
        write_glmix_avro(str(tmp_path / "data.avro"), rng, n=50, d=4)
        out = tmp_path / "maps"
        rc = feature_indexing_driver.main([
            "--input-data-directories", str(tmp_path / "data.avro"),
            "--output-directory", str(out),
            "--feature-shard-configurations", "name=shardA,feature.bags=features",
        ])
        assert rc == 0
        from photon_ml_tpu.data.index_map import IndexMap

        imap = IndexMap.load(str(out / "shardA"))
        assert imap.size == 5  # 4 features + intercept

    def test_feature_indexing_driver_paldb_format(self, tmp_path):
        """--format paldb emits real partitioned PalDB v1 stores under the
        reference's partition naming (PalDBIndexMapBuilder.scala:98), which
        the training driver's index-map loader then consumes unchanged."""
        rng = np.random.default_rng(1)
        write_glmix_avro(str(tmp_path / "data.avro"), rng, n=50, d=6)
        out = tmp_path / "maps"
        rc = feature_indexing_driver.main([
            "--input-data-directories", str(tmp_path / "data.avro"),
            "--output-directory", str(out),
            "--feature-shard-configurations", "name=shardA,feature.bags=features",
            "--format", "paldb",
            "--num-partitions", "3",
        ])
        assert rc == 0
        assert sorted(p.name for p in out.iterdir()) == [
            f"paldb-partition-shardA-{i}.dat" for i in range(3)
        ]
        from photon_ml_tpu.cli.game_training_driver import _load_index_maps

        maps = _load_index_maps(str(out), ["shardA"])
        imap = maps["shardA"]
        assert imap.size == 7  # 6 features + intercept
        names = [imap.get_feature_name(i) for i in range(imap.size)]
        assert len(set(names)) == 7
        assert all(imap.get_index(n) == i for i, n in enumerate(names))

    def test_feature_indexing_driver_offheap_format(self, tmp_path):
        """--format offheap emits the mmap store and the training driver's
        index-map loader consumes it through the same --off-heap-index-map
        directory surface as the other formats."""
        rng = np.random.default_rng(4)
        write_glmix_avro(str(tmp_path / "data.avro"), rng, n=50, d=5)
        out = tmp_path / "maps"
        rc = feature_indexing_driver.main([
            "--input-data-directories", str(tmp_path / "data.avro"),
            "--output-directory", str(out),
            "--feature-shard-configurations", "name=shardA,feature.bags=features",
            "--format", "offheap",
            "--num-partitions", "2",
        ])
        assert rc == 0
        assert (out / "shardA" / "meta").exists()
        from photon_ml_tpu.cli.game_training_driver import _load_index_maps

        imap = _load_index_maps(str(out), ["shardA"])["shardA"]
        assert imap.size == 6  # 5 features + intercept
        assert imap.intercept_index is not None
        names = [imap.get_feature_name(i) for i in range(imap.size)]
        assert len(set(names)) == 6
        assert all(imap.get_index(n) == i for i, n in enumerate(names))

    def test_name_and_term_bags_driver(self, tmp_path):
        rng = np.random.default_rng(2)
        write_glmix_avro(str(tmp_path / "data.avro"), rng, n=30, d=3)
        out = tmp_path / "bags"
        rc = name_and_term_bags_driver.main([
            "--input-data-directories", str(tmp_path / "data.avro"),
            "--output-directory", str(out),
            "--feature-bags", "features",
        ])
        assert rc == 0
        lines = (out / "features").read_text().strip().split("\n")
        assert len(lines) == 3
        assert lines[0].split("\t")[0] == "f0"


class TestReviewRegressions:
    def test_model_spec_preserves_data_config(self, tmp_path):
        """model-spec.json must record the coordinate's REAL data configuration
        (random-effect type, shard) so the recorded spec round-trips."""
        rng = np.random.default_rng(3)
        write_glmix_avro(str(tmp_path / "train.avro"), rng, n=200)
        out = tmp_path / "out"
        game_training_driver.main([
            "--training-task", "LOGISTIC_REGRESSION",
            "--input-data-directories", str(tmp_path / "train.avro"),
            "--root-output-directory", str(out),
            "--feature-shard-configurations", "name=shardA,feature.bags=features",
            "--coordinate-configurations", RE_COORD,
            "--coordinate-update-sequence", "per-user",
        ])
        spec = json.loads((out / "best" / "model-spec.json").read_text())
        name, cfg = parse_coordinate_configuration(spec["per-user"])
        assert name == "per-user"
        assert isinstance(cfg.data_config, RandomEffectDataConfiguration)
        assert cfg.data_config.random_effect_type == "userId"
        assert cfg.data_config.feature_shard_id == "shardA"

    def test_scoring_from_models_subdir(self, tmp_path):
        """Index maps at <root>/index-maps must be found when scoring
        <root>/models/<i>, not just <root>/best."""
        rng = np.random.default_rng(4)
        _, _, _, w, bias = write_glmix_avro(str(tmp_path / "train.avro"), rng, n=200)
        out = tmp_path / "out"
        game_training_driver.main([
            "--training-task", "LOGISTIC_REGRESSION",
            "--input-data-directories", str(tmp_path / "train.avro"),
            "--root-output-directory", str(out),
            "--feature-shard-configurations", "name=shardA,feature.bags=features",
            "--coordinate-configurations", FE_COORD,
            "--coordinate-update-sequence", "global",
            "--output-mode", "ALL",
        ])
        rc = game_scoring_driver.main([
            "--input-data-directories", str(tmp_path / "train.avro"),
            "--model-input-directory", str(out / "models" / "0"),
            "--root-output-directory", str(tmp_path / "scores"),
            "--feature-shard-configurations", "name=shardA,feature.bags=features",
        ])
        assert rc == 0

    def test_sparse_take_rows_duplicates(self):
        import jax.numpy as jnp
        import scipy.sparse as sp

        from photon_ml_tpu.data.matrix import DenseDesignMatrix, SparseDesignMatrix

        rng = np.random.default_rng(5)
        M = rng.normal(size=(6, 4)) * (rng.random((6, 4)) < 0.5)
        sparse = SparseDesignMatrix.from_scipy(sp.csr_matrix(M), dtype=jnp.float64,
                                               pad_nnz=40)
        dense = DenseDesignMatrix(values=jnp.asarray(M))
        idx = np.array([3, 3, 0, 5, 3])
        np.testing.assert_allclose(
            np.asarray(sparse.take_rows(idx).to_dense()),
            np.asarray(dense.take_rows(idx).to_dense()),
        )

    def test_best_model_selection_smaller_is_better(self, tmp_path):
        """With an RMSE primary evaluator (smaller is better), the lowest-RMSE
        config must win, and unevaluated results must never be selected."""
        rng = np.random.default_rng(6)
        _, _, _, w, bias = write_glmix_avro(str(tmp_path / "train.avro"), rng, n=300)
        write_glmix_avro(str(tmp_path / "val.avro"), rng, n=200, w=w, bias=bias)
        out = tmp_path / "out"
        result = game_training_driver.run(game_training_driver.build_arg_parser().parse_args([
            "--training-task", "LOGISTIC_REGRESSION",
            "--input-data-directories", str(tmp_path / "train.avro"),
            "--validation-data-directories", str(tmp_path / "val.avro"),
            "--root-output-directory", str(out),
            "--feature-shard-configurations", "name=shardA,feature.bags=features",
            "--coordinate-configurations",
            "name=global,feature.shard=shardA,optimizer=LBFGS,max.iter=40,"
            "tolerance=1e-8,regularization=L2,reg.weights=0.01|100.0",
            "--coordinate-update-sequence", "global",
            "--evaluators", "RMSE",
        ]))
        results = result["results"]
        metrics = [r.best_metric for r in results]
        assert result["best_index"] == int(np.argmin(metrics))


def test_training_driver_profiler_trace(rng, tmp_path):
    """--profile-output-directory captures an XLA profiler trace during the
    training phase (SURVEY §5.1: the TPU-native tracing story)."""
    import os

    from photon_ml_tpu.cli.game_training_driver import main
    from photon_ml_tpu.data import avro_io

    n, d = 120, 3
    X = rng.normal(size=(n, d))
    y = (X @ rng.normal(size=d) > 0).astype(float)
    indir = tmp_path / "in"
    indir.mkdir()
    avro_io.write_container(
        str(indir / "p.avro"),
        avro_io.TRAINING_EXAMPLE_SCHEMA,
        (
            {
                "uid": str(i), "label": float(y[i]), "weight": 1.0, "offset": 0.0,
                "features": [
                    {"name": f"f{j}", "term": "", "value": float(X[i, j])}
                    for j in range(d)
                ],
                "metadataMap": {},
            }
            for i in range(n)
        ),
    )
    prof = tmp_path / "prof"
    rc = main([
        "--input-data-directories", str(indir),
        "--root-output-directory", str(tmp_path / "out"),
        "--feature-shard-configurations", "name=global,feature.bags=features",
        "--training-task", "LOGISTIC_REGRESSION",
        "--coordinate-configurations",
        "name=global,feature.shard=global,optimizer=LBFGS,max.iter=10,"
        "tolerance=1e-6,regularization=L2,reg.weights=1.0",
        "--coordinate-update-sequence", "global",
        "--profile-output-directory", str(prof),
    ])
    assert rc == 0
    traces = [
        os.path.join(base, f)
        for base, _, files in os.walk(prof)
        for f in files
    ]
    assert traces, "no profiler trace files written"


def test_compute_backend_fused_is_refused_by_argparse(tmp_path, capsys):
    """The fused whole-pass backend is gone: its choice is argparse's own
    error (exit code 2), before any ingest."""
    from photon_ml_tpu.cli import game_training_driver as d

    with pytest.raises(SystemExit) as refused:
        d.build_arg_parser().parse_args([
            "--input-data-directories", str(tmp_path / "none"),
            "--root-output-directory", str(tmp_path / "out"),
            "--training-task", "LOGISTIC_REGRESSION",
            "--feature-shard-configurations", "name=global,feature.bags=features",
            "--coordinate-configurations",
            "name=global,feature.shard=global,optimizer=LBFGS,max.iter=5,"
            "tolerance=1e-6,regularization=L2,reg.weights=1.0",
            "--coordinate-update-sequence", "global",
            "--compute-backend", "fused",
        ])
    assert refused.value.code == 2
    assert "invalid choice: 'fused'" in capsys.readouterr().err


# ----------------------------------------------------------- sweep driver


def test_parse_sweep_axis_grammar():
    from photon_ml_tpu.cli.sweep_driver import parse_sweep_axis

    axis = parse_sweep_axis(
        "coordinate=global,parameter=l2,min=0.01,max=100,transform=LOG"
    )
    assert (axis.coordinate_id, axis.parameter) == ("global", "l2")
    assert (axis.min, axis.max, axis.transform) == (0.01, 100.0, "LOG")
    with pytest.raises(ValueError, match="Duplicate key"):
        parse_sweep_axis("coordinate=g,parameter=l2,min=0.1,max=1,min=0.5")
    with pytest.raises(ValueError, match="Missing required key"):
        parse_sweep_axis("coordinate=g,parameter=l2,min=0.1")
    with pytest.raises(ValueError, match="Unknown sweep-axis keys"):
        parse_sweep_axis("coordinate=g,parameter=l2,min=0.1,max=1,scale=LOG")
