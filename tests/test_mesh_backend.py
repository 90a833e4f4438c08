"""Mesh execution backend: the SAME coordinate-descent implementation runs as
sharded SPMD programs when GameEstimator places datasets on a jax.sharding.Mesh
(VERDICT round-1 items 2/5/6). Mirrors the reference's pattern of exercising the
distributed path on a multi-core local backend (SparkTestUtils.sparkTest,
SURVEY.md §4) on the simulated 8-device CPU mesh."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.data.game_data import GameInput
from photon_ml_tpu.estimators.config import (
    CoordinateConfiguration,
    FixedEffectDataConfiguration,
    RandomEffectDataConfiguration,
)
from photon_ml_tpu.estimators.game_estimator import GameEstimator
from photon_ml_tpu.evaluation.evaluators import EvaluatorType
from photon_ml_tpu.optimization.common import OptimizerConfig
from photon_ml_tpu.optimization.config import (
    GLMOptimizationConfiguration,
    RegularizationContext,
)
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType

N, D, U = 200, 4, 11  # U deliberately not divisible by 8 (uneven entity axis)


def _glmix_data(rng, n=N):
    w = rng.normal(size=D)
    u_eff = 0.7 * rng.normal(size=U)
    X = rng.normal(size=(n, D))
    # deterministic round-robin entities: stable bucket shapes -> shared compiles
    users = np.arange(n) % U
    z = X @ w + u_eff[users]
    y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(float)
    return X, users, y


def _cfg(iters=40):
    return GLMOptimizationConfiguration(
        optimizer_config=OptimizerConfig(
            optimizer_type=OptimizerType.LBFGS, max_iterations=iters
        ),
        regularization_context=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )


def _estimator(mesh=None, locked=(), sparse_shard=False):
    return GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations={
            "global": CoordinateConfiguration(FixedEffectDataConfiguration("global"), _cfg()),
            "per-user": CoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "global"), _cfg()
            ),
        },
        validation_evaluators=[EvaluatorType.AUC],
        partial_retrain_locked_coordinates=locked,
        dtype=jnp.float64,
        mesh=mesh,
    )


def _inputs(rng, sparse=False):
    X, users, y = _glmix_data(rng)
    Xv, uv, yv = _glmix_data(rng)
    feat = (lambda a: sp.csr_matrix(a)) if sparse else (lambda a: a)
    train = GameInput(features={"global": feat(X)}, labels=y, id_columns={"userId": users})
    val = GameInput(features={"global": feat(Xv)}, labels=yv, id_columns={"userId": uv})
    return train, val


class TestMeshBackend:
    def test_mesh_fit_matches_host(self, rng, eight_devices):
        """Identical data through the host and mesh backends must agree: same
        coordinate-descent implementation, two placements."""
        train, val = _inputs(rng)
        host = _estimator().fit(train, validation_data=val)
        mesh = make_mesh(8)
        sharded = _estimator(mesh=mesh).fit(train, validation_data=val)
        assert host[0].best_metric == pytest.approx(sharded[0].best_metric, abs=1e-6)
        np.testing.assert_allclose(
            np.asarray(host[0].best_model.get_model("global").model.coefficients.means),
            np.asarray(sharded[0].best_model.get_model("global").model.coefficients.means),
            atol=1e-6,
        )
        h_re = np.asarray(host[0].best_model.get_model("per-user").coeffs)
        m_re = np.asarray(sharded[0].best_model.get_model("per-user").coeffs)
        np.testing.assert_allclose(h_re, m_re[: h_re.shape[0]], atol=1e-6)
        # table padding rows (mesh divisibility) must be exactly zero
        assert np.all(m_re[h_re.shape[0] :] == 0.0)

    def test_sparse_fixed_effect_parity_on_mesh(self, rng, eight_devices):
        """SparseDesignMatrix rides the COO-sharded path (billion-feature story:
        PalDBIndexMap.scala:43-278 + sparse vectors); results match dense."""
        mesh = make_mesh(8)
        rng2 = np.random.default_rng(rng.integers(1 << 31))
        train_d, val_d = _inputs(rng2)
        rng3 = np.random.default_rng(0)
        # same underlying arrays, sparse container
        train_s = GameInput(
            features={"global": sp.csr_matrix(train_d.features["global"])},
            labels=train_d.labels,
            id_columns=train_d.id_columns,
        )
        val_s = GameInput(
            features={"global": sp.csr_matrix(val_d.features["global"])},
            labels=val_d.labels,
            id_columns=val_d.id_columns,
        )
        dense = _estimator(mesh=mesh).fit(train_d, validation_data=val_d)
        sparse = _estimator(mesh=mesh).fit(train_s, validation_data=val_s)
        assert dense[0].best_metric == pytest.approx(sparse[0].best_metric, abs=1e-6)
        np.testing.assert_allclose(
            np.asarray(dense[0].model.get_model("global").model.coefficients.means),
            np.asarray(sparse[0].model.get_model("global").model.coefficients.means),
            atol=1e-6,
        )

    def test_re_tables_entity_sharded(self, rng, eight_devices):
        """Per-device memory for random-effect coefficient tables scales
        ~1/n_devices (VERDICT item 6): the [E_pad, K] table is sharded over the
        entity axis, never replicated."""
        mesh = make_mesh(8)
        train, val = _inputs(rng)
        res = _estimator(mesh=mesh).fit(train, validation_data=val)
        coeffs = res[0].model.get_model("per-user").coeffs
        E_pad = coeffs.shape[0]
        assert E_pad % 8 == 0 and E_pad >= U
        shard_rows = {s.data.shape[0] for s in coeffs.addressable_shards}
        assert shard_rows == {E_pad // 8}, shard_rows
        # 8 distinct device shards -> not replicated
        devices = {s.device for s in coeffs.addressable_shards}
        assert len(devices) == 8

    def test_second_pass_on_mesh_reuses_the_first_pass_programs(self, rng, eight_devices):
        """A solve on a mesh returns its [D] coefficients typed with the mesh;
        initial zeros typed without it gave pass 2 its own jit cache key — one
        more trace + compile of the whole fixed-effect solver (seen as 452
        traces in the mesh bench's measured region). The initial coefficients
        are now replicated over the mesh, so a one-pass warm-up compiles
        everything a longer run needs."""
        import dataclasses

        from photon_ml_tpu.analysis.runtime_guard import no_retrace

        train, _ = _inputs(rng)
        one_pass = dataclasses.replace(
            _estimator(mesh=make_mesh(8)), validation_evaluators=()
        )
        one_pass.fit(train)
        with no_retrace(what="two passes after a one-pass warm-up"):
            dataclasses.replace(one_pass, n_iterations=3).fit(train)

    def test_mesh_partial_retrain_and_best_model(self, rng, eight_devices):
        """Locked coordinates + validation best-model tracking work unchanged on
        the mesh backend (feature parity with the host loop, VERDICT item 2)."""
        mesh = make_mesh(8)
        train, val = _inputs(rng)
        base = _estimator(mesh=mesh).fit(train, validation_data=val)
        warm = base[0].best_model
        retrain = _estimator(mesh=mesh, locked=("global",)).fit(
            train, validation_data=val, initial_model=warm
        )
        assert retrain[0].best_metric is not None
        np.testing.assert_allclose(
            np.asarray(retrain[0].model.get_model("global").model.coefficients.means),
            np.asarray(warm.get_model("global").model.coefficients.means),
        )
        # the unlocked random effect did retrain
        assert retrain[0].descent.trackers["per-user"]

    def test_training_driver_mesh_backend_cli(self, rng, tmp_path):
        """A CLI invocation trains the GLMix on an 8-device CPU mesh end to end
        (VERDICT item 2 'done' criterion)."""
        from photon_ml_tpu.data import avro_io

        X, users, y = _glmix_data(rng, n=120)
        indir = tmp_path / "in"
        indir.mkdir()

        def records():
            for i in range(len(y)):
                yield {
                    "uid": f"s{i}",
                    "label": float(y[i]),
                    "features": [
                        {"name": f"f{j}", "term": "", "value": float(X[i, j])}
                        for j in range(D)
                    ],
                    "metadataMap": {"userId": f"u{users[i]}"},
                    "weight": 1.0,
                    "offset": 0.0,
                }

        avro_io.write_container(
            str(indir / "part-0.avro"), avro_io.TRAINING_EXAMPLE_SCHEMA, records()
        )
        out = tmp_path / "out"
        from photon_ml_tpu.cli.game_training_driver import main

        rc = main([
            "--input-data-directories", str(indir),
            "--validation-data-directories", str(indir),
            "--root-output-directory", str(out),
            "--feature-shard-configurations", "name=global,feature.bags=features",
            "--training-task", "LOGISTIC_REGRESSION",
            "--coordinate-configurations",
            "name=global,feature.shard=global,optimizer=LBFGS,max.iter=30,"
            "tolerance=1e-7,regularization=L2,reg.weights=1.0",
            "--coordinate-configurations",
            "name=per-user,feature.shard=global,random.effect.type=userId,"
            "optimizer=LBFGS,max.iter=30,tolerance=1e-7,regularization=L2,reg.weights=1.0",
            "--coordinate-update-sequence", "global,per-user",
            "--evaluators", "AUC",
            "--compute-backend", "mesh",
            "--mesh-devices", "8",
        ])
        assert rc == 0
        assert (out / "best" / "fixed-effect").exists()


class TestFeatureShardedBackend:
    """GameEstimator on a 2-D ("data", "model") mesh: the fixed effect's
    feature axis shards over "model" (coefficients + optimizer state live
    distributed), random effects keep their 1-D entity sharding over "data"."""

    def test_2d_mesh_fit_matches_host(self, rng, eight_devices):
        # n_model=3 does NOT divide D=4, so the feature axis genuinely pads
        # (D -> 6) and the padded-column assertion is non-vacuous
        from photon_ml_tpu.parallel import make_mesh2

        train, val = _inputs(rng)
        host = _estimator().fit(train, validation_data=val)
        mesh2 = make_mesh2(2, 3)
        sharded = _estimator(mesh=mesh2).fit(train, validation_data=val)
        assert host[0].best_metric == pytest.approx(sharded[0].best_metric, abs=1e-6)
        h = np.asarray(host[0].best_model.get_model("global").model.coefficients.means)
        s = np.asarray(sharded[0].best_model.get_model("global").model.coefficients.means)
        assert s.shape[0] > h.shape[0]  # feature padding actually happened
        np.testing.assert_allclose(h, s[: h.shape[0]], atol=1e-6)
        assert np.all(s[h.shape[0] :] == 0.0)  # padded feature columns stay 0

    def test_2d_mesh_warm_start_from_host_model(self, rng, eight_devices):
        """A host-trained (unpadded) model warm-starts a feature-sharded fit:
        prepare_initial_model pads + places the coefficients."""
        from photon_ml_tpu.parallel import make_mesh2

        train, val = _inputs(rng)
        host = _estimator().fit(train, validation_data=val)[0]
        mesh2 = make_mesh2(2, 3)
        warm = _estimator(mesh=mesh2).fit(
            train, validation_data=val, initial_model=host.best_model
        )[0]
        # warm-starting from the (padded+placed) host model lands on the same
        # optimum the host run found (_inputs draws val from a different truth,
        # so only parity — not an absolute AUC level — is meaningful here)
        assert warm.best_metric == pytest.approx(host.best_metric, abs=1e-6)

    def test_2d_mesh_partial_retrain_locked_fixed_effect(self, rng, eight_devices):
        from photon_ml_tpu.parallel import make_mesh2

        train, val = _inputs(rng)
        host_model = _estimator().fit(train, validation_data=val)[0].best_model
        mesh2 = make_mesh2(2, 3)
        locked = _estimator(mesh=mesh2, locked=("global",)).fit(
            train, validation_data=val, initial_model=host_model
        )[0]
        fixed_before = np.asarray(
            host_model.get_model("global").model.coefficients.means
        )
        fixed_after = np.asarray(
            locked.model.get_model("global").model.coefficients.means
        )
        np.testing.assert_allclose(
            fixed_after[: fixed_before.shape[0]], fixed_before, atol=1e-12
        )

    def test_2d_mesh_fe_coefficients_model_sharded(self, rng, eight_devices):
        from photon_ml_tpu.parallel import make_mesh2
        from photon_ml_tpu.parallel.feature_sharded import MODEL_AXIS

        train, val = _inputs(rng)
        mesh2 = make_mesh2(4, 2)
        res = _estimator(mesh=mesh2).fit(train, validation_data=val)[0]
        coef = res.model.get_model("global").model.coefficients.means
        assert coef.sharding.spec == jax.sharding.PartitionSpec(MODEL_AXIS)
        shard_sizes = {s.data.shape[0] for s in coef.addressable_shards}
        assert shard_sizes == {coef.shape[0] // 2}

    def test_2d_mesh_training_driver_cli(self, rng, tmp_path):
        """--mesh-model-devices=2 trains the GLMix with a feature-sharded fixed
        effect end to end through the CLI and exports a loadable model."""
        from photon_ml_tpu.data import avro_io

        X, users, y = _glmix_data(rng, n=120)
        indir = tmp_path / "in"
        indir.mkdir()

        def records():
            for i in range(len(y)):
                yield {
                    "uid": f"s{i}",
                    "label": float(y[i]),
                    "features": [
                        {"name": f"f{j}", "term": "", "value": float(X[i, j])}
                        for j in range(D)
                    ],
                    "metadataMap": {"userId": f"u{users[i]}"},
                    "weight": 1.0,
                    "offset": 0.0,
                }

        avro_io.write_container(
            str(indir / "part-0.avro"), avro_io.TRAINING_EXAMPLE_SCHEMA, records()
        )
        out = tmp_path / "out"
        from photon_ml_tpu.cli.game_training_driver import main

        rc = main([
            "--input-data-directories", str(indir),
            "--validation-data-directories", str(indir),
            "--root-output-directory", str(out),
            "--feature-shard-configurations", "name=global,feature.bags=features",
            "--training-task", "LOGISTIC_REGRESSION",
            "--coordinate-configurations",
            "name=global,feature.shard=global,optimizer=LBFGS,max.iter=30,"
            "tolerance=1e-7,regularization=L2,reg.weights=1.0",
            "--coordinate-configurations",
            "name=per-user,feature.shard=global,random.effect.type=userId,"
            "optimizer=LBFGS,max.iter=30,tolerance=1e-7,regularization=L2,reg.weights=1.0",
            "--coordinate-update-sequence", "global,per-user",
            "--evaluators", "AUC",
            "--compute-backend", "mesh",
            "--mesh-devices", "8",
            "--mesh-model-devices", "2",
        ])
        assert rc == 0
        assert (out / "best" / "fixed-effect").exists()



class TestMeshScoring:
    def test_transformer_mesh_scoring_matches_host(self, rng, eight_devices):
        from photon_ml_tpu.parallel.mesh import make_mesh
        from photon_ml_tpu.transformers import GameTransformer

        train, _ = _inputs(rng)
        # n=197 is NOT divisible by 8: mesh placement pads the sample axis and
        # the [:n] trim in score_per_coordinate is genuinely exercised
        Xv, uv, yv = _glmix_data(rng, n=197)
        val = GameInput(
            features={"global": Xv}, labels=yv, id_columns={"userId": uv}
        )
        model = _estimator().fit(train, validation_data=val)[0].best_model
        host_scores, host_metrics = GameTransformer(
            model=model, evaluators=["AUC"]
        ).transform(val)
        mesh_scores, mesh_metrics = GameTransformer(
            model=model, evaluators=["AUC"], mesh=make_mesh(8)
        ).transform(val)
        np.testing.assert_allclose(mesh_scores, host_scores, atol=1e-10)
        assert mesh_metrics["AUC"] == pytest.approx(host_metrics["AUC"], abs=1e-12)


def test_2d_mesh_with_normalization_matches_host(rng, eight_devices):
    """Feature-sharded mesh + standardization: the [D] normalization vectors
    are padded with identity entries to the padded feature axis and results
    match the host backend."""
    from photon_ml_tpu.normalization import FeatureDataStatistics, NormalizationContext
    from photon_ml_tpu.parallel import make_mesh2
    from photon_ml_tpu.types import NormalizationType

    X, users, y = _glmix_data(rng)
    Xn = np.concatenate([np.ones((N, 1)), X], axis=1)  # intercept col 0
    train = GameInput(features={"global": Xn}, labels=y, id_columns={"userId": users})
    Xv, uv, yv = _glmix_data(rng)
    val = GameInput(
        features={"global": np.concatenate([np.ones((N, 1)), Xv], axis=1)},
        labels=yv, id_columns={"userId": uv},
    )
    stats = FeatureDataStatistics.compute(Xn, intercept_index=0)
    norm = NormalizationContext.build(NormalizationType.STANDARDIZATION, stats)

    def est(mesh=None):
        e = _estimator(mesh=mesh)
        e.normalization_contexts = {"global": norm}
        return e

    host = est().fit(train, validation_data=val)[0]
    sharded = est(make_mesh2(2, 3)).fit(train, validation_data=val)[0]
    assert sharded.best_metric == pytest.approx(host.best_metric, abs=1e-6)
    h = np.asarray(host.best_model.get_model("global").model.coefficients.means)
    s = np.asarray(sharded.best_model.get_model("global").model.coefficients.means)
    assert s.shape[0] > h.shape[0]  # feature padding happened
    np.testing.assert_allclose(s[: h.shape[0]], h, atol=1e-6)
    assert np.all(s[h.shape[0] :] == 0.0)


def test_2d_mesh_box_constraints_match_host(rng, eight_devices):
    """Box constraints on the feature-sharded backend: bounds padded with
    +/-inf for the padded columns; active constraints match the host solve."""
    from photon_ml_tpu.parallel import make_mesh2

    train, val = _inputs(rng)
    bounds = (np.full(D, -0.1), np.full(D, 0.1))  # tight: definitely active

    def est(mesh=None):
        return GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configurations={
                "global": CoordinateConfiguration(
                    FixedEffectDataConfiguration("global"), _cfg(),
                    box_constraints=bounds,
                ),
                "per-user": CoordinateConfiguration(
                    RandomEffectDataConfiguration("userId", "global"), _cfg()
                ),
            },
            validation_evaluators=[EvaluatorType.AUC],
            dtype=jnp.float64,
            mesh=mesh,
        )

    host = est().fit(train, validation_data=val)[0]
    sharded = est(make_mesh2(2, 3)).fit(train, validation_data=val)[0]
    h = np.asarray(host.model.get_model("global").model.coefficients.means)
    s = np.asarray(sharded.model.get_model("global").model.coefficients.means)
    assert np.all(np.abs(h) <= 0.1 + 1e-9) and np.any(np.abs(h) > 0.0999)
    np.testing.assert_allclose(s[: h.shape[0]], h, atol=1e-6)
    assert np.all(np.abs(s[: h.shape[0]]) <= 0.1 + 1e-9)
    assert np.all(s[h.shape[0] :] == 0.0)
