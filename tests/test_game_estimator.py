"""GameEstimator / GameTransformer tests: config grid expansion, warm-started
sweeps, partial retrain, scoring round trips. Mirrors GameEstimatorIntegTest /
GameTransformerIntegTest in the reference."""

import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.data.game_data import GameInput
from photon_ml_tpu.estimators import (
    CoordinateConfiguration,
    FixedEffectDataConfiguration,
    GameEstimator,
    RandomEffectDataConfiguration,
    expand_game_configurations,
)
from photon_ml_tpu.evaluation import EvaluatorType, evaluator_for_type
from photon_ml_tpu.optimization.common import OptimizerConfig
from photon_ml_tpu.optimization.config import (
    GLMOptimizationConfiguration,
    RegularizationContext,
)
from photon_ml_tpu.transformers import GameTransformer
from photon_ml_tpu.types import RegularizationType, TaskType

OPT = GLMOptimizationConfiguration(
    optimizer_config=OptimizerConfig(max_iterations=60, tolerance=1e-8),
    regularization_context=RegularizationContext(RegularizationType.L2),
    regularization_weight=1.0,
)


def make_input(rng, n=800, d=4, n_users=8):
    w = rng.normal(size=d)
    bias = rng.normal(size=n_users) * 1.5
    X = rng.normal(size=(n, d))
    # deterministic round-robin entities: stable bucket shapes -> shared compiles
    users = np.arange(n) % n_users
    z = X @ w + bias[users]
    y = (z + 0.3 * rng.normal(size=n) > 0).astype(np.float64)
    uid = np.asarray([f"u{u}" for u in users], dtype=object)
    return GameInput(
        features={
            "global": X,
            "per-user": sp.csr_matrix(np.ones((n, 1))),
        },
        labels=y,
        id_columns={"userId": uid},
    )


def make_configs(reg_weights=()):
    return {
        "fixed": CoordinateConfiguration(
            data_config=FixedEffectDataConfiguration("global"),
            optimization_config=OPT,
            reg_weights=reg_weights,
        ),
        "per-user": CoordinateConfiguration(
            data_config=RandomEffectDataConfiguration("userId", "per-user"),
            optimization_config=OPT,
        ),
    }


def test_expand_game_configurations():
    configs = {
        "a": CoordinateConfiguration(
            data_config=FixedEffectDataConfiguration(),
            optimization_config=OPT,
            reg_weights=(0.1, 10.0, 1.0),
        ),
        "b": CoordinateConfiguration(
            data_config=FixedEffectDataConfiguration(),
            optimization_config=OPT,
            reg_weights=(2.0, 0.5),
        ),
    }
    sweep = expand_game_configurations(configs)
    assert len(sweep) == 6
    # strong -> weak regularization within each coordinate
    assert [c["a"].regularization_weight for c in sweep] == [10.0, 10.0, 1.0, 1.0, 0.1, 0.1]
    assert [c["b"].regularization_weight for c in sweep[:2]] == [2.0, 0.5]


def test_fit_and_select_best(rng):
    data = make_input(rng)
    train, val = data.select(np.arange(0, 550)), data.select(np.arange(550, 800))
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations=make_configs(reg_weights=(10.0, 0.5)),
        n_iterations=2,
    )
    results = est.fit(train, validation_data=val)
    assert len(results) == 2  # two reg weights on the fixed coordinate
    assert [r.configuration["fixed"].regularization_weight for r in results] == [10.0, 0.5]
    for r in results:
        assert r.best_metric is not None and r.best_metric > 0.8
        assert r.evaluations is not None and "AUC" in r.evaluations
    best = est.select_best_model(results)
    assert best.best_metric == max(r.best_metric for r in results)


def test_fit_without_validation(rng):
    data = make_input(rng, n=300)
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations=make_configs(),
        n_iterations=1,
    )
    results = est.fit(data)
    assert len(results) == 1
    assert results[0].best_metric is None
    assert est.select_best_model(results) is results[0]


def test_transformer_scores_and_metrics(rng):
    data = make_input(rng)
    train, test = data.select(np.arange(0, 600)), data.select(np.arange(600, 800))
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations=make_configs(),
        n_iterations=2,
    )
    model = est.fit(train)[0].model
    transformer = GameTransformer(
        model=model, evaluators=[evaluator_for_type(EvaluatorType.AUC)]
    )
    scores, metrics = transformer.transform(test)
    assert scores.shape == (200,)
    assert metrics["AUC"] > 0.8
    # per-coordinate decomposition sums to the total (minus offsets here: zero)
    per = transformer.score_per_coordinate(test)
    np.testing.assert_allclose(per["fixed"] + per["per-user"], scores, rtol=1e-5)


def test_transformer_unseen_entities_score_fixed_only(rng):
    data = make_input(rng, n=400)
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations=make_configs(),
        n_iterations=1,
    )
    model = est.fit(data)[0].model
    n_new = 50
    X_new = rng.normal(size=(n_new, 4))
    new_input = GameInput(
        features={"global": X_new, "per-user": sp.csr_matrix(np.ones((n_new, 1)))},
        id_columns={"userId": np.asarray(["stranger"] * n_new, dtype=object)},
    )
    per = GameTransformer(model=model).score_per_coordinate(new_input)
    np.testing.assert_array_equal(per["per-user"], np.zeros(n_new))
    assert np.abs(per["fixed"]).max() > 0


def test_partial_retrain_locks_coordinate(rng):
    data = make_input(rng, n=500)
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations=make_configs(),
        n_iterations=1,
    )
    first = est.fit(data)[0].model

    est2 = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations=make_configs(),
        n_iterations=2,
        partial_retrain_locked_coordinates=["fixed"],
    )
    results = est2.fit(data, initial_model=first)
    after = results[0].model.get_model("fixed")
    np.testing.assert_array_equal(
        np.asarray(after.model.coefficients.means),
        np.asarray(first.get_model("fixed").model.coefficients.means),
    )


def test_partial_retrain_requires_initial_model(rng):
    data = make_input(rng, n=200)
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations=make_configs(),
        partial_retrain_locked_coordinates=["fixed"],
    )
    with pytest.raises(ValueError, match="initial_model"):
        est.fit(data)


def test_warm_start_chain_improves_or_matches(rng):
    """Sweep results should all be sane — the warm-start chain must not poison
    later configs (GameEstimator.fit:344-360 semantics)."""
    data = make_input(rng)
    train, val = data.select(np.arange(0, 550)), data.select(np.arange(550, 800))
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations=make_configs(reg_weights=(100.0, 1.0, 0.01)),
        n_iterations=1,
    )
    results = est.fit(train, validation_data=val)
    assert len(results) == 3
    aucs = [r.best_metric for r in results]
    assert all(a > 0.75 for a in aucs)


def test_fe_storage_dtype_bf16_close_to_f32(rng):
    """Estimator-level bf16 feature storage: coefficients/metrics stay f32 and
    land near the full-precision fit (DenseDesignMatrix._mxu_dot)."""
    data = make_input(rng)
    train, val = data.select(np.arange(0, 550)), data.select(np.arange(550, 800))

    def fit(storage):
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configurations=make_configs(),
            n_iterations=2,
            fe_storage_dtype=storage,
        )
        return est.fit(train, validation_data=val)[0]

    import jax.numpy as jnp

    f32 = fit(None)
    bf16 = fit(jnp.bfloat16)
    coef = bf16.model.get_model("fixed").model.coefficients.means
    assert coef.dtype == jnp.float32
    assert bf16.best_metric == pytest.approx(f32.best_metric, abs=0.01)


# -------------------------------------------------- GLM family matrix


def make_family_input(rng, task, n=600, d=4, n_users=8):
    """GLMix data whose labels follow the family's generative model."""
    w = rng.normal(size=d) * 0.6
    bias = rng.normal(size=n_users)
    X = rng.normal(size=(n, d))
    users = np.arange(n) % n_users
    z = X @ w + bias[users]
    task = TaskType(task)
    if task == TaskType.LOGISTIC_REGRESSION:
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
    elif task == TaskType.LINEAR_REGRESSION:
        y = z + 0.3 * rng.normal(size=n)
    elif task == TaskType.POISSON_REGRESSION:
        y = rng.poisson(np.exp(np.clip(z, -3.0, 2.0))).astype(np.float64)
    else:
        y = (z > 0).astype(np.float64)
    uid = np.asarray([f"u{u}" for u in users], dtype=object)
    return GameInput(
        features={
            "global": X,
            "per-user": sp.csr_matrix(np.ones((n, 1))),
        },
        labels=y,
        id_columns={"userId": uid},
    )


@pytest.mark.parametrize(
    "task",
    [
        TaskType.LOGISTIC_REGRESSION,
        TaskType.LINEAR_REGRESSION,
        TaskType.POISSON_REGRESSION,
        TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
    ],
)
def test_family_matrix_end_to_end(rng, task):
    """Every GLM family the reference trains (logistic, linear, Poisson,
    smoothed hinge) goes through the FULL GAME pipeline: fixed + random
    effect coordinate descent, the task's default validation evaluator,
    best-model selection, and fused-engine scoring of the result."""
    data = make_family_input(rng, task)
    train, val = data.select(np.arange(0, 420)), data.select(np.arange(420, 600))
    est = GameEstimator(
        task=task, coordinate_configurations=make_configs(), n_iterations=2
    )
    results = est.fit(train, validation_data=val)
    assert len(results) == 1
    r = results[0]
    assert r.best_metric is not None and np.isfinite(r.best_metric)
    for cid in ("fixed", "per-user"):
        m = r.best_model.get_model(cid)
        arrays = (
            [m.coeffs] if hasattr(m, "coeffs") else [m.model.coefficients.means]
        )
        for a in arrays:
            assert np.isfinite(np.asarray(a)).all(), cid
    # the trained family's model serves through the fused engine at one-ulp
    # tolerance: trained f32 coefficients against the x64 harness's f64
    # features promote the reduction, and eager/fused associate it
    # differently in the last f64 bit (same budget as test_serving's
    # mesh-path assert_parity; the same-dtype bitwise contract is pinned
    # there by the family_matrix engine tests)
    eager_t = GameTransformer(model=r.best_model, engine="eager")
    fused_t = GameTransformer(model=r.best_model, engine="fused")
    eager = eager_t.score(val, include_offsets=False)
    fused = fused_t.score(val, include_offsets=False)
    assert fused.dtype == eager.dtype
    np.testing.assert_allclose(fused, eager, rtol=5e-15, atol=1e-14)
    pc_e, pc_f = eager_t.score_per_coordinate(val), fused_t.score_per_coordinate(val)
    for cid in pc_e:
        np.testing.assert_allclose(
            pc_f[cid], pc_e[cid], rtol=5e-15, atol=1e-14, err_msg=cid
        )
    # the family's mean prediction applies its link (prediction sanity)
    if task == TaskType.POISSON_REGRESSION:
        from photon_ml_tpu.serving import get_engine

        assert (get_engine(r.best_model).predict(val) >= 0).all()
