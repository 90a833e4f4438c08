"""GAME coordinate-descent tests: residual-score bookkeeping, fixed+random effect
GLMix training, locked coordinates (partial retrain), best-model tracking,
down-samplers. Mirrors the reference's CoordinateDescent + coordinate integ tests
(photon-lib algorithm/, photon-api src/integTest/.../algorithm/)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.algorithm import (
    FixedEffectCoordinate,
    ModelCoordinate,
    RandomEffectCoordinate,
    run_coordinate_descent,
)
from photon_ml_tpu.data.dataset import FixedEffectDataset, LabeledData
from photon_ml_tpu.data.random_effect import build_random_effect_dataset
from photon_ml_tpu.evaluation import EvaluatorType, evaluator_for_type
from photon_ml_tpu.evaluation.evaluators import EvaluationSuite
from photon_ml_tpu.optimization.common import OptimizerConfig
from photon_ml_tpu.optimization.config import (
    GLMOptimizationConfiguration,
    RegularizationContext,
)
from photon_ml_tpu.optimization.problem import GLMOptimizationProblem
from photon_ml_tpu.sampling import (
    BinaryClassificationDownSampler,
    DefaultDownSampler,
    down_sampler_for_task,
)
from photon_ml_tpu.types import RegularizationType, TaskType

CFG = GLMOptimizationConfiguration(
    optimizer_config=OptimizerConfig(max_iterations=80, tolerance=1e-9),
    regularization_context=RegularizationContext(RegularizationType.L2),
    regularization_weight=1.0,
)


def glmix_data(rng, n=900, d=4, n_users=10, user_scale=2.0):
    """Global GLM + per-user intercept/slope: the canonical GLMix generating model."""
    w_global = rng.normal(size=d)
    user_bias = rng.normal(size=n_users) * user_scale
    user_slope = rng.normal(size=n_users)
    X = rng.normal(size=(n, d))
    # deterministic round-robin user assignment: identical (n, n_users) calls
    # yield identical per-entity bucket shapes, so the vmapped solvers compile
    # once per shape for the whole suite (values stay rng-driven)
    users = np.arange(n) % n_users
    x_re = rng.normal(size=n)  # the per-user feature
    z = X @ w_global + user_bias[users] + user_slope[users] * x_re
    y = (z + 0.3 * rng.normal(size=n) > 0).astype(np.float64)
    # random-effect shard: column 0 = intercept, column 1 = x_re
    X_re = sp.csr_matrix(np.stack([np.ones(n), x_re], axis=1))
    user_ids = np.asarray([f"u{u}" for u in users], dtype=object)
    return X, X_re, user_ids, y


def build_coordinates(X, X_re, user_ids, y, task=TaskType.LOGISTIC_REGRESSION):
    n = len(y)
    fe_ds = FixedEffectDataset(LabeledData.build(X, y), feature_shard_id="global")
    re_ds = build_random_effect_dataset(
        X_re, user_ids, "userId", feature_shard_id="per-user", labels=y
    )
    coords = {
        "fixed": FixedEffectCoordinate(
            coordinate_id="fixed", dataset=fe_ds, task=task, configuration=CFG
        ),
        "per-user": RandomEffectCoordinate(
            coordinate_id="per-user",
            dataset=re_ds,
            task=task,
            configuration=CFG,
            base_offsets=jnp.zeros(n),
        ),
    }
    return coords, fe_ds, re_ds


def test_single_coordinate_equals_direct_solve(rng):
    X, _, _, y = glmix_data(rng)
    fe_ds = FixedEffectDataset(LabeledData.build(X, y))
    coord = FixedEffectCoordinate(
        coordinate_id="fixed",
        dataset=fe_ds,
        task=TaskType.LOGISTIC_REGRESSION,
        configuration=CFG,
    )
    result = run_coordinate_descent({"fixed": coord}, n_iterations=1)
    problem = GLMOptimizationProblem(task=TaskType.LOGISTIC_REGRESSION, configuration=CFG)
    direct, _ = problem.run(fe_ds.data)
    trained = result.model.get_model("fixed").model
    np.testing.assert_allclose(
        np.asarray(trained.coefficients.means),
        np.asarray(direct.coefficients.means),
        rtol=1e-6,
        atol=1e-8,
    )


def test_glmix_beats_fixed_effect_alone(rng):
    X, X_re, users, y = glmix_data(rng)
    n = len(y)
    split = 600
    tr = slice(0, split)
    va = slice(split, n)

    coords, _, _ = build_coordinates(X[tr], X_re[tr], users[tr], y[tr])
    fe_val = FixedEffectDataset(LabeledData.build(X[va], y[va]), feature_shard_id="global")
    re_val = build_random_effect_dataset(
        X_re[va], users[va], "userId", feature_shard_id="per-user", scoring_only=True
    )
    suite = EvaluationSuite(
        evaluators=[evaluator_for_type(EvaluatorType.AUC)],
        labels=y[va],
        offsets=np.zeros(n - split),
        weights=np.ones(n - split),
    )
    val_ds = {"fixed": fe_val, "per-user": re_val}

    full = run_coordinate_descent(
        coords, n_iterations=3, validation_datasets=val_ds, evaluation_suite=suite
    )
    fixed_only = run_coordinate_descent(
        {"fixed": coords["fixed"]},
        n_iterations=1,
        validation_datasets={"fixed": fe_val},
        evaluation_suite=suite,
    )
    assert full.best_metric > fixed_only.best_metric + 0.02
    assert full.best_metric > 0.75
    # history records one entry per (iteration, coordinate)
    assert len(full.metrics_history) == 3 * 2
    # best metric must equal the max AUC seen in history
    best_seen = max(m["AUC"] for _, _, m in full.metrics_history)
    assert full.best_metric == pytest.approx(best_seen)


def test_training_scores_match_model_scores(rng):
    X, X_re, users, y = glmix_data(rng, n=400)
    coords, fe_ds, re_ds = build_coordinates(X, X_re, users, y)
    result = run_coordinate_descent(coords, n_iterations=2)
    fe_score = result.model.get_model("fixed").score_dataset(fe_ds)
    re_score = result.model.get_model("per-user").score_dataset(re_ds)
    np.testing.assert_allclose(
        np.asarray(result.training_scores["fixed"]), np.asarray(fe_score), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(result.training_scores["per-user"]), np.asarray(re_score), rtol=1e-6
    )


def test_locked_coordinate_partial_retrain(rng):
    """Locked fixed effect: model unchanged, random effect trains against its scores
    (CoordinateDescent.scala:45, GameEstimator partial retrain)."""
    X, X_re, users, y = glmix_data(rng, n=400)
    n = len(y)
    coords, fe_ds, re_ds = build_coordinates(X, X_re, users, y)

    pre = run_coordinate_descent({"fixed": coords["fixed"]}, n_iterations=1)
    locked_model = pre.model.get_model("fixed")

    locked = ModelCoordinate(coordinate_id="fixed", dataset=fe_ds, model=locked_model)
    result = run_coordinate_descent(
        {"fixed": locked, "per-user": coords["per-user"]}, n_iterations=2
    )
    after = result.model.get_model("fixed")
    np.testing.assert_array_equal(
        np.asarray(after.model.coefficients.means),
        np.asarray(locked_model.model.coefficients.means),
    )
    # the random effect actually learned something non-trivial
    re_coef = np.asarray(result.model.get_model("per-user").coeffs)
    assert np.abs(re_coef).max() > 0.1


def test_all_locked_raises(rng):
    X, _, _, y = glmix_data(rng, n=120)
    fe_ds = FixedEffectDataset(LabeledData.build(X, y))
    coord = FixedEffectCoordinate(
        coordinate_id="fixed", dataset=fe_ds, task=TaskType.LOGISTIC_REGRESSION, configuration=CFG
    )
    model = coord.initialize_model()
    locked = ModelCoordinate(coordinate_id="fixed", dataset=fe_ds, model=model)
    with pytest.raises(ValueError, match="locked"):
        run_coordinate_descent({"fixed": locked}, n_iterations=1)


def test_residual_trick_consistency(rng):
    """After every update the stored full score equals the sum of per-coordinate
    scores (CoordinateDescent residual bookkeeping :197-204)."""
    X, X_re, users, y = glmix_data(rng, n=400)
    coords, _, _ = build_coordinates(X, X_re, users, y)
    result = run_coordinate_descent(coords, n_iterations=2)
    total = sum(result.training_scores.values())
    recomputed = sum(
        coords[cid].score(result.model.get_model(cid)) for cid in coords
    )
    np.testing.assert_allclose(np.asarray(total), np.asarray(recomputed), rtol=1e-6)


# ------------------------------------------------- the initial training score


class _ScoreSpy:
    """Duck-typed coordinate: everything is the wrapped coordinate's. Counts
    the ``score`` calls and the ``zero_model_score`` calls that were answered
    (not None); with ``hide`` it has no ``zero_model_score`` at all, like a
    coordinate that predates the method, so the loop has to score."""

    def __init__(self, inner, hide):
        self._inner = inner
        self._hide = hide
        self.scored = 0
        self.answered = 0

    def __getattr__(self, attr):
        if attr != "zero_model_score":
            return getattr(self._inner, attr)
        if self._hide:
            raise AttributeError(attr)
        return self._zero_model_score

    def score(self, model):
        self.scored += 1
        return self._inner.score(model)

    def _zero_model_score(self):
        out = self._inner.zero_model_score()
        self.answered += out is not None
        return out


def _table(model):
    return np.asarray(model.coeffs if hasattr(model, "coeffs") else model.model.coefficients.means)


def _bits(a):
    a = np.asarray(a)
    return a.dtype, a.shape, a.tobytes()  # +0.0 and -0.0 differ here


def _descent_bits(result):
    out = {"history": result.metrics_history, "best": result.best_metric}
    for cid, model in result.model.models.items():
        out[f"{cid}.model"] = _bits(_table(model))
        out[f"{cid}.score"] = _bits(result.training_scores[cid])
        out[f"{cid}.iterations"] = [
            (t.iterations_mean, t.iterations_max) if hasattr(t, "iterations_max") else t.iterations
            for t in result.trackers[cid]
        ]
    return out


# scenario -> the coordinates whose initial training score the coordinate may
# answer itself: the random effect of a fresh fit and nothing else
INITIAL_SCORE_SCENARIOS = {
    "fresh": {"per-user"},
    "warm": set(),
    "resumed": set(),
    "locked-fe": {"per-user"},
    "locked-re": set(),
}


@pytest.mark.parametrize("scenario", sorted(INITIAL_SCORE_SCENARIOS))
def test_initial_score_answered_only_for_a_fresh_zero_model(rng, tmp_path, scenario):
    """A fresh fit's random effect answers its initial score without the
    scoring kernel, and the run is bit for bit the run that scored it; a warm
    start, a checkpoint resume and a locked coordinate are scored as ever —
    a non-zero model must never be answered with zeros."""
    from photon_ml_tpu.io.checkpoint import CoordinateDescentCheckpointer

    X, X_re, users, y = glmix_data(rng, n=400)
    tr, va = slice(0, 300), slice(300, 400)
    val_ds = {
        "fixed": FixedEffectDataset(LabeledData.build(X[va], y[va]), feature_shard_id="global"),
        "per-user": build_random_effect_dataset(
            X_re[va], users[va], "userId", feature_shard_id="per-user", scoring_only=True
        ),
    }
    suite = EvaluationSuite(
        evaluators=[evaluator_for_type(EvaluatorType.AUC)],
        labels=y[va], offsets=np.zeros(100), weights=np.ones(100),
    )

    def build():
        return build_coordinates(X[tr], X_re[tr], users[tr], y[tr])

    def descend(coords, n_iterations, **kwargs):
        return run_coordinate_descent(
            coords, n_iterations=n_iterations,
            validation_datasets=val_ds, evaluation_suite=suite, **kwargs,
        )

    trained = descend(build()[0], 1).model.models
    assert all(np.abs(_table(m)).max() > 1e-3 for m in trained.values())

    def run(hide):
        coords, fe_ds, re_ds = build()
        kwargs = {}
        if scenario == "warm":
            kwargs["initial_models"] = trained
        elif scenario == "resumed":
            directory = str(tmp_path / f"ckpt-{hide}")
            descend(build()[0], 1, checkpointer=CoordinateDescentCheckpointer(directory, dtype=None))
            kwargs["checkpointer"] = CoordinateDescentCheckpointer(directory, dtype=None)
        elif scenario == "locked-fe":
            coords["fixed"] = ModelCoordinate("fixed", fe_ds, trained["fixed"])
        elif scenario == "locked-re":
            coords["per-user"] = ModelCoordinate("per-user", re_ds, trained["per-user"])
        spies = {cid: _ScoreSpy(coord, hide) for cid, coord in coords.items()}
        return descend(spies, 3, **kwargs), spies

    answered, spies = run(hide=False)
    scored, forced = run(hide=True)
    assert _descent_bits(answered) == _descent_bits(scored)
    assert len(answered.metrics_history) > 0

    expected = INITIAL_SCORE_SCENARIOS[scenario]
    for cid in spies:
        assert forced[cid].answered == 0
        assert forced[cid].scored >= 1, cid
        assert spies[cid].answered == (cid in expected), cid
        assert forced[cid].scored - spies[cid].scored == (cid in expected), cid
    locked = {"locked-fe": "fixed", "locked-re": "per-user"}.get(scenario)
    if locked is not None:
        assert np.abs(np.asarray(answered.training_scores[locked])).max() > 1e-3


# ------------------------------------------------------------- down-sampling


def test_binary_down_sampler_keeps_positives(rng):
    y = (rng.uniform(size=2000) < 0.3).astype(np.float64)
    X = rng.normal(size=(2000, 3))
    data = LabeledData.build(X, y)
    ds = BinaryClassificationDownSampler(down_sampling_rate=0.25, seed=7)
    out = ds.down_sample(data)
    w = np.asarray(out.weights)
    # every positive keeps weight 1
    assert np.all(w[y == 1.0] == 1.0)
    neg = w[y == 0.0]
    kept = neg > 0
    # kept negatives re-weighted by 1/rate
    np.testing.assert_allclose(neg[kept], 4.0)
    # keep fraction near the rate
    assert 0.15 < kept.mean() < 0.35
    # total negative weight is an unbiased estimate of the original
    assert abs(neg.sum() - (y == 0).sum()) / (y == 0).sum() < 0.15
    # successive calls RESAMPLE (the reference redraws per pass) ...
    out2 = ds.down_sample(data)
    assert not np.array_equal(w, np.asarray(out2.weights))
    # ... but a fresh sampler with the same seed reproduces the same sequence
    ds2 = BinaryClassificationDownSampler(down_sampling_rate=0.25, seed=7)
    np.testing.assert_array_equal(w, np.asarray(ds2.down_sample(data).weights))


def test_default_down_sampler_uniform(rng):
    y = rng.normal(size=1000)
    X = rng.normal(size=(1000, 3))
    data = LabeledData.build(X, y)
    out = DefaultDownSampler(down_sampling_rate=0.5, seed=3).down_sample(data)
    w = np.asarray(out.weights)
    assert set(np.unique(w)) <= {0.0, 1.0}
    assert 0.4 < w.mean() < 0.6


def test_down_sampler_factory():
    assert isinstance(
        down_sampler_for_task(TaskType.LOGISTIC_REGRESSION, 0.5),
        BinaryClassificationDownSampler,
    )
    assert isinstance(
        down_sampler_for_task(TaskType.LINEAR_REGRESSION, 0.5), DefaultDownSampler
    )
    with pytest.raises(ValueError):
        down_sampler_for_task(TaskType.LINEAR_REGRESSION, 1.5)


def test_fixed_effect_coordinate_with_down_sampling(rng):
    X, _, _, y = glmix_data(rng, n=800)
    fe_ds = FixedEffectDataset(LabeledData.build(X, y))
    coord = FixedEffectCoordinate(
        coordinate_id="fixed",
        dataset=fe_ds,
        task=TaskType.LOGISTIC_REGRESSION,
        configuration=CFG,
        down_sampler=BinaryClassificationDownSampler(down_sampling_rate=0.5, seed=11),
    )
    result = run_coordinate_descent({"fixed": coord}, n_iterations=1)
    coef = np.asarray(result.model.get_model("fixed").model.coefficients.means)
    # down-sampled solve still recovers a usable model
    problem = GLMOptimizationProblem(task=TaskType.LOGISTIC_REGRESSION, configuration=CFG)
    direct, _ = problem.run(fe_ds.data)
    ref = np.asarray(direct.coefficients.means)
    cos = coef @ ref / (np.linalg.norm(coef) * np.linalg.norm(ref))
    assert cos > 0.97


# ----------------------------------------------------------- divergence guard


class _HostileCoordinate:
    """Wraps a real coordinate; its solver 'diverges' on chosen update calls —
    the seeded-NaN hostile loss of the divergence-guard contract. ``poison``
    maps 1-based update-call index -> how ("nan" coefficients, "inf"
    objective)."""

    def __init__(self, inner, poison):
        self.inner = inner
        self.coordinate_id = inner.coordinate_id
        self.poison = dict(poison)
        self.calls = 0

    @property
    def is_locked(self):
        return False

    def initialize_model(self):
        return self.inner.initialize_model()

    def prepare_initial_model(self, model):
        return self.inner.prepare_initial_model(model)

    def score(self, model):
        return self.inner.score(model)

    def update_model(self, initial_model, partial_scores):
        model, tracker = self.inner.update_model(initial_model, partial_scores)
        self.calls += 1
        how = self.poison.get(self.calls)
        if how == "nan":
            glm = model.model
            bad = glm.coefficients.means.at[0].set(jnp.nan)
            model = dataclasses.replace(
                model,
                model=dataclasses.replace(
                    glm,
                    coefficients=dataclasses.replace(glm.coefficients, means=bad),
                ),
            )
        elif how == "inf":
            tracker = dataclasses.replace(tracker, final_value=float("inf"))
        return model, tracker


class TestDivergenceGuard:
    def test_nan_update_rejected_remaining_coordinates_intact(self, rng):
        X, X_re, user_ids, y = glmix_data(rng)
        coords, _, _ = build_coordinates(X, X_re, user_ids, y)
        hostile = _HostileCoordinate(coords["fixed"], poison={1: "nan", 2: "nan"})
        coords = {"fixed": hostile, "per-user": coords["per-user"]}

        result = run_coordinate_descent(coords, n_iterations=2)

        # every hostile update was rejected: the fixed model is still the zero
        # initialization, finite, and the random effect trained normally
        fe = np.asarray(result.model.get_model("fixed").model.coefficients.means)
        assert np.isfinite(fe).all()
        np.testing.assert_array_equal(fe, np.zeros_like(fe))
        re_coef = np.asarray(result.model.get_model("per-user").coeffs)
        assert np.isfinite(re_coef).all() and np.abs(re_coef).sum() > 0

        assert len(result.incidents) == 2
        for inc, it in zip(result.incidents, (0, 1)):
            assert inc.kind == "divergence"
            assert inc.coordinate_id == "fixed"
            assert inc.iteration == it
            assert "non-finite" in inc.cause

    def test_objective_blowup_rejected(self, rng):
        X, X_re, user_ids, y = glmix_data(rng)
        coords, _, _ = build_coordinates(X, X_re, user_ids, y)
        hostile = _HostileCoordinate(coords["fixed"], poison={1: "inf"})
        coords = {"fixed": hostile, "per-user": coords["per-user"]}
        result = run_coordinate_descent(coords, n_iterations=1)
        (inc,) = result.incidents
        assert inc.kind == "divergence" and "objective" in inc.cause
        fe = np.asarray(result.model.get_model("fixed").model.coefficients.means)
        np.testing.assert_array_equal(fe, np.zeros_like(fe))

    def test_transient_divergence_recovers_next_iteration(self, rng):
        # poison only the FIRST update: iteration 0 is rejected, iteration 1
        # trains normally — graceful degradation, then full recovery
        X, X_re, user_ids, y = glmix_data(rng)
        coords, _, _ = build_coordinates(X, X_re, user_ids, y)
        hostile = _HostileCoordinate(coords["fixed"], poison={1: "nan"})
        coords = {"fixed": hostile, "per-user": coords["per-user"]}
        result = run_coordinate_descent(coords, n_iterations=2)
        assert len(result.incidents) == 1
        fe = np.asarray(result.model.get_model("fixed").model.coefficients.means)
        assert np.isfinite(fe).all() and np.abs(fe).sum() > 0

    def test_incidents_persist_through_checkpoint_resume(self, rng, tmp_path):
        from photon_ml_tpu.io.checkpoint import CoordinateDescentCheckpointer

        X, X_re, user_ids, y = glmix_data(rng)

        def hostile_coords():
            coords, _, _ = build_coordinates(X, X_re, user_ids, y)
            return {
                "fixed": _HostileCoordinate(coords["fixed"], poison={1: "nan"}),
                "per-user": coords["per-user"],
            }

        ck_dir = str(tmp_path / "ck")
        run_coordinate_descent(
            hostile_coords(), n_iterations=1,
            checkpointer=CoordinateDescentCheckpointer(ck_dir, dtype=jnp.float64),
        )
        # the resumed run (now healthy) still reports its predecessor's incident
        healthy, _, _ = build_coordinates(X, X_re, user_ids, y)
        resumed = run_coordinate_descent(
            healthy, n_iterations=2,
            checkpointer=CoordinateDescentCheckpointer(ck_dir, dtype=jnp.float64),
        )
        assert len(resumed.incidents) == 1
        assert resumed.incidents[0].kind == "divergence"
        assert resumed.incidents[0].iteration == 0

    def test_healthy_run_has_no_incidents(self, rng):
        X, X_re, user_ids, y = glmix_data(rng)
        coords, _, _ = build_coordinates(X, X_re, user_ids, y)
        result = run_coordinate_descent(coords, n_iterations=1)
        assert result.incidents == []
