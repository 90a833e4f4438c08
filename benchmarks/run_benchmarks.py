"""The five BASELINE.md benchmark configurations, measured end to end.

Each config runs its workload once UNTIMED (compile warm-up: XLA programs live
in the process jit/solver caches, the production regime under a persistent
compilation cache) and then reports the steady-state wall clock of a second,
identical run. Baselines are recorded the same way, so the comparison is
compile-free on both sides.

Each config reports wall-clock-to-converged-quality plus the converged metric,
and compares against the recorded CPU baseline (baselines.json, regenerate with
``--record-baseline``) with an explicit quality-parity assertion — the north
star is "faster at identical AUC", so a speedup only counts when the metric
matches the baseline run.

The reference repo ships no datasets (a1a is a download in its tutorial,
MovieLens-20M is external); this container has no egress, so every config runs
on a DETERMINISTIC synthetic dataset with the same shape statistics:

  1. a1a-shaped sparse binary logistic (1,605 train / 30,956 test rows, 123
     binary features, ~14 nnz/row), ingested THROUGH the Avro reader, LBFGS+L2
     sweep over lambda in {0.1, 1, 10, 100} (README.md:240-305 tutorial).
  2. Linear + Poisson regression, TRON, L2 (BASELINE.md config #2; the
     elastic-net L1 part routes to OWLQN by design, so TRON measures the
     smooth path).
  3. GLMix 3-coordinate logistic (fixed + per-user + per-item), MovieLens-like
     shape scaled by --scale (default 100k samples, 2k users, 500 items).
  4. Smoothed-hinge linear SVM fixed effect + warm-start partial retrain.
  5. GAME hyperparameter auto-tune: Bayesian GP search over reg weights.

Usage:
  python benchmarks/run_benchmarks.py [--configs 1,3] [--scale 1.0]
      [--record-baseline] [--output results.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "baselines.json")
AUC_PARITY_TOL = 0.005


# --------------------------------------------------------------- data builders


def _a1a_like(rng, n_train=1605, n_test=30956, d=123, nnz_per_row=14):
    """a1a shape: binary features, ~11% density, imbalanced binary labels."""
    w = rng.normal(size=d) * (rng.random(d) < 0.4)

    def draw(n):
        import scipy.sparse as sp

        rows = np.repeat(np.arange(n), nnz_per_row)
        cols = rng.integers(0, d, size=n * nnz_per_row)
        X = sp.csr_matrix(
            (np.ones(n * nnz_per_row), (rows, cols)), shape=(n, d)
        )
        X.data[:] = 1.0  # binary indicators (duplicates collapse)
        X.sum_duplicates()
        z = X @ w - 1.2  # shift for ~25% positive rate like a1a
        y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(float)
        return X, y

    return draw(n_train), draw(n_test)


class _GlmixTruth:
    """One fixed ground-truth GLMix model; train/validation draws share it.

    The truth is genuinely mixed-effects: per-entity biases AND per-entity
    coefficients on a few covariates. Without the latter, the random-effect
    covariate dimensions would have true weight zero for every entity — a pure
    overfitting surface where training the REs can only HURT validation, which
    degenerates the benchmark into selecting a fixed-effect-only snapshot."""

    def __init__(self, rng, n_users, n_items, d=64, k_re=3):
        self.rng = rng
        self.d = d
        self.k_re = k_re
        self.n_users, self.n_items = n_users, n_items
        self.w = rng.normal(size=d) * 0.3
        self.u_eff = 0.6 * rng.normal(size=n_users)
        self.i_eff = 0.6 * rng.normal(size=n_items)
        self.u_coef = 0.3 * rng.normal(size=(n_users, k_re))
        self.i_coef = 0.3 * rng.normal(size=(n_items, k_re))

    def draw(self, n):
        rng = self.rng
        k = self.k_re
        X = rng.normal(size=(n, self.d)).astype(np.float32)
        users = rng.integers(0, self.n_users, size=n)
        items = rng.integers(0, self.n_items, size=n)
        z = (
            X @ self.w
            + self.u_eff[users]
            + self.i_eff[items]
            + np.sum(X[:, :k] * self.u_coef[users], axis=1)
            + np.sum(X[:, k : 2 * k] * self.i_coef[items], axis=1)
        )
        y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(float)
        return X, users, items, y


# --------------------------------------------------------------------- configs


def config1_a1a_avro_lbfgs_l2(n_train=1605, n_test=30956):
    """Fixed-effect logistic via Avro ingest, LBFGS+L2 sweep (config #1).

    Size parameters exist for the suite's smoke test; benchmark runs use the
    a1a defaults."""
    import jax.numpy as jnp

    from photon_ml_tpu.data import avro_io
    from photon_ml_tpu.data.readers import read_merged_avro
    from photon_ml_tpu.estimators.config import (
        CoordinateConfiguration,
        FeatureShardConfiguration,
        FixedEffectDataConfiguration,
    )
    from photon_ml_tpu.estimators.game_estimator import GameEstimator
    from photon_ml_tpu.evaluation.evaluators import EvaluatorType
    from photon_ml_tpu.optimization.common import OptimizerConfig
    from photon_ml_tpu.optimization.config import (
        GLMOptimizationConfiguration,
        RegularizationContext,
    )
    from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType

    rng = np.random.default_rng(1605)
    (Xtr, ytr), (Xte, yte) = _a1a_like(rng, n_train=n_train, n_test=n_test)

    def write(path, X, y):
        X = X.tocsr()

        def records():
            for i in range(X.shape[0]):
                row = X.getrow(i)
                yield {
                    "uid": str(i),
                    "label": float(y[i]),
                    "features": [
                        {"name": f"f{j}", "term": "", "value": float(v)}
                        for j, v in zip(row.indices, row.data)
                    ],
                    "metadataMap": {},
                    "weight": 1.0,
                    "offset": 0.0,
                }

        avro_io.write_container(path, avro_io.TRAINING_EXAMPLE_SCHEMA, records())

    shards = {"global": FeatureShardConfiguration(feature_bags=("features",))}
    with tempfile.TemporaryDirectory(prefix="bench_a1a_") as tmp:
        write(os.path.join(tmp, "train.avro"), Xtr, ytr)
        write(os.path.join(tmp, "test.avro"), Xte, yte)
        t0 = time.perf_counter()
        train, maps, _ = read_merged_avro(os.path.join(tmp, "train.avro"), shards)
        test, _, _ = read_merged_avro(
            os.path.join(tmp, "test.avro"), shards, index_maps=maps
        )
        ingest_s = time.perf_counter() - t0

    cfg = GLMOptimizationConfiguration(
        optimizer_config=OptimizerConfig(
            optimizer_type=OptimizerType.LBFGS, max_iterations=50
        ),
        regularization_context=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations={
            "global": CoordinateConfiguration(
                FixedEffectDataConfiguration("global"), cfg,
                reg_weights=(0.1, 1.0, 10.0, 100.0),
            )
        },
        validation_evaluators=[EvaluatorType.AUC],
        dtype=jnp.float32,
    )
    est.fit(train, validation_data=test)  # untimed compile warm-up
    t0 = time.perf_counter()
    results = est.fit(train, validation_data=test)
    best = est.select_best_model(results)
    train_s = time.perf_counter() - t0
    return {
        "metric": "a1a_avro_lbfgs_l2_wall_clock_to_auc",
        "value": round(train_s, 3),
        "unit": "seconds",
        "auc": round(float(best.best_metric), 5),
        "ingest_seconds": round(ingest_s, 3),
        "sweep_size": 4,
    }


def config2_tron_linear_poisson():
    """Linear + Poisson regression, TRON, L2 (config #2)."""
    import jax.numpy as jnp

    from photon_ml_tpu.data.dataset import LabeledData
    from photon_ml_tpu.evaluation.evaluators import rmse
    from photon_ml_tpu.optimization.common import OptimizerConfig
    from photon_ml_tpu.optimization.config import (
        GLMOptimizationConfiguration,
        RegularizationContext,
    )
    from photon_ml_tpu.optimization.problem import GLMOptimizationProblem
    from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType

    rng = np.random.default_rng(2)
    n, d = 50_000, 64
    X = rng.normal(size=(n, d))
    w = rng.normal(size=d) * 0.3
    y_lin = X @ w + 0.5 * rng.normal(size=n)
    y_poi = rng.poisson(np.exp(np.clip(X @ w * 0.25, -4, 4))).astype(float)

    out = {}
    for warmup in (True, False):  # first pass untimed: compile warm-up
        if not warmup:
            t0 = time.perf_counter()
        for task, y in ((TaskType.LINEAR_REGRESSION, y_lin),
                        (TaskType.POISSON_REGRESSION, y_poi)):
            problem = GLMOptimizationProblem(
                task=task,
                configuration=GLMOptimizationConfiguration(
                    optimizer_config=OptimizerConfig(
                        optimizer_type=OptimizerType.TRON, max_iterations=50
                    ),
                    regularization_context=RegularizationContext(RegularizationType.L2),
                    regularization_weight=1.0,
                ),
            )
            data = LabeledData.build(X, y, dtype=jnp.float32)
            glm, res = problem.run(data)
            out[task.value] = int(res.iterations)
    wall = time.perf_counter() - t0
    scores = np.asarray(
        LabeledData.build(X, y_lin, dtype=jnp.float32).X.matvec(
            jnp.asarray(w, dtype=jnp.float32)
        )
    )
    return {
        "metric": "tron_linear_poisson_wall_clock",
        "value": round(wall, 3),
        "unit": "seconds",
        "rmse_floor": round(float(rmse(scores, y_lin, np.ones(n))), 4),
        "iterations": out,
    }


def config3_glmix_movielens_like(scale=1.0):
    """3-coordinate GLMix wall-clock-to-AUC (config #3, the north star)."""
    import jax.numpy as jnp

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
    from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType

    import scipy.sparse as sp

    rng = np.random.default_rng(20)
    n = int(100_000 * scale)
    n_users, n_items = int(2_000 * scale), int(500 * scale)
    truth = _GlmixTruth(rng, n_users, n_items)
    X, users, items, y = truth.draw(n)
    Xv, uv, iv, yv = truth.draw(n // 4)

    # Random effects see a SMALL shard (intercept + a few covariates), the
    # realistic GLMix shape (per-entity bias + limited interactions — the
    # reference's per-member models are narrow) and the flagship bench's
    # workload. Giving entities the full 64-dim shard lets ~50-sample
    # per-entity solves overfit until training the REs HURTS validation AUC,
    # which degenerates the benchmark into measuring a fixed-effect-only
    # snapshot.
    def re_shard(M):
        return sp.csr_matrix(
            np.concatenate([np.ones((M.shape[0], 1), np.float32), M[:, :7]], axis=1)
        )

    def cfg(iters):
        return GLMOptimizationConfiguration(
            optimizer_config=OptimizerConfig(
                optimizer_type=OptimizerType.LBFGS, max_iterations=iters
            ),
            regularization_context=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        )

    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations={
            "global": CoordinateConfiguration(
                FixedEffectDataConfiguration("global"), cfg(50)
            ),
            "per-user": CoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "re"), cfg(30)
            ),
            "per-item": CoordinateConfiguration(
                RandomEffectDataConfiguration("itemId", "re"), cfg(30)
            ),
        },
        n_iterations=2,
        validation_evaluators=[EvaluatorType.AUC],
        dtype=jnp.float32,
    )
    train = GameInput(
        features={"global": X, "re": re_shard(X)}, labels=y,
        id_columns={"userId": users, "itemId": items},
    )
    val = GameInput(
        features={"global": Xv, "re": re_shard(Xv)}, labels=yv,
        id_columns={"userId": uv, "itemId": iv},
    )
    est.fit(train, validation_data=val)  # untimed compile warm-up
    t0 = time.perf_counter()
    results = est.fit(train, validation_data=val)
    best = est.select_best_model(results)
    wall = time.perf_counter() - t0
    return {
        "metric": "glmix_movielens_like_wall_clock_to_auc",
        "value": round(wall, 3),
        "unit": "seconds",
        "auc": round(float(best.best_metric), 5),
        "samples": n,
        "samples_per_sec": round(2 * n / wall, 1),
    }


def config4_svm_warm_start():
    """Smoothed-hinge SVM + warm-start partial retrain (config #4)."""
    import jax.numpy as jnp

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
    from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType

    rng = np.random.default_rng(4)
    n, d, n_users = 30_000, 32, 500
    truth = _GlmixTruth(rng, n_users, 10, d=d)
    X, users, _, y = truth.draw(n)
    Xv, uv, _, yv = truth.draw(n // 3)

    def cfg(iters=50):
        return GLMOptimizationConfiguration(
            optimizer_config=OptimizerConfig(
                optimizer_type=OptimizerType.LBFGS, max_iterations=iters
            ),
            regularization_context=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        )

    coords = {
        "global": CoordinateConfiguration(FixedEffectDataConfiguration("global"), cfg()),
        "per-user": CoordinateConfiguration(
            RandomEffectDataConfiguration("userId", "global"), cfg(30)
        ),
    }
    train = GameInput(features={"global": X}, labels=y, id_columns={"userId": users})
    val = GameInput(features={"global": Xv}, labels=yv, id_columns={"userId": uv})

    est = GameEstimator(
        task=TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
        coordinate_configurations=coords,
        validation_evaluators=[EvaluatorType.AUC],
        dtype=jnp.float32,
    )
    warm0 = est.fit(train, validation_data=val)[-1].best_model  # untimed warm-up
    t0 = time.perf_counter()
    results = est.fit(train, validation_data=val)
    full_s = time.perf_counter() - t0
    warm = results[-1].best_model

    retrain = GameEstimator(
        task=TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
        coordinate_configurations=coords,
        validation_evaluators=[EvaluatorType.AUC],
        partial_retrain_locked_coordinates=("global",),
        dtype=jnp.float32,
    )
    retrain.fit(train, validation_data=val, initial_model=warm0)  # warm-up
    t0 = time.perf_counter()
    retrain_results = retrain.fit(train, validation_data=val, initial_model=warm)
    retrain_s = time.perf_counter() - t0
    return {
        "metric": "svm_warm_start_retrain_wall_clock",
        "value": round(full_s + retrain_s, 3),
        "unit": "seconds",
        "full_fit_seconds": round(full_s, 3),
        "partial_retrain_seconds": round(retrain_s, 3),
        "auc": round(float(retrain_results[-1].best_metric), 5),
    }


def config5_bayesian_tuning():
    """GAME Bayesian GP auto-tuning over reg weights (config #5)."""
    import jax.numpy as jnp

    from photon_ml_tpu.data.game_data import GameInput
    from photon_ml_tpu.estimators.config import (
        CoordinateConfiguration,
        FixedEffectDataConfiguration,
    )
    from photon_ml_tpu.estimators.evaluation_function import (
        GameEstimatorEvaluationFunction,
    )
    from photon_ml_tpu.estimators.game_estimator import GameEstimator
    from photon_ml_tpu.evaluation.evaluators import EvaluatorType
    from photon_ml_tpu.hyperparameter import GaussianProcessSearch
    from photon_ml_tpu.optimization.common import OptimizerConfig
    from photon_ml_tpu.optimization.config import (
        GLMOptimizationConfiguration,
        RegularizationContext,
    )
    from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType

    rng = np.random.default_rng(5)
    n, d = 20_000, 24
    X = rng.normal(size=(n, d))
    w = rng.normal(size=d)
    y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w)))).astype(float)
    Xv = rng.normal(size=(n // 2, d))
    yv = (rng.random(n // 2) < 1 / (1 + np.exp(-(Xv @ w)))).astype(float)

    cfg = GLMOptimizationConfiguration(
        optimizer_config=OptimizerConfig(
            optimizer_type=OptimizerType.LBFGS, max_iterations=40
        ),
        regularization_context=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations={
            "global": CoordinateConfiguration(FixedEffectDataConfiguration("global"), cfg)
        },
        validation_evaluators=[EvaluatorType.AUC],
        dtype=jnp.float32,
    )
    fn = GameEstimatorEvaluationFunction(
        est,
        {"global": cfg},
        GameInput(features={"global": X}, labels=y),
        GameInput(features={"global": Xv}, labels=yv),
        is_opt_max=True,
    )
    GaussianProcessSearch(fn.num_params, fn, seed=5).find(2)  # untimed warm-up
    t0 = time.perf_counter()
    search = GaussianProcessSearch(fn.num_params, fn, seed=5)
    results = search.find(6)
    wall = time.perf_counter() - t0
    best_auc = max(r.best_metric for r in results)
    return {
        "metric": "bayesian_tuning_wall_clock",
        "value": round(wall, 3),
        "unit": "seconds",
        "tuning_iterations": 6,
        "best_auc": round(float(best_auc), 5),
    }


CONFIGS = {
    "1": ("a1a_avro_lbfgs_l2", config1_a1a_avro_lbfgs_l2),
    "2": ("tron_linear_poisson", config2_tron_linear_poisson),
    "3": ("glmix_movielens_like", config3_glmix_movielens_like),
    "4": ("svm_warm_start", config4_svm_warm_start),
    "5": ("bayesian_tuning", config5_bayesian_tuning),
}

QUALITY_KEYS = ("auc", "best_auc")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="1,2,3,4,5")
    ap.add_argument("--scale", type=float, default=1.0, help="config 3 size factor")
    ap.add_argument("--record-baseline", action="store_true",
                    help="store results as the CPU baseline")
    ap.add_argument("--output", default=None)
    ap.add_argument("--no-strict", action="store_true",
                    help="exit 0 even when a config fails quality parity "
                         "(default: parity failure exits 1 — a speedup only "
                         "counts at matching quality); a config that ERRORS "
                         "always exits 1")
    args = ap.parse_args(argv)

    import jax

    platform = jax.devices()[0].platform
    baselines = {}
    if os.path.exists(BASELINE_PATH):
        with open(BASELINE_PATH) as f:
            baselines = json.load(f)

    results = {}
    for key in args.configs.split(","):
        name, fn = CONFIGS[key.strip()]
        kwargs = {"scale": args.scale} if key.strip() == "3" else {}
        try:
            res = fn(**kwargs)
        except Exception as e:  # recorded so the other configs still run;
            # an errored config makes the run exit non-zero (below)
            res = {"error": f"{type(e).__name__}: {e}"[:300]}
            results[name] = res
            print(json.dumps({name: res}), flush=True)
            continue
        res["platform"] = platform
        base = baselines.get(name)
        if base and "value" in base and not args.record_baseline:
            res["vs_baseline"] = round(base["value"] / res["value"], 4)  # speedup
            for qk in QUALITY_KEYS:
                if qk in res and qk in base:
                    res["quality_parity"] = bool(
                        abs(res[qk] - base[qk]) <= AUC_PARITY_TOL
                    )
                    res["baseline_" + qk] = base[qk]
        results[name] = res
        print(json.dumps({name: res}), flush=True)

    if args.record_baseline:
        from photon_ml_tpu.util.provenance import measurement_provenance

        provenance = measurement_provenance(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ignore_paths=("benchmarks/baselines.json",),
        )
        for res in results.values():
            res.update(provenance)
        # merge: re-recording a subset must not erase other configs' baselines
        # (and an errored config must not clobber a good one with its error)
        recorded = {n: r for n, r in results.items() if "error" not in r}
        baselines.update(recorded)
        with open(BASELINE_PATH, "w") as f:
            json.dump(baselines, f, indent=2)
        print(json.dumps({"recorded_baseline_for": list(recorded)}))
    if args.output:
        with open(args.output, "w") as f:
            json.dump(results, f, indent=2)

    errored = [n for n, r in results.items() if "error" in r]
    if errored:  # never excused: a config that did not run is not a result
        print(json.dumps({"configs_errored": errored}))
        sys.exit(1)
    failed = [n for n, r in results.items() if r.get("quality_parity") is False]
    if failed and not args.no_strict:
        print(json.dumps({"quality_parity_failed": failed}))
        sys.exit(1)


if __name__ == "__main__":
    main()
