"""photon-ml-tpu: a TPU-native framework with the capabilities of LinkedIn Photon ML.

Trains and scores Generalized Linear Models (linear / logistic / Poisson regression and
smoothed-hinge linear SVM) and GLMix / GAME mixed-effect models (a fixed-effect GLM plus
per-entity random-effect GLMs, fit by block coordinate descent) — re-designed TPU-first:

- jitted ``lax.while_loop`` optimizers (LBFGS / OWLQN / LBFGSB / TRON), batched-first so
  ``vmap`` yields the per-entity version for free;
- dense / sparse-COO design matrices whose matvec & rmatvec map onto the MXU and
  segment ops instead of Spark treeAggregate;
- ``jax.sharding`` mesh parallelism (data-parallel fixed effect, entity-sharded random
  effects) instead of broadcast / shuffle;
- score exchange between coordinates as elementwise ops over a global sample axis
  instead of RDD joins.

Reference behavior catalogued in /root/repo/SURVEY.md; parity targets cite
reference files as ``photon-lib/.../File.scala:line``.
"""

# The recorder registers the process's compile listener when it is imported:
# first, so that every program the package makes lands in it as jit.* spans.
from photon_ml_tpu.util.timed import records as _records  # noqa: F401
from photon_ml_tpu.types import (
    TaskType,
    OptimizerType,
    RegularizationType,
    NormalizationType,
    VarianceComputationType,
    ConvergenceReason,
)
from photon_ml_tpu.normalization import NormalizationContext, FeatureDataStatistics

__version__ = "0.1.0"

# Lazy top-level conveniences: the whole quick-start in one import. (Laziness
# here avoids importing the heavier subpackages — estimators, parallel, io —
# eagerly; jax itself is already imported above via normalization.)
_LAZY = {
    "GameEstimator": "photon_ml_tpu.estimators.game_estimator",
    "GameResult": "photon_ml_tpu.estimators.game_estimator",
    "GameTransformer": "photon_ml_tpu.transformers.game_transformer",
    "GameServingEngine": "photon_ml_tpu.serving.engine",
    "get_engine": "photon_ml_tpu.serving.engine",
    "GameInput": "photon_ml_tpu.data.game_data",
    "CoordinateConfiguration": "photon_ml_tpu.estimators.config",
    "FixedEffectDataConfiguration": "photon_ml_tpu.estimators.config",
    "RandomEffectDataConfiguration": "photon_ml_tpu.estimators.config",
    "GLMOptimizationConfiguration": "photon_ml_tpu.optimization.config",
    "RegularizationContext": "photon_ml_tpu.optimization.config",
    "OptimizerConfig": "photon_ml_tpu.optimization.common",
    "EvaluatorType": "photon_ml_tpu.evaluation.evaluators",
    "make_mesh": "photon_ml_tpu.parallel.mesh",
    "make_mesh2": "photon_ml_tpu.parallel.feature_sharded",
    "save_game_model": "photon_ml_tpu.io.model_io",
    "load_game_model": "photon_ml_tpu.io.model_io",
    "enable_pallas": "photon_ml_tpu.ops",
}


def __getattr__(name):
    target = _LAZY.get(name)
    if target is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    value = getattr(importlib.import_module(target), name)
    globals()[name] = value  # cache: later accesses are plain dict lookups
    return value


def __dir__():
    return sorted(set(globals()) | set(_LAZY))


__all__ = [
    "TaskType",
    "OptimizerType",
    "RegularizationType",
    "NormalizationType",
    "VarianceComputationType",
    "ConvergenceReason",
    "NormalizationContext",
    "FeatureDataStatistics",
    "__version__",
    *sorted(_LAZY),
]
