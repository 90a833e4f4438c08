"""Fused GAME backend: the whole coordinate-descent pass as ONE XLA program.

The host backend (algorithm/coordinate_descent.py) dispatches one solver
program per coordinate update with host round trips in between — faithful to
the reference's driver⇄executor choreography (CoordinateDescent.scala:119-346)
and required for its full feature surface (normalization, down-sampling,
constraints, per-update validation, checkpointing). On an accelerator those
round trips ARE the latency floor at bench shapes, so the flagship pass is
also available as a single jitted SPMD program (parallel/game.py — the
program bench.py measures). This module exposes that program through
GameEstimator for the configurations whose semantics it can reproduce
exactly; anything else raises with the reasons rather than silently
degrading.

Semantic difference, by design: validation runs after each full PASS (the
fused program has no host boundary between coordinate updates), so the best
model is tracked at pass granularity, not per coordinate update as in the
host loop.
"""

from __future__ import annotations

import functools
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.algorithm.coordinate import score_model_on_dataset
from photon_ml_tpu.algorithm.coordinate_descent import CoordinateDescentResult
from photon_ml_tpu.data.dataset import FixedEffectDataset
from photon_ml_tpu.data.random_effect import RandomEffectDataset
from photon_ml_tpu.models.game import FixedEffectModel, GameModel, RandomEffectModel
from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.types import RegularizationType, TaskType, VarianceComputationType


def fused_pass_ineligibilities(estimator, opt_configs: Mapping) -> list[str]:
    """Why this (estimator, sweep configuration) cannot run the fused pass.

    Empty list = eligible. Every condition mirrors a capability the single-jit
    program (parallel/game.py) does not implement; the host backend covers all
    of them.
    """
    reasons: list[str] = []
    coord_ids = list(estimator.coordinate_configurations)
    configs = estimator.coordinate_configurations

    from photon_ml_tpu.estimators.config import (
        FixedEffectDataConfiguration,
        RandomEffectDataConfiguration,
    )

    if not coord_ids:
        reasons.append("no coordinates")
        return reasons
    first = configs[coord_ids[0]].data_config
    if not isinstance(first, FixedEffectDataConfiguration):
        reasons.append("first coordinate must be the fixed effect")
    for cid in coord_ids[1:]:
        if not isinstance(configs[cid].data_config, RandomEffectDataConfiguration):
            reasons.append(
                f"coordinate {cid!r}: only [fixed, random...] sequences are fused"
            )
    for cid in coord_ids:
        cfg = configs[cid]
        if 0.0 < cfg.down_sampling_rate < 1.0:
            reasons.append(f"coordinate {cid!r}: down-sampling")
        if cfg.box_constraints is not None:
            reasons.append(f"coordinate {cid!r}: box constraints")
        if cfg.per_entity_reg_weights:
            reasons.append(f"coordinate {cid!r}: per-entity regularization weights")
        dc = cfg.data_config
        if isinstance(dc, RandomEffectDataConfiguration) and dc.projector is not None:
            reasons.append(f"coordinate {cid!r}: random projection")
        oc = opt_configs[cid]
        if oc.regularization_context.regularization_type not in (
            RegularizationType.NONE,
            RegularizationType.L2,
        ):
            reasons.append(f"coordinate {cid!r}: only NONE/L2 regularization is fused")
    if estimator.normalization_contexts and any(
        not n.is_identity for n in estimator.normalization_contexts.values()
    ):
        reasons.append("normalization")
    if VarianceComputationType(estimator.variance_computation) != (
        VarianceComputationType.NONE
    ):
        reasons.append("coefficient variances")
    if estimator.partial_retrain_locked_coordinates:
        reasons.append("locked coordinates (partial retrain)")
    if estimator.checkpoint_directory is not None:
        reasons.append("iteration checkpointing")
    if estimator.mesh is not None and estimator.mesh.devices.ndim != 1:
        reasons.append("2-D (data x model) meshes")
    return reasons


@functools.lru_cache(maxsize=None)
def _fused_step(task, fe_config, re_configs: tuple, mesh, re_solver: str = "lbfgs"):
    """Cross-fit trace cache for the fused pass.

    Data is a jit ARGUMENT (as in parallel/game.make_jitted_game_step):
    estimator fits repeat — warm-up + timed runs, sweeps, notebooks — and
    with argument-form data every fit after the first is a jit-cache hit
    instead of a full retrace of the pass.

    Regularization weights are traced arguments too, and the cache key uses
    the WEIGHT-STRIPPED configs (``with_weight(0.0)``): a reg-weight sweep or
    a Bayesian tuning run reuses ONE compiled pass across every candidate —
    the same reuse surface solver_cache gives the host loop. Registered with
    solver_cache.clear() because the traced program bakes in the trace-time
    Pallas fuse decision."""
    from photon_ml_tpu.parallel.game import game_train_step

    fuse_fe = mesh.devices.size == 1
    shard_mesh = mesh if mesh.devices.size > 1 else None

    @functools.partial(jax.jit, donate_argnums=(1,))
    def _step(d, params, fe_l2, re_l2):
        return game_train_step(
            d, params, task, fe_config, re_configs,
            fuse_fe=fuse_fe, shard_mesh=shard_mesh,
            fe_l2=fe_l2, re_l2=re_l2, re_solver=re_solver,
        )

    return _step


def _register_with_solver_cache() -> None:
    from photon_ml_tpu.optimization import solver_cache

    solver_cache.register_cache(_fused_step.cache_clear)


_register_with_solver_cache()


def run_fused_game_descent(
    estimator,
    datasets: Mapping[str, object],
    opt_configs: Mapping,
    validation_datasets: Optional[Mapping[str, object]],
    evaluation_suite,
    data,
    mesh,
    warm_params: Optional[dict] = None,
) -> tuple[CoordinateDescentResult, dict]:
    """One sweep configuration through the single-jit pass.

    ``data`` is the ShardedGameData built ONCE by the caller (identical
    across sweep configurations — rebuilding would re-pad and re-transfer
    the whole dataset per configuration).

    Returns (a CoordinateDescentResult interchangeable with the host loop's,
    the chaining params for the next sweep configuration — the BEST pass's
    params when validating, mirroring the host loop's
    ``warm = descent.best_model``, else the final pass's)."""
    from photon_ml_tpu.parallel.game import init_game_params

    if estimator.n_iterations < 1:
        raise ValueError(
            f"n_iterations must be >= 1, got {estimator.n_iterations}"
        )
    coord_ids = list(estimator.coordinate_configurations)
    fe_cid, re_cids = coord_ids[0], coord_ids[1:]
    fe_ds: FixedEffectDataset = datasets[fe_cid]
    re_ds: list[RandomEffectDataset] = [datasets[c] for c in re_cids]
    task = TaskType(estimator.task)

    dtype = data.labels.dtype
    cached = _fused_step(
        task,
        opt_configs[fe_cid].with_weight(0.0),
        tuple(opt_configs[c].with_weight(0.0) for c in re_cids),
        mesh,
        getattr(estimator, "re_solver", "lbfgs"),
    )
    fe_l2 = jnp.asarray(opt_configs[fe_cid].l2_weight, dtype=dtype)
    re_l2 = tuple(jnp.asarray(opt_configs[c].l2_weight, dtype=dtype) for c in re_cids)
    step = lambda p: cached(data, p, fe_l2, re_l2)  # noqa: E731
    params = warm_params if warm_params is not None else init_game_params(data, mesh)

    validate = evaluation_suite is not None
    primary = evaluation_suite.primary if validate else None
    metrics_history: list = []
    best_model = best_metric = best_metrics = best_params = None
    model = None
    diag = None

    def snapshot_model():
        return _params_to_model(estimator, task, params, fe_cid, fe_ds, re_cids, re_ds)

    for iteration in range(estimator.n_iterations):
        params, diag = step(params)
        if validate:  # model snapshots are only needed per pass when scoring
            model = snapshot_model()
            total_val = sum(
                score_model_on_dataset(model.get_model(cid), validation_datasets[cid])
                for cid in coord_ids
            )
            metrics = evaluation_suite.evaluate(total_val)
            # one history row per PASS (the fused program has no host boundary
            # between coordinate updates to evaluate at)
            metrics_history.append((iteration, coord_ids[-1], metrics))
            metric = metrics[primary.name]
            if primary.better_than(metric, best_metric):
                best_metric = metric
                best_metrics = metrics
                best_model = model
                # the step donates its params input: copy before the next pass
                best_params = jax.tree_util.tree_map(
                    lambda a: jnp.array(a, copy=True), params
                )

    if model is None:  # without validation only the final model materializes
        model = snapshot_model()
    # one transfer for both tracker scalars (not two blocking reads)
    fe_value_h, fe_iters_h = jax.device_get((diag["fe_value"], diag["fe_iterations"]))
    fe_tracker = _FusedPassTracker(
        final_value=float(fe_value_h),
        iterations=int(fe_iters_h),
        passes=estimator.n_iterations,
    )
    result = CoordinateDescentResult(
        model=model,
        best_model=best_model if best_model is not None else model,
        best_metric=best_metric,
        metrics_history=metrics_history,
        trackers={fe_cid: [fe_tracker]},
        training_scores={},  # the fused program keeps scores on device only
        best_metrics=best_metrics,
    )
    return result, (best_params if best_params is not None else params)


class _FusedPassTracker:
    """Minimal tracker for the fused pass (the per-coordinate reasons live
    inside the jitted program; only the fixed effect's final state surfaces)."""

    def __init__(self, final_value: float, iterations: int, passes: int):
        self.final_value = final_value
        self.iterations = iterations
        self.passes = passes
        self.convergence_reason = "FUSED_PASS"

    def summary(self) -> str:
        return (
            f"fused pass x{self.passes}: fe_value={self.final_value:.6g} "
            f"(fe {self.iterations} iters in final pass)"
        )


def _params_to_model(
    estimator, task, params, fe_cid, fe_ds, re_cids, re_ds
) -> GameModel:
    """Device params -> the same GameModel the host backend produces.

    Arrays are COPIED out of params: the step donates its params argument, so
    a model aliasing them would be deleted by the next pass/configuration."""
    glm = GeneralizedLinearModel(
        Coefficients(jnp.array(params["fixed"], copy=True)), task
    )
    models: dict[str, object] = {
        fe_cid: FixedEffectModel(model=glm, feature_shard_id=fe_ds.feature_shard_id)
    }
    for cid, ds, table in zip(re_cids, re_ds, params["re"]):
        E = ds.n_entities
        models[cid] = RandomEffectModel(
            re_type=ds.re_type,
            feature_shard_id=ds.feature_shard_id,
            task=task,
            entity_ids=ds.entity_ids,
            coeffs=jnp.array(table[:E], copy=True),
            proj_indices=ds.proj_indices[:E],
            variances=None,
        )
    return GameModel(models=models)
