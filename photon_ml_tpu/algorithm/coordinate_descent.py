"""Block coordinate descent over GAME coordinates.

Re-designs photon-lib algorithm/CoordinateDescent.scala:38-347 for TPU. The
reference exchanges scores between coordinates through full-outer-join RDD ops
(DataScores.scala:37-53) and persist/unpersist choreography; here every
coordinate's score is a dense [N] array over the global sample axis, so

- the residual trick ``partialScore = fullTrainingScore - ownScore``
  (CoordinateDescent.scala:197-204) is elementwise subtraction,
- ``addScoresToOffsets`` is elementwise addition (done inside each coordinate),
- there is no persistence choreography: arrays live on device, XLA manages memory.

Best-model selection on the primary validation evaluator follows
CoordinateDescent.scala:292-325: after every coordinate update the full validation
score is re-evaluated and the best GAME model snapshot kept. Locked coordinates
(partial retrain) contribute scores but are never updated (CoordinateDescent.scala:45).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Mapping, Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.algorithm.coordinate import (
    Coordinate,
    coefficient_arrays,
    score_model_on_dataset,
)
from photon_ml_tpu.evaluation.evaluators import EvaluationSuite
from photon_ml_tpu.models.game import FixedEffectModel, GameModel, RandomEffectModel
from photon_ml_tpu.resilience import faultpoint, register_fault_point
from photon_ml_tpu.resilience.incidents import Incident
from photon_ml_tpu.util.timed import span

Array = jnp.ndarray

logger = logging.getLogger(__name__)

# armed as coord.update.<coordinate_id> (hierarchical match): chaos proves a
# crash between any two coordinate updates resumes to the identical model
FP_COORD_UPDATE = register_fault_point("coord.update")


def _model_kind(model) -> str:
    """``fe`` | ``re``: the ``kind`` attribute of the spans of a coordinate."""
    if isinstance(model, FixedEffectModel):
        return "fe"
    return "re" if isinstance(model, RandomEffectModel) else type(model).__name__


def _update_attrs(coord, fused_path: bool) -> dict:
    """The ``score_path``, ``solver`` and ``loop`` attributes of a
    ``descent.update`` span, for the coordinates that name them (random
    effects: ``bucket`` | ``view``; ``direct`` | ``lbfgs`` | ``auto``; and
    ``lanes`` | ``vmap``, read from the coordinate before the span opens).
    Off the fused protocol the loop scores the model itself, through the
    view."""
    path = getattr(coord, "score_path", None)
    if path is None:
        return {}
    return {
        "score_path": path if fused_path else "view",
        "solver": coord.re_solver,
        "loop": coord.solve_loop,
    }


def _device_guard(model, tracker) -> tuple:
    """The divergence guard's inputs as DEVICE scalars — no host sync here.

    Returns ``(coefs_ok, value_ok, final_value)``: all coefficient arrays
    finite; the solver's final objective finite (None when the tracker has no
    final value, e.g. random-effect trackers); the raw final value for the
    incident message. The host read happens later — immediately when the run
    validates (the reject decision gates validation), else in the
    once-per-iteration batched flush."""
    flags = [jnp.all(jnp.isfinite(a)) for a in coefficient_arrays(model)]
    coefs_ok = flags[0] if len(flags) == 1 else jnp.stack(flags).all()
    final_value = getattr(tracker, "final_value", None)
    value_ok = None if final_value is None else jnp.isfinite(jnp.asarray(final_value))
    return coefs_ok, value_ok, final_value


def _guard_cause(coefs_ok, value_ok, final_value) -> Optional[str]:
    """Host-side reject cause from materialized guard values (same wording
    and check order as the original blocking guard: the solver's final
    objective value blew up, or the coefficients it emitted contain NaN/Inf —
    TRON/L-BFGS/OWL-QN on hostile data can do either)."""
    if value_ok is not None and not bool(value_ok):
        # mirror the pre-device-guard message exactly ("inf"/"nan" via float)
        v = final_value if isinstance(final_value, float) else float(final_value)
        return f"training objective is non-finite ({v})"
    if not bool(coefs_ok):
        return "solver emitted non-finite coefficients"
    return None


def _select_variances(ok, new_var, prev_var):
    """Reject semantics for variance arrays: keep the previous ones on a
    rejected update. Variances are excluded from the guard itself
    (coefficient_arrays), but a diverged solve's NaN variances must not
    survive an update the loop reports as rejected — when the previous model
    had none (first update), the device-side reject value is zeros and the
    host-side reject handling then strips the field back to None
    (_strip_variances), restoring the old keep-previous-model schema."""
    if new_var is None:
        return None
    if prev_var is not None:
        return jnp.where(ok, new_var, prev_var)
    return jnp.where(ok, new_var, jnp.zeros_like(new_var))


def _has_variances(model) -> bool:
    if isinstance(model, RandomEffectModel):
        return model.variances is not None
    if isinstance(model, FixedEffectModel):
        return model.model.coefficients.variances is not None
    return False


def _strip_variances(model):
    """Drop the variance field entirely — the reject repair for updates whose
    PREVIOUS model carried no variances: a select can't emit 'absent', so the
    device side substitutes zeros and this restores variances=None once the
    reject is known host-side (zero variances would read as infinite
    confidence in an exported model)."""
    if isinstance(model, RandomEffectModel) and model.variances is not None:
        return dataclasses.replace(model, variances=None)
    if (
        isinstance(model, FixedEffectModel)
        and model.model.coefficients.variances is not None
    ):
        coef = dataclasses.replace(model.model.coefficients, variances=None)
        return dataclasses.replace(
            model, model=dataclasses.replace(model.model, coefficients=coef)
        )
    return model


def _select_update(ok, new_model, prev_model):
    """Device-side reject for coordinates without an in-program guard:
    ``where(ok, new, prev)`` on the coefficient (and variance) arrays, so the
    loop never has to read ``ok`` to keep the previous model's values
    bit-for-bit."""
    if isinstance(new_model, FixedEffectModel):
        glm = new_model.model
        prev_coef = prev_model.model.coefficients
        coef = dataclasses.replace(
            glm.coefficients,
            means=jnp.where(ok, glm.coefficients.means, prev_coef.means),
            variances=_select_variances(
                ok, glm.coefficients.variances, prev_coef.variances
            ),
        )
        return dataclasses.replace(
            new_model, model=dataclasses.replace(glm, coefficients=coef)
        )
    if isinstance(new_model, RandomEffectModel):
        coeffs = jnp.where(ok, new_model.coeffs, prev_model.coeffs)
        variances = _select_variances(ok, new_model.variances, prev_model.variances)
        return dataclasses.replace(new_model, coeffs=coeffs, variances=variances)
    raise TypeError(f"Unknown model type: {type(new_model).__name__}")


@dataclasses.dataclass
class _PendingGuard:
    """A deferred divergence decision: the update's guard scalars stay on
    device until the iteration-end batched flush."""

    iteration: int
    coordinate_id: str
    guard: tuple  # (coefs_ok, value_ok, final_value) — device scalars
    # the pre-update model carried no variances: on a reject the stored
    # model's device-substituted zero variances must be stripped back to None
    prev_had_no_variances: bool = False


def _flush_guards(pending: list, incidents: list, models: dict) -> None:
    """ONE batched transfer for every deferred guard of the iteration, then
    incident recording for the rejects (the state itself was already kept
    previous device-side — this writes the paper trail and repairs the
    variance schema of first-update rejects)."""
    if not pending:
        return
    with span("descent.guard", iteration=pending[0].iteration):
        host = jax.device_get([p.guard for p in pending])
    for p, (coefs_ok, value_ok, final_value) in zip(pending, host):
        cause = _guard_cause(coefs_ok, value_ok, final_value)
        if cause is None:
            continue
        if p.prev_had_no_variances:
            models[p.coordinate_id] = _strip_variances(models[p.coordinate_id])
        incident = Incident(
            kind="divergence",
            cause=cause,
            action="update rejected; previous model kept",
            coordinate_id=p.coordinate_id,
            iteration=p.iteration,
        )
        incidents.append(incident)
        logger.warning("iter %d %s", p.iteration, incident.summary())


def _snapshot_models(models: dict, donating: set) -> dict:
    """Copy coefficient arrays out of models owned by donating coordinates:
    the next fused update CONSUMES its input table (donate_argnums), so a
    best-model snapshot aliasing the live array would be invalidated.
    Non-donating coordinates keep zero-copy snapshots."""
    out = dict(models)
    for cid in donating:
        m = out.get(cid)
        if isinstance(m, RandomEffectModel):
            out[cid] = dataclasses.replace(
                m,
                coeffs=jnp.array(m.coeffs, copy=True),
                variances=(
                    None if m.variances is None else jnp.array(m.variances, copy=True)
                ),
            )
        elif isinstance(m, FixedEffectModel):
            coef = m.model.coefficients
            coef = dataclasses.replace(
                coef,
                means=jnp.array(coef.means, copy=True),
                variances=(
                    None
                    if coef.variances is None
                    else jnp.array(coef.variances, copy=True)
                ),
            )
            out[cid] = dataclasses.replace(
                m, model=dataclasses.replace(m.model, coefficients=coef)
            )
    return out


@dataclasses.dataclass
class CoordinateDescentResult:
    """Outcome of one descent run."""

    model: GameModel  # model after the final iteration
    best_model: GameModel  # best by primary validation metric (== model if no validation)
    best_metric: Optional[float]
    metrics_history: list  # [(iteration, coordinate_id, {metric: value})]
    trackers: dict  # coordinate_id -> [tracker per update]
    training_scores: dict  # coordinate_id -> final [N] score array
    # full metrics dict of the best snapshot (survives checkpoint resume, where
    # the row that set best_metric may predate the resumed metrics_history)
    best_metrics: Optional[dict] = None
    # survived failures (rejected divergent updates, checkpoint rollbacks) —
    # graceful degradation is recorded, never silent (resilience/incidents.py)
    incidents: list = dataclasses.field(default_factory=list)

    @property
    def has_validation(self) -> bool:
        return self.best_metric is not None


def run_coordinate_descent(
    coordinates: Mapping[str, Coordinate],
    n_iterations: int,
    initial_models: Optional[Mapping[str, object]] = None,
    validation_datasets: Optional[Mapping[str, object]] = None,
    evaluation_suite: Optional[EvaluationSuite] = None,
    checkpointer: Optional[object] = None,
    defer_guard: bool = True,
    active_sets: Optional[Mapping[str, object]] = None,
) -> CoordinateDescentResult:
    """Run block coordinate descent (CoordinateDescent.run/descend:93-346).

    ``coordinates`` is ordered — iteration order is the update sequence. Locked
    coordinates are scored, never updated. ``validation_datasets`` must cover every
    coordinate id when ``evaluation_suite`` is given; validation scores are summed
    across coordinates and handed to the suite after each update.

    The descent loop is SYNC-FREE between coordinate updates: coordinates
    offering the fused ``update_and_score`` protocol run as one donated XLA
    program per update with the divergence guard applied device-side, and the
    generic path computes its guard as device scalars with a ``where``-based
    reject — the blocking per-update ``device_get`` of the old guard becomes
    one batched transfer per iteration (``defer_guard=False`` restores the
    blocking per-update read; validating runs always resolve per update, so
    rejected updates skip validation exactly as before).

    ``checkpointer`` (io/checkpoint.CoordinateDescentCheckpointer) enables
    iteration-level failure recovery: after each completed iteration the models +
    best-model snapshot are saved atomically, and a rerun with the same
    checkpointer resumes from the last completed iteration (training scores are
    recomputed from the restored models — they are pure functions of them).

    ``active_sets`` (continuous training, photon_ml_tpu/continuous/) switches a
    coordinate into ACTIVE-SET delta mode: ``{coordinate_id: host bool [E]
    mask}``. Such a coordinate must offer ``update_model_active`` and have an
    initial model to warm-start from; only masked entities are re-solved, the
    rest keep the previous generation's coefficients bit for bit. Coordinates
    absent from the mapping update normally (the fixed effect refreshes over
    whatever its coordinate was configured with, e.g. a reservoir
    down-sampler).
    """
    if n_iterations < 1:
        raise ValueError(f"n_iterations must be >= 1, got {n_iterations}")
    coordinate_ids = list(coordinates.keys())
    if not coordinate_ids:
        raise ValueError("No coordinates to descend over")
    validate = evaluation_suite is not None
    if validate:
        if validation_datasets is None:
            raise ValueError(
                "evaluation_suite requires validation_datasets covering every coordinate"
            )
        missing = [c for c in coordinate_ids if c not in validation_datasets]
        if missing:
            raise ValueError(f"Missing validation datasets for coordinates {missing}")

    # --- resume from checkpoint (overrides initial_models) -----------------------
    start_iteration = 0
    restored_best_models = None
    restored_best_metric = None
    restored_best_metrics = None
    incidents: list[Incident] = []
    if checkpointer is not None:
        # install only where the checkpointer supports the protocol (the
        # attribute exists) and the caller didn't already set a provider
        if getattr(checkpointer, "extra_state_provider", False) is None:
            # fingerprint-ADJACENT run state rides the manifest's "extra" key:
            # the measured re_solver="auto" decisions per coordinate, so a
            # resumed run replays the original run's per-bucket solver choices
            # bitwise instead of re-measuring against restored warm tables
            # (a re-probe could flip a choice). The estimator fingerprint pins
            # the "auto" STRING; the measured outcome stays out of it.
            def _collect_extra_state():
                auto = {
                    cid: coord.re_solver_stats()
                    for cid, coord in coordinates.items()
                    if getattr(coord, "re_solver_stats", None) is not None
                    and coord.re_solver_stats() is not None
                }
                return {"re_solver_auto": auto} if auto else None

            checkpointer.extra_state_provider = _collect_extra_state
        restored = checkpointer.restore()
        if restored is not None and set(restored["models"]) != set(coordinate_ids):
            logger.warning(
                "Ignoring checkpoint: coordinates %s do not match this run's %s",
                sorted(restored["models"]),
                sorted(coordinate_ids),
            )
            restored = None
        if restored is None:
            # a restore that ends in a fresh start (only corrupt generations,
            # or a rejected checkpoint) must not forget the quarantines it
            # physically performed on the way
            incidents = [
                Incident.from_dict(d)
                for d in getattr(checkpointer, "restore_incidents", [])
            ]
        if restored is not None:
            start_iteration = restored["completed_iterations"]
            initial_models = restored["models"]
            restored_best_models = restored["best_models"]
            restored_best_metric = restored["best_metric"]
            restored_best_metrics = restored.get("best_metrics")
            # incident history survives the crash: a resumed run still knows
            # what its predecessor absorbed (and any restore-time rollback)
            incidents = [
                Incident.from_dict(d) for d in restored.get("incidents") or []
            ]
            auto_state = (restored.get("extra") or {}).get("re_solver_auto") or {}
            for cid, rec in auto_state.items():
                coord = coordinates.get(cid)
                if coord is not None and hasattr(coord, "seed_solver_decision"):
                    coord.seed_solver_decision(rec)
            if start_iteration > n_iterations:
                logger.warning(
                    "Checkpoint has %d completed iterations but only %d were "
                    "requested; returning the checkpointed state unchanged "
                    "(clear the checkpoint directory to retrain from scratch)",
                    start_iteration,
                    n_iterations,
                )
            else:
                logger.info(
                    "Resuming coordinate descent from checkpoint: %d/%d iterations done",
                    start_iteration,
                    n_iterations,
                )

    # --- initialize models and their training/validation scores -----------------
    models: dict[str, object] = {}
    train_scores: dict[str, Array] = {}
    val_scores: dict[str, Array] = {}
    with span("descent.init"):
        for cid, coord in coordinates.items():
            init = None if initial_models is None else initial_models.get(cid)
            if init is None and active_sets is not None and active_sets.get(cid) is not None:
                # without a warm start, initialize_model() would silently supply a
                # ZERO model and the pass would export coefficient 0 for every
                # inactive entity — an active set only makes sense over the
                # previous generation's coefficients
                raise ValueError(
                    f"Coordinate {cid!r} has an active set but no initial model: "
                    "active-set delta updates keep inactive entities' previous "
                    "coefficients, so a warm-start model is required "
                    "(initial_models or a resumable checkpoint)"
                )
            if init is not None:
                # adapt external/restored models to the coordinate's dataset:
                # RE models re-align entity rows, FE models pad + place
                # coefficients for feature-sharded datasets
                init = coord.prepare_initial_model(init)
            model = init if init is not None else coord.initialize_model()
            models[cid] = model
            # the INITIAL model's training score. Of a fresh fit that is the
            # zero model, and a coordinate that vouches for it answers without
            # the scoring kernel (duck-typed coordinates may predate the
            # method). Never inferred here: a locked coordinate's initial
            # model is its trained one, a given or restored one is warm.
            zero_model_score = (
                getattr(coord, "zero_model_score", None) if init is None else None
            )
            answered = zero_model_score() if zero_model_score is not None else None
            with span(
                "descent.init_score", cid=cid, kind=_model_kind(model),
                scored=answered is None,
            ):
                train_scores[cid] = coord.score(model) if answered is None else answered
                if validate:
                    val_scores[cid] = score_model_on_dataset(model, validation_datasets[cid])

    n = {int(s.shape[0]) for s in train_scores.values()}
    if len(n) != 1:
        raise ValueError(f"Coordinate datasets disagree on sample count: {sorted(n)}")

    trackers: dict[str, list] = {cid: [] for cid in coordinate_ids}
    metrics_history: list = []
    best_model: Optional[GameModel] = None
    best_metric: Optional[float] = None
    best_metrics: Optional[dict] = None
    if restored_best_models is not None:
        best_model = GameModel(models=restored_best_models)
        best_metric = restored_best_metric
        best_metrics = restored_best_metrics
    primary = evaluation_suite.primary if validate else None

    updatable = [cid for cid in coordinate_ids if not coordinates[cid].is_locked]
    if not updatable:
        raise ValueError("All coordinates are locked; nothing to train")

    # guard resolution: a validating run must know the reject BEFORE scoring
    # validation data (rejected updates skip validation); otherwise decisions
    # defer to one batched transfer per iteration
    sync_guard = validate or not defer_guard
    # coordinates whose live model tables are fed back DONATED: their arrays
    # in `models`/`train_scores` are consumed by the next update, so
    # snapshots of them must copy (see _snapshot_models)
    donating: set = set()

    for iteration in range(start_iteration, n_iterations):
        # Recompute (not accumulate) the total at each iteration boundary: the
        # state is then a pure function of the models dict, which makes a
        # checkpoint-resumed run BIT-identical to an uninterrupted one (resume
        # restores models and recomputes scores the same way). The other half
        # of that promise is the coordinates': a fused update must hand back
        # the bits its ``score(model)`` gives (the random-effect program
        # scores from its bucket blocks only where it does:
        # algorithm/random_effect.bucket_score_slots).
        full_train_score = sum(train_scores.values())
        pending: list[_PendingGuard] = []
        for cid in updatable:
            coord = coordinates[cid]
            faultpoint(f"{FP_COORD_UPDATE}.{cid}")
            prev_model = models[cid]
            active = None if active_sets is None else active_sets.get(cid)
            # duck-typed coordinates (test wrappers, external impls) may
            # predate the fused protocol — treat a missing method as "no
            # fused path". Active-set updates always take the generic path:
            # the delta program gathers/scatters host-chosen lane sets, which
            # the donated fused program cannot express.
            update_and_score = (
                getattr(coord, "update_and_score", None) if active is None else None
            )
            with span(
                "descent.update", cid=cid, kind=_model_kind(prev_model), iteration=iteration,
                **_update_attrs(coord, update_and_score is not None),
            ) as update_span:
                # Residual trick (CoordinateDescent.scala:197-204)
                partial = full_train_score - train_scores[cid]
                prev_score = train_scores[cid]
                prev_had_var = _has_variances(prev_model)
                fused = (
                    update_and_score(prev_model, partial, prev_score, donate=cid in donating)
                    if update_and_score is not None
                    else None
                )
                if fused is not None:
                    model, new_score, tracker = fused
                    donating.add(cid)
                    guard_ok = getattr(tracker, "guard_ok", None)
                    if guard_ok is None:
                        # the fused protocol applies its reject IN-PROGRAM and
                        # must surface the flag: without it the loop could store
                        # a diverged model while recording "previous model kept"
                        raise TypeError(
                            f"Coordinate {cid!r}: update_and_score must return a "
                            "tracker exposing the device-side guard_ok flag"
                        )
                    guard = (guard_ok, None, None)
                    # the fused program applied the reject select internally (and
                    # consumed the previous buffers): state always moves to the
                    # returned arrays — on a reject they HOLD the previous values
                    models[cid] = model
                    train_scores[cid] = new_score
                elif active is not None:
                    update_active = getattr(coord, "update_model_active", None)
                    if update_active is None:
                        raise TypeError(
                            f"Coordinate {cid!r} has an active set but no "
                            "update_model_active method (active-set delta updates "
                            "are a random-effect capability)"
                        )
                    model, tracker = update_active(prev_model, partial, active)
                    guard = _device_guard(model, tracker)
                else:
                    model, tracker = coord.update_model(prev_model, partial)
                    guard = _device_guard(model, tracker)
                trackers[cid].append(tracker)

                if sync_guard:
                    # validating (or defer_guard=False) runs resolve per update
                    # on purpose: a rejected update must skip validation
                    with span("descent.guard", cid=cid, iteration=iteration):
                        cause = _guard_cause(*jax.device_get(guard))  # jaxlint: disable=HS001 deliberate per-update read, validation gates on the reject decision
                    if cause is not None:
                        # Divergence guard: REJECT the update — the previous model
                        # for this coordinate is kept (scores unchanged), an
                        # incident is recorded, and the descent continues over the
                        # remaining coordinates. Graceful degradation instead of a
                        # poisoned GAME model, mirroring eager Photon's keep-best
                        # semantics. full_train_score stays the pre-update total.
                        incident = Incident(
                            kind="divergence",
                            cause=cause,
                            action="update rejected; previous model kept",
                            coordinate_id=cid,
                            iteration=iteration,
                        )
                        incidents.append(incident)
                        logger.warning("iter %d %s", iteration, incident.summary())
                        if fused is not None and not prev_had_var:
                            # the in-program reject substituted zeros for the
                            # absent previous variances; restore variances=None
                            models[cid] = _strip_variances(models[cid])
                        continue
                    if fused is None:
                        models[cid] = model
                        new_score = coord.score(model)
                        train_scores[cid] = new_score
                    full_train_score = partial + new_score
                else:
                    if fused is None:
                        # device-side reject: keep the previous values without
                        # reading the flag (scoring the selected model reproduces
                        # the previous score bit-for-bit on a reject)
                        ok = guard[0] if guard[1] is None else jnp.logical_and(*guard[:2])
                        model = _select_update(ok, model, prev_model)
                        models[cid] = model
                        new_score = coord.score(model)
                        train_scores[cid] = new_score
                    # on a (not-yet-known) reject this rebuilds the total as
                    # partial + previous-score values — possibly one ulp off the
                    # pre-update total; the iteration-boundary recompute restores
                    # exactness, and healthy updates are bit-identical
                    full_train_score = partial + new_score
                    pending.append(
                        _PendingGuard(iteration, cid, guard, prev_had_no_variances=not prev_had_var)
                    )

            if logger.isEnabledFor(logging.INFO):
                # summary() materializes device trackers: only pay the sync
                # when the log line is actually emitted
                logger.info(
                    "iter %d coordinate %s: %s (%.2fs)",
                    iteration,
                    cid,
                    tracker.summary(),
                    update_span.seconds,
                )

            if validate:
                with span("descent.validate", cid=cid, iteration=iteration):
                    with span("descent.validate_score", cid=cid):
                        val_scores[cid] = score_model_on_dataset(model, validation_datasets[cid])
                        total_val = sum(val_scores.values())
                    # ends in the evaluators' own host read: the scores, or
                    # (metric_path="device") the few integers of a device metric
                    metric_path = evaluation_suite.metric_path(total_val)
                    with span("descent.evaluate", cid=cid, metric_path=metric_path):
                        metrics = evaluation_suite.evaluate(total_val)
                    metrics_history.append((iteration, cid, metrics))
                    metric = metrics[primary.name]
                    logger.info("iter %d coordinate %s: validation %s", iteration, cid, metrics)
                    if primary.better_than(metric, best_metric):
                        best_metric = metric
                        best_metrics = metrics
                        with span("descent.snapshot", cid=cid):
                            best_model = GameModel(models=_snapshot_models(models, donating))

        # incident details for the whole iteration in ONE batched transfer
        # (the reject itself already happened device-side)
        _flush_guards(pending, incidents, models)

        if checkpointer is not None:
            with span("descent.checkpoint", iteration=iteration):
                checkpointer.maybe_save(
                    iteration + 1,
                    dict(models),
                    None if best_model is None else dict(best_model.models),
                    best_metric,
                    best_metrics,
                    force=(iteration + 1 == n_iterations),
                    incidents=incidents,
                )

    with span("descent.finish"):
        # Restore the host-value tracker contract before results escape: the
        # trackers buffered device values through the sync-free loop;
        # materialize them now, outside the hot path (this is also where the
        # solver counters are published, util/timed). Every update's guard has
        # been read by now (per update, or in the iteration's batched flush),
        # so the programs these values come from have ended: a transfer, no
        # new wait, and ONE batched transfer for all of them (a round trip per
        # tracker was 11 ms of a 7.8 s unit on the v5e; PERF.md section 6).
        # Probe the CLASS, not the instance: LazyRandomEffectTracker's
        # __getattr__ treats an instance probe as a field read.
        buffered = [
            t
            for tracker_list in trackers.values()
            for t in tracker_list
            if getattr(type(t), "device_values", None) is not None
        ]
        fetched = jax.device_get([type(t).device_values(t) for t in buffered])
        for t, host in zip(buffered, fetched):
            type(t).materialize(t, host)

        final_model = GameModel(models=dict(models))
        if best_model is None:
            best_model = final_model
        return CoordinateDescentResult(
            model=final_model,
            best_model=best_model,
            best_metric=best_metric,
            metrics_history=metrics_history,
            trackers=trackers,
            training_scores=dict(train_scores),
            best_metrics=best_metrics,
            incidents=incidents,
        )
