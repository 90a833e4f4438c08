"""Single-program random-effect coordinate update tests.

The fused update (optimization/solver_cache.re_coordinate_update_program +
RandomEffectCoordinate.update_and_score) must be a pure performance
transformation of the per-bucket loop: bitwise-equal coefficients, variances
and scores across normalization x per-entity-reg x variance configurations,
donation that can never invalidate caller-held models, a device-side
divergence guard with unchanged reject semantics, and a descent loop that
stops retracing after the first iteration.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.algorithm import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
    run_coordinate_descent,
    train_random_effect,
)
from photon_ml_tpu.analysis.runtime_guard import RetraceError, no_retrace
from photon_ml_tpu.data.dataset import FixedEffectDataset, LabeledData
from photon_ml_tpu.data.random_effect import build_random_effect_dataset
from photon_ml_tpu.normalization import FeatureDataStatistics, NormalizationContext
from photon_ml_tpu.optimization.common import OptimizerConfig
from photon_ml_tpu.optimization.config import (
    GLMOptimizationConfiguration,
    RegularizationContext,
)
from photon_ml_tpu.types import (
    NormalizationType,
    RegularizationType,
    TaskType,
    VarianceComputationType,
)
from tests import parity_tiers

CFG = GLMOptimizationConfiguration(
    optimizer_config=OptimizerConfig(max_iterations=50, tolerance=1e-9),
    regularization_context=RegularizationContext(RegularizationType.L2),
    regularization_weight=1.0,
)

N, D, N_USERS = 420, 3, 12


def make_workload(rng):
    """Deterministic shapes (same bucket classes for every test in the file)
    with rng-driven values; entity counts vary so several shape classes
    exist."""
    X = rng.normal(size=(N, D))
    # deterministic skewed assignment: entity e gets ~(e+1) shares
    shares = np.repeat(np.arange(N_USERS), np.arange(1, N_USERS + 1))
    users = shares[np.arange(N) % len(shares)]
    w = rng.normal(size=D)
    y = (X @ w + 0.7 * rng.normal(size=N_USERS)[users] > 0).astype(np.float64)
    re_dense = np.concatenate([np.ones((N, 1)), 2.0 * X[:, :2] + 0.5], axis=1)
    X_re = sp.csr_matrix(re_dense)
    stats = FeatureDataStatistics.compute(re_dense, intercept_index=0)
    norm = NormalizationContext.build(NormalizationType.STANDARDIZATION, stats)
    return X, X_re, users, y, norm


def build_coords(
    workload,
    *,
    use_program,
    normalization=None,
    per_entity=None,
    variance=VarianceComputationType.NONE,
    precision=None,
    dtype=jnp.float32,
):
    X, X_re, users, y, norm = workload
    fe_ds = FixedEffectDataset(LabeledData.build(X, y), feature_shard_id="global")
    re_ds = build_random_effect_dataset(
        X_re, users, "userId", feature_shard_id="per-user", labels=y,
        normalization=normalization,
        intercept_index=0 if normalization is not None else None,
        dtype=dtype,
    )
    assert len(re_ds.buckets) >= 2
    return {
        "fixed": FixedEffectCoordinate(
            coordinate_id="fixed", dataset=fe_ds,
            task=TaskType.LOGISTIC_REGRESSION, configuration=CFG,
        ),
        "per-user": RandomEffectCoordinate(
            coordinate_id="per-user", dataset=re_ds,
            task=TaskType.LOGISTIC_REGRESSION, configuration=CFG,
            base_offsets=jnp.zeros(N, dtype=re_ds.sample_vals.dtype),
            normalization=normalization,
            variance_computation=variance,
            per_entity_reg_weights=per_entity,
            use_update_program=use_program,
            precision=precision,
        ),
    }


def descent_state(result):
    out = {}
    for cid in result.model.models:
        m = result.model.get_model(cid)
        if hasattr(m, "coeffs"):
            out[f"{cid}.coeffs"] = np.asarray(m.coeffs)
            if m.variances is not None:
                out[f"{cid}.variances"] = np.asarray(m.variances)
        else:
            out[f"{cid}.means"] = np.asarray(m.model.coefficients.means)
        out[f"{cid}.score"] = np.asarray(result.training_scores[cid])
    return out


# --------------------------------------------------------------- parity matrix


@pytest.fixture(scope="module")
def order_exact():
    """parity_tiers' probe of this backend, once a module."""
    return parity_tiers.order_exact()


@pytest.mark.parametrize("with_norm", [False, True], ids=["raw", "norm"])
@pytest.mark.parametrize("with_per_entity", [False, True], ids=["uniform", "per-entity-l2"])
@pytest.mark.parametrize(
    "variance",
    [VarianceComputationType.NONE, VarianceComputationType.SIMPLE],
    ids=["novar", "simplevar"],
)
def test_update_program_parity(rng, order_exact, with_norm, with_per_entity, variance):
    """The same coefficients, variances and [N] scores as the per-bucket loop
    across the featureful configuration matrix, over multiple descent
    iterations: bit for bit where the backend is order-exact, else by
    parity_tiers' two bounds."""
    workload = make_workload(rng)
    norm = workload[-1] if with_norm else None
    per_entity = (
        {int(e): float(v) for e, v in enumerate(rng.uniform(0.4, 2.5, size=N_USERS))}
        if with_per_entity
        else None
    )

    def descend(use_program, n_iterations, dtype):
        coords = build_coords(
            workload, use_program=use_program, normalization=norm,
            per_entity=per_entity, variance=variance, dtype=dtype,
        )
        return descent_state(
            run_coordinate_descent(
                coords, n_iterations=n_iterations, defer_guard=use_program
            )
        )

    parity_tiers.assert_program_matches_loop(descend, order_exact)


def _stub_descent(drift):
    """A descent whose program side is ``drift(dtype)`` (relative) off its
    loop side in every update."""

    def descend(use_program, n_iterations, dtype):
        w = np.linspace(0.5, 1.0, 12).astype(dtype)
        return {"coeffs": w * dtype(1.0 + drift(dtype)) if use_program else w}

    return descend


def _one_ulp(dtype):
    return float(np.finfo(dtype).eps)


@pytest.mark.parametrize(
    "exact, drift, tier",
    [
        (True, lambda dtype: 0.0, "bitwise"),
        (True, _one_ulp, AssertionError),  # one ulp is too much there
        (False, _one_ulp, "tolerance"),  # and reassociation passes here
        (False, lambda dtype: 1e-3, AssertionError),  # where a 1e-3 fault does not
    ],
    ids=["exact", "exact-ulp", "inexact-ulp", "inexact-fault"],
)
def test_parity_tier_follows_the_probe(exact, drift, tier):
    """The probe's answer selects the tier, and each tier refuses what it
    should."""
    descend = _stub_descent(drift)
    if tier is AssertionError:
        with pytest.raises(AssertionError):
            parity_tiers.assert_program_matches_loop(descend, exact)
    else:
        assert parity_tiers.assert_program_matches_loop(descend, exact) == tier


def test_parity_probe_reads_a_stubbed_backend_both_ways():
    """Order-exact where fusion leaves the bits alone; not where the fused
    form rounds once (an FMA) and the separate one twice."""

    def rounds_twice(a, b, c):
        return np.asarray(a) * np.asarray(b) + np.asarray(c)

    def rounds_once(a, b, c):
        wide = np.asarray(a, np.float64) * np.asarray(b, np.float64)
        return (wide + np.asarray(c, np.float64)).astype(np.float32)

    assert parity_tiers.order_exact(rounds_twice, rounds_twice)
    assert not parity_tiers.order_exact(rounds_once, rounds_twice)


# ------------------------------------------------------------- donation safety


def _donation_supported() -> bool:
    donated = jnp.arange(4.0)
    jax.jit(lambda a: a + 1.0, donate_argnums=0)(donated)
    return donated.is_deleted()


def test_steady_state_updates_donate_and_outputs_stay_live(rng):
    """Iteration 2..N feed the previous outputs back donated (the hot loop
    stops copying the [E, K] table), while the final result's arrays are
    always readable."""
    workload = make_workload(rng)
    coords = build_coords(workload, use_program=True)
    c = coords["per-user"]
    zeros = jnp.zeros(N, dtype=c.dataset.sample_vals.dtype)

    m1, s1, _ = c.update_and_score(None, zeros, zeros, donate=False)
    m2, s2, _ = c.update_and_score(m1, jnp.zeros(N), s1, donate=True)
    if _donation_supported():
        # the previous table and score were CONSUMED by the second update
        assert m1.coeffs.is_deleted()
        assert s1.is_deleted()
    # outputs are fresh buffers, fully usable
    assert np.isfinite(np.asarray(m2.coeffs)).all()
    assert np.isfinite(np.asarray(s2)).all()


def test_external_warm_start_model_survives_descent(rng):
    """donate=False on foreign buffers: a caller-held warm-start model must
    never be invalidated by the descent's donation (use-after-donate
    safety)."""
    workload = make_workload(rng)
    X, X_re, users, y, _ = workload
    re_ds = build_random_effect_dataset(
        X_re, users, "userId", feature_shard_id="per-user", labels=y
    )
    warm_model, _ = train_random_effect(
        re_ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(N)
    )
    warm_coeffs_before = np.asarray(warm_model.coeffs).copy()

    coords = build_coords(workload, use_program=True)
    result = run_coordinate_descent(
        coords, n_iterations=3, initial_models={"per-user": warm_model}
    )
    # the warm model's buffer is alive and unchanged after 3 donated updates
    assert not warm_model.coeffs.is_deleted()
    np.testing.assert_array_equal(np.asarray(warm_model.coeffs), warm_coeffs_before)
    # and every result array is readable
    for arr in descent_state(result).values():
        assert np.isfinite(arr).all()


def test_warm_start_survives_generation_growth_bitwise(rng):
    """The continuous-training contract on top of the donation discipline:
    train gen-N, GROW the entity set (new rows for two existing entities plus
    two brand-new entities, previous row order pinned), run an active-set
    delta pass warm-started from gen-N — every untouched entity's
    coefficients are bitwise gen-N's, and the foreign gen-N table itself
    survives the pass."""
    workload = make_workload(rng)
    X, X_re, users, y, _ = workload
    coords = build_coords(workload, use_program=True)
    gen_n = run_coordinate_descent(coords, n_iterations=2)
    prev = gen_n.model.get_model("per-user")
    prev_coeffs = np.asarray(prev.coeffs).copy()

    n_new = 36
    Xn = rng.normal(size=(n_new, D))
    re_new = np.concatenate([np.ones((n_new, 1)), 2.0 * Xn[:, :2] + 0.5], axis=1)
    new_users = np.concatenate(
        [np.repeat([0, 1], 8), np.repeat([N_USERS, N_USERS + 1], 10)]
    )
    y_new = (Xn @ rng.normal(size=D) > 0).astype(np.float64)
    grown_ds = build_random_effect_dataset(
        sp.vstack([X_re, sp.csr_matrix(re_new)], format="csr"),
        np.concatenate([users, new_users]),
        "userId",
        feature_shard_id="per-user",
        labels=np.concatenate([y, y_new]),
        entity_order=prev.entity_ids,
    )
    # stable growth: gen-N's row order is a verbatim prefix of the grown layout
    assert tuple(grown_ds.entity_ids)[: len(prev.entity_ids)] == prev.entity_ids

    coord = RandomEffectCoordinate(
        coordinate_id="per-user", dataset=grown_ds,
        task=TaskType.LOGISTIC_REGRESSION, configuration=CFG,
        base_offsets=jnp.zeros(N + n_new, dtype=grown_ds.sample_vals.dtype),
    )
    touched = {0, 1, N_USERS, N_USERS + 1}
    active = np.array([e in touched for e in grown_ds.entity_ids], dtype=bool)
    result = run_coordinate_descent(
        {"per-user": coord}, n_iterations=1,
        initial_models={"per-user": prev},
        active_sets={"per-user": active},
    )
    grown = result.model.get_model("per-user")
    stats = coord.last_active_stats
    assert stats.n_active == int(active.sum()) == 4
    for i, e in enumerate(prev.entity_ids):
        if e in touched:
            assert not np.array_equal(np.asarray(grown.coeffs[i]), prev_coeffs[i])
        else:
            np.testing.assert_array_equal(
                np.asarray(grown.coeffs[i]), prev_coeffs[i], err_msg=str(e)
            )
    # donation discipline: the foreign gen-N table is alive and unchanged
    assert not prev.coeffs.is_deleted()
    np.testing.assert_array_equal(np.asarray(prev.coeffs), prev_coeffs)


def test_best_model_snapshot_survives_later_donated_updates(rng):
    """Validating runs snapshot the best model mid-descent; later donated
    updates must not invalidate the snapshot's arrays."""
    from photon_ml_tpu.evaluation import EvaluatorType, evaluator_for_type
    from photon_ml_tpu.evaluation.evaluators import EvaluationSuite

    workload = make_workload(rng)
    X, X_re, users, y, _ = workload
    coords = build_coords(workload, use_program=True)
    fe_val = FixedEffectDataset(LabeledData.build(X, y), feature_shard_id="global")
    re_val = build_random_effect_dataset(
        X_re, users, "userId", feature_shard_id="per-user", scoring_only=True
    )
    suite = EvaluationSuite(
        evaluators=[evaluator_for_type(EvaluatorType.AUC)],
        labels=y, offsets=np.zeros(N), weights=np.ones(N),
    )
    result = run_coordinate_descent(
        coords, n_iterations=3,
        validation_datasets={"fixed": fe_val, "per-user": re_val},
        evaluation_suite=suite,
    )
    best = result.best_model.get_model("per-user")
    assert not best.coeffs.is_deleted()
    assert np.isfinite(np.asarray(best.coeffs)).all()


# -------------------------------------------------------------- retrace guard


def test_zero_retraces_across_descent_iterations(rng):
    """Iteration 1 compiles every program; iterations 2..N (and any
    subsequent same-shape descent) must be pure jit-cache hits. A retrace in
    the guarded region raises RetraceError."""
    workload = make_workload(rng)
    per_entity = {0: 2.0}
    norm = workload[-1]
    coords = build_coords(
        workload, use_program=True, normalization=norm, per_entity=per_entity,
        variance=VarianceComputationType.SIMPLE,
    )
    # warmup descent compiles the update program, scoring and guard ops
    run_coordinate_descent(coords, n_iterations=1)
    with no_retrace(what="descent iterations 2..N"):
        result = run_coordinate_descent(coords, n_iterations=3)
    assert np.isfinite(np.asarray(result.model.get_model("per-user").coeffs)).all()


def test_retrace_guard_actually_guards(rng):
    """Sanity: the guard used above does fire on a fresh trace (otherwise the
    zero-retrace assertion would be vacuous)."""
    with pytest.raises(RetraceError):
        with no_retrace(what="seeded"):
            jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(7.0))


# ---------------------------------------------------- device-side reject path


def test_in_program_divergence_rejected_with_incident(rng):
    """A diverging bucket solve (a NaN warm-start row propagates through its
    entity's solve — L-BFGS line search cannot recover a NaN iterate) must:
    keep the previous table BIT-FOR-BIT via the in-program select, keep the
    previous score, and record a divergence incident per rejected update."""
    workload = make_workload(rng)
    X, X_re, users, y, _ = workload
    coords = build_coords(workload, use_program=True)
    re_ds = coords["per-user"].dataset
    healthy, _ = train_random_effect(
        re_ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(N)
    )
    bad = np.asarray(healthy.coeffs).copy()
    bad[2, 0] = np.nan  # one poisoned entity row diverges its whole bucket
    warm = dataclasses.replace(healthy, coeffs=jnp.asarray(bad))
    warm_score = np.asarray(coords["per-user"].score(warm))

    result = run_coordinate_descent(
        coords, n_iterations=2, initial_models={"per-user": warm}
    )

    # every per-user update was rejected: the warm table (NaN row included)
    # and its score survive bit-for-bit
    re_model = result.model.get_model("per-user")
    np.testing.assert_array_equal(np.asarray(re_model.coeffs), bad)
    np.testing.assert_array_equal(
        np.asarray(result.training_scores["per-user"]), warm_score
    )
    re_incidents = [i for i in result.incidents if i.coordinate_id == "per-user"]
    assert len(re_incidents) == 2
    for inc, it in zip(re_incidents, (0, 1)):
        assert inc.kind == "divergence"
        assert inc.iteration == it
        assert "non-finite" in inc.cause
    # the fixed effect sees NaN partial scores, so ITS guard rejects too —
    # with the objective-value cause, like the original blocking guard
    fe_incidents = [i for i in result.incidents if i.coordinate_id == "fixed"]
    assert len(fe_incidents) == 2
    assert all("objective" in i.cause for i in fe_incidents)
    fe = np.asarray(result.model.get_model("fixed").model.coefficients.means)
    assert np.isfinite(fe).all()


def test_hostile_wrapper_still_rejected_in_blocking_mode(rng):
    """defer_guard=False keeps the original per-update blocking guard
    semantics (the bench denominator path)."""
    import sys

    sys.path.insert(0, "tests")
    from test_coordinate_descent import _HostileCoordinate, build_coordinates, glmix_data

    X, X_re, user_ids, y = glmix_data(rng)
    coords, _, _ = build_coordinates(X, X_re, user_ids, y)
    hostile = _HostileCoordinate(coords["fixed"], poison={1: "nan"})
    coords = {"fixed": hostile, "per-user": coords["per-user"]}
    result = run_coordinate_descent(coords, n_iterations=1, defer_guard=False)
    (inc,) = result.incidents
    assert inc.kind == "divergence" and "non-finite" in inc.cause
    fe = np.asarray(result.model.get_model("fixed").model.coefficients.means)
    np.testing.assert_array_equal(fe, np.zeros_like(fe))


# ------------------------------------------------------------- lazy trackers


def test_lazy_random_effect_tracker_matches_eager(rng):
    """The fused path's lazily-materialized tracker reports the same
    convergence stats as the per-bucket path's eager tracker."""
    workload = make_workload(rng)
    c_new = build_coords(workload, use_program=True)["per-user"]
    c_old = build_coords(workload, use_program=False)["per-user"]
    zeros = jnp.zeros(N, dtype=c_new.dataset.sample_vals.dtype)
    _, _, lazy = c_new.update_and_score(None, jnp.zeros(N), zeros)
    _, eager = c_old.update_model(None, jnp.zeros(N))
    assert lazy.guard_ok is not None
    assert lazy.n_entities == eager.n_entities
    assert lazy.convergence_reason_counts == eager.convergence_reason_counts
    assert lazy.iterations_mean == eager.iterations_mean
    assert lazy.iterations_max == eager.iterations_max
    assert "entities=" in lazy.summary()


def test_rejected_update_does_not_leak_diverged_variances(rng):
    """The generic (non-fused) deferred reject must revert VARIANCES too: a
    diverged solve's NaN variances surviving an update the loop reports as
    'rejected; previous model kept' would poison the exported model."""
    import sys

    sys.path.insert(0, "tests")
    from test_coordinate_descent import _HostileCoordinate, glmix_data

    X, X_re, user_ids, y = glmix_data(rng)
    fe_ds = FixedEffectDataset(LabeledData.build(X, y), feature_shard_id="global")
    fe = FixedEffectCoordinate(
        coordinate_id="fixed", dataset=fe_ds,
        task=TaskType.LOGISTIC_REGRESSION, configuration=CFG,
        variance_computation=VarianceComputationType.SIMPLE,
    )
    hostile = _HostileCoordinate(fe, poison={1: "nan", 2: "nan"})
    result = run_coordinate_descent({"fixed": hostile}, n_iterations=2)
    assert len(result.incidents) == 2
    coef = result.model.get_model("fixed").model.coefficients
    np.testing.assert_array_equal(np.asarray(coef.means), np.zeros_like(coef.means))
    # the pre-update model had no variances: "previous model kept" means the
    # field comes back ABSENT, not as a fabricated zero table
    assert coef.variances is None


def test_trackers_materialized_in_results(rng):
    """result.trackers must honor the host-value field contract (str/int/
    float) even in sync-free runs where nothing read them mid-descent."""
    workload = make_workload(rng)
    coords = build_coords(workload, use_program=True)
    result = run_coordinate_descent(coords, n_iterations=1)
    (fe_tracker,) = result.trackers["fixed"]
    assert isinstance(fe_tracker.convergence_reason, str)
    assert isinstance(fe_tracker.iterations, int)
    assert isinstance(fe_tracker.final_value, float)


def test_fused_tracker_without_guard_flag_is_refused(rng):
    """A fused-protocol coordinate whose tracker omits guard_ok would let a
    diverged model through while recording a reject — the loop refuses it."""
    workload = make_workload(rng)
    coords = build_coords(workload, use_program=True)
    inner = coords["per-user"]

    class FlaglessFused:
        coordinate_id = "per-user"
        is_locked = False

        def initialize_model(self):
            return inner.initialize_model()

        def prepare_initial_model(self, model):
            return inner.prepare_initial_model(model)

        def score(self, model):
            return inner.score(model)

        def update_and_score(self, initial_model, partial, prev_score, donate=False):
            model, score, tracker = inner.update_and_score(
                initial_model, partial, prev_score, donate=donate
            )
            tracker.guard_ok = None
            return model, score, tracker

    coords["per-user"] = FlaglessFused()
    with pytest.raises(TypeError, match="guard_ok"):
        run_coordinate_descent(coords, n_iterations=1)


def test_fixed_effect_tracker_materializes_lazily(rng):
    workload = make_workload(rng)
    coords = build_coords(workload, use_program=True)
    model, tracker = coords["fixed"].update_model(None, jnp.zeros(N))
    # device scalars until first read; summary materializes to host values
    summary = tracker.summary()
    assert isinstance(tracker.convergence_reason, str)
    assert isinstance(tracker.iterations, int)
    assert isinstance(tracker.final_value, float)
    assert "reason=" in summary and "value=" in summary


# ------------------------------------------------- aligned_to identity fast path


def test_aligned_to_identity_fast_path_does_no_array_work(rng, monkeypatch):
    """The warm-start case inside coordinate descent (model trained ON this
    dataset) must short-circuit on object identity — no np.asarray /
    np.array_equal over the [E, K] projection tables (a device->host
    transfer in the hot loop on accelerators)."""
    workload = make_workload(rng)
    X, X_re, users, y, _ = workload
    ds = build_random_effect_dataset(
        X_re, users, "userId", feature_shard_id="per-user", labels=y
    )
    model, _ = train_random_effect(ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(N))
    assert model.proj_indices is ds.proj_indices  # precondition of the fast path

    def forbidden(*a, **k):  # pragma: no cover - failure path
        raise AssertionError("aligned_to fast path did array work")

    monkeypatch.setattr(np, "array_equal", forbidden)
    monkeypatch.setattr(np, "asarray", forbidden)
    assert model.aligned_to(ds) is model


def test_aligned_to_slow_path_still_works(rng):
    """Equal-valued but distinct proj arrays still re-align correctly (the
    pre-existing value-equality path)."""
    workload = make_workload(rng)
    X, X_re, users, y, _ = workload
    ds = build_random_effect_dataset(
        X_re, users, "userId", feature_shard_id="per-user", labels=y
    )
    model, _ = train_random_effect(ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(N))
    clone = dataclasses.replace(
        model, proj_indices=jnp.asarray(np.asarray(model.proj_indices).copy())
    )
    assert clone.proj_indices is not ds.proj_indices
    assert clone.aligned_to(ds) is clone


def test_aligned_to_tail_growth_skips_the_per_entity_remap(rng, monkeypatch):
    """Continuous training pins the previous generation's entity order, so a
    grown dataset whose old rows keep their slot layout must re-align via the
    vectorized prefix copy — the O(E*K) per-entity Python remap loop (visible
    as row_for_entity calls) must not run at all."""
    from photon_ml_tpu.models.game import RandomEffectModel

    workload = make_workload(rng)
    X, X_re, users, y, _ = workload
    ds = build_random_effect_dataset(
        X_re, users, "userId", feature_shard_id="per-user", labels=y
    )
    prev, _ = train_random_effect(ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(N))
    prev_coeffs = np.asarray(prev.coeffs).copy()

    n_new = 12
    Xn = rng.normal(size=(n_new, D))
    re_new = np.concatenate([np.ones((n_new, 1)), 2.0 * Xn[:, :2] + 0.5], axis=1)
    new_users = np.repeat([N_USERS, N_USERS + 1], 6)
    grown_ds = build_random_effect_dataset(
        sp.vstack([X_re, sp.csr_matrix(re_new)], format="csr"),
        np.concatenate([users, new_users]),
        "userId",
        feature_shard_id="per-user",
        labels=np.concatenate([y, (Xn @ rng.normal(size=D) > 0).astype(np.float64)]),
        entity_order=prev.entity_ids,
    )

    calls = []
    orig = RandomEffectModel.row_for_entity
    monkeypatch.setattr(
        RandomEffectModel,
        "row_for_entity",
        lambda self, e: (calls.append(e), orig(self, e))[1],
    )
    aligned = prev.aligned_to(grown_ds)
    assert calls == []  # pure tail growth: only the vectorized copy ran
    n_old = len(prev.entity_ids)
    assert aligned.entity_ids[:n_old] == prev.entity_ids
    np.testing.assert_array_equal(np.asarray(aligned.coeffs)[:n_old], prev_coeffs)
    assert (np.asarray(aligned.coeffs)[n_old:] == 0).all()


def test_active_set_without_warm_start_is_refused(rng):
    """An active set over a zero-initialized model would silently export
    coefficient 0 for every inactive entity — the descent must refuse before
    initialize_model() can paper over the missing warm start."""
    workload = make_workload(rng)
    coords = build_coords(workload, use_program=True)
    active = np.zeros(N_USERS, dtype=bool)
    active[0] = True
    with pytest.raises(ValueError, match="active set but no initial model"):
        run_coordinate_descent(
            {"per-user": coords["per-user"]},
            n_iterations=1,
            active_sets={"per-user": active},
        )


# ------------------------------------------------- mesh-sharded update program
#
# PR 10: the SAME donated update program compiles as ONE SPMD module when the
# dataset is mesh-placed — entity-sharded tables and bucket solves,
# sample-sharded scores, donated state keeping its sharding across updates.
# The honest parity contract (the PR 8 lesson: XLA re-vectorizes per LOCAL
# shape, so cross-layout/cross-device-count comparisons are tolerance-only):
# bitwise WITHIN a layout — sharded fused program vs sharded per-bucket loop,
# and run to run — which transitively ties the mesh program to the host
# reference through test_mesh_backend's host-vs-mesh tolerance gates.


def build_mesh_coord(
    workload,
    *,
    use_program=True,
    normalization=None,
    per_entity=None,
    variance=VarianceComputationType.NONE,
    precision=None,
    dtype=jnp.float32,
):
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.parallel.placement import (
        pad_and_shard_vector,
        place_random_effect_dataset,
    )

    X, X_re, users, y, _ = workload
    re_ds = build_random_effect_dataset(
        X_re, users, "userId", feature_shard_id="per-user", labels=y,
        normalization=normalization,
        intercept_index=0 if normalization is not None else None,
        dtype=dtype,
    )
    mesh = make_mesh(8)
    ds_m = place_random_effect_dataset(re_ds, mesh)
    base = pad_and_shard_vector(np.zeros(N), mesh, dtype=ds_m.sample_vals.dtype)
    coord = RandomEffectCoordinate(
        coordinate_id="per-user", dataset=ds_m,
        task=TaskType.LOGISTIC_REGRESSION, configuration=CFG,
        base_offsets=base,
        normalization=normalization,
        variance_computation=variance,
        per_entity_reg_weights=per_entity,
        use_update_program=use_program,
        precision=precision,
    )
    return coord, ds_m, mesh


def test_mesh_update_program_bitwise_parity_vs_per_bucket(
    rng, eight_devices, order_exact
):
    """The sharded single-program update must train the SAME model as the
    sharded per-bucket loop — coefficients, variances and scores over
    multiple iterations, in the featureful configuration (normalization +
    per-entity L2 + SIMPLE variances): bit for bit where the backend is
    order-exact, else by parity_tiers' two bounds."""
    workload = make_workload(rng)
    norm = workload[-1]
    per_entity = {
        int(e): float(v)
        for e, v in enumerate(rng.uniform(0.4, 2.5, size=N_USERS))
    }

    def descend(use_program, n_iterations, dtype):
        coord, _, _ = build_mesh_coord(
            workload, use_program=use_program, normalization=norm,
            per_entity=per_entity, variance=VarianceComputationType.SIMPLE,
            dtype=dtype,
        )
        return descent_state(
            run_coordinate_descent(
                {"per-user": coord}, n_iterations=n_iterations,
                defer_guard=use_program,
            )
        )

    parity_tiers.assert_program_matches_loop(descend, order_exact)


def test_mesh_donated_updates_keep_sharding_and_consume_buffers(rng, eight_devices):
    """Steady-state mesh updates donate the sharded table/score and the
    outputs come back under the SAME shardings — no resharding between
    updates (the with_sharding_constraint contract in solver_cache)."""
    workload = make_workload(rng)
    coord, ds_m, mesh = build_mesh_coord(workload)
    n_pad = int(ds_m.sample_entity_rows.shape[0])
    zeros = jax.device_put(
        jnp.zeros(n_pad, dtype=ds_m.sample_vals.dtype),
        coord.base_offsets.sharding,
    )
    m1, s1, _ = coord.update_and_score(None, zeros, zeros, donate=False)
    assert m1.coeffs.sharding == ds_m.coeffs_sharding
    assert m1.coeffs.shape == (ds_m.coeffs_rows, ds_m.max_k)
    score_sharding = s1.sharding
    m2, s2, _ = coord.update_and_score(
        m1, jnp.zeros(n_pad, dtype=zeros.dtype), s1, donate=True
    )
    if _donation_supported():
        assert m1.coeffs.is_deleted()
        assert s1.is_deleted()
    assert m2.coeffs.sharding == ds_m.coeffs_sharding
    assert s2.sharding == score_sharding
    # table padding rows (mesh divisibility) stay exactly zero
    assert np.all(np.asarray(m2.coeffs)[ds_m.n_entities:] == 0.0)


def test_mesh_external_warm_start_survives_donated_updates(rng, eight_devices):
    """A caller-held host-layout warm-start model fed to a mesh coordinate is
    padded + placed as a COPY: the foreign buffer survives the descent's
    donation bit for bit."""
    workload = make_workload(rng)
    X, X_re, users, y, _ = workload
    host_ds = build_random_effect_dataset(
        X_re, users, "userId", feature_shard_id="per-user", labels=y
    )
    warm, _ = train_random_effect(
        host_ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(N)
    )
    warm_bits = np.asarray(warm.coeffs).copy()
    coord, _, _ = build_mesh_coord(workload)
    result = run_coordinate_descent(
        {"per-user": coord}, n_iterations=3,
        initial_models={"per-user": warm},
    )
    assert not warm.coeffs.is_deleted()
    np.testing.assert_array_equal(np.asarray(warm.coeffs), warm_bits)
    out = result.model.get_model("per-user")
    assert np.isfinite(np.asarray(out.coeffs)).all()


def test_mesh_divergence_reject_keeps_sharded_table_bits(rng, eight_devices):
    """The in-program reject on a mesh: a NaN-poisoned warm table's bits
    (including the sharded padding rows) survive the rejected update, and the
    incident is recorded."""
    workload = make_workload(rng)
    coord, ds_m, _ = build_mesh_coord(workload)
    healthy, _ = train_random_effect(
        ds_m, TaskType.LOGISTIC_REGRESSION, CFG, coord.base_offsets
    )
    bad = np.asarray(healthy.coeffs).copy()
    bad[2, 0] = np.nan
    warm = dataclasses.replace(healthy, coeffs=jnp.asarray(bad))
    warm_score = np.asarray(coord.score(warm))

    result = run_coordinate_descent(
        {"per-user": coord}, n_iterations=2,
        initial_models={"per-user": warm},
    )
    out = result.model.get_model("per-user")
    np.testing.assert_array_equal(np.asarray(out.coeffs), bad)
    np.testing.assert_array_equal(
        np.asarray(result.training_scores["per-user"]), warm_score
    )
    assert out.coeffs.sharding == ds_m.coeffs_sharding
    assert len(result.incidents) == 2
    assert all(i.kind == "divergence" for i in result.incidents)


def test_mesh_update_program_solves_are_data_collective_free(rng, eight_devices):
    """The embarrassingly-parallel contract: the compiled SPMD update
    program's solver while-loops contain ZERO data collectives — the only
    in-loop communication is the scalar convergence-predicate all-reduce a
    globally batched while_loop needs for termination consensus, whose count
    must be NONZERO (a zero would mean the scan no longer sees the solver
    loops at all — the vacuity failure mode). Everything around the loops
    stays within the gather/scatter payload bounds."""
    from photon_ml_tpu.parallel import hlo_guards

    workload = make_workload(rng)
    coord, ds_m, _ = build_mesh_coord(
        workload, normalization=workload[-1],
        variance=VarianceComputationType.SIMPLE,
    )
    hlo = coord.compiled_update_hlo()
    in_loop = hlo_guards.loop_collectives(hlo)
    predicates = hlo_guards.assert_entity_solves_collective_free(hlo)
    assert predicates > 0  # the scan actually reached the solver loops
    assert len(in_loop) == predicates  # every in-loop entry is a predicate
    assert all(elements == 1 for _, _, elements in in_loop)
    hlo_guards.assert_collective_profile(
        hlo,
        grad_elements=ds_m.max_k,
        table_elements=(ds_m.coeffs_rows + 1) * ds_m.max_k,
        n_samples=int(ds_m.sample_entity_rows.shape[0]),
        bucket_block_elements=max(
            b.n_entities * b.shape[0] for b in ds_m.buckets
        ),
        max_collectives=16 * len(ds_m.buckets),
    )


def test_loop_collective_scan_catches_real_in_loop_collective(eight_devices):
    """Sanity for the guard above, against REAL compiled HLO (real while
    bodies take a single TUPLE-typed parameter — a hand-written non-tuple
    fixture once let the scan go vacuous): a carry-dependent reduction over
    the sharded axis compiles a data all-reduce INSIDE the loop and must be
    refused; the same reduction hoisted out of the loop (loop-invariant) is
    legal."""
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec
    from photon_ml_tpu.parallel import hlo_guards
    from photon_ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)
    x = jax.device_put(
        jnp.arange(32.0).reshape(8, 4),
        NamedSharding(mesh, PartitionSpec("data", None)),
    )

    def in_loop(x):
        def body(c):
            i, acc = c
            # carry-dependent reduction over the SHARDED axis: the [4]
            # all-reduce cannot be hoisted and runs per iteration
            return i + 1, acc + jnp.sum(x * acc, axis=0)

        return lax.while_loop(
            lambda c: c[0] < 3, body, (0, jnp.ones(4, dtype=x.dtype))
        )

    hlo = jax.jit(in_loop).lower(x).compile().as_text()
    entries = hlo_guards.loop_collectives(hlo)
    assert any(elements > 1 for _, _, elements in entries)
    with pytest.raises(AssertionError, match="while-loops"):
        hlo_guards.assert_entity_solves_collective_free(hlo)

    def hoisted(x):
        s = jnp.sum(x, axis=0)  # loop-invariant: all-reduce sits outside

        def body(c):
            return c[0] + 1, c[1] + 1.0

        i, acc = lax.while_loop(lambda c: c[0] < 3, body, (0, 0.0))
        return acc + jnp.sum(s)

    hlo2 = jax.jit(hoisted).lower(x).compile().as_text()
    assert all(e == 1 for _, _, e in hlo_guards.loop_collectives(hlo2))
    hlo_guards.assert_entity_solves_collective_free(hlo2)


def test_mesh_active_set_delta_keeps_inactive_shards_bitwise(rng, eight_devices):
    """Active-set delta updates on a mesh-sharded dataset (the PR 7 mesh
    remnant): gathered sub-buckets re-place under the entity sharding, padding
    lanes scatter out of bounds, and every inactive entity's shard content —
    and the table's padding rows — keep the previous generation's bits."""
    workload = make_workload(rng)
    coord, ds_m, _ = build_mesh_coord(workload)
    prev, _ = train_random_effect(
        ds_m, TaskType.LOGISTIC_REGRESSION, CFG, coord.base_offsets
    )
    prev_bits = np.asarray(prev.coeffs).copy()
    active = np.zeros(N_USERS, dtype=bool)
    active[[0, 3, 7]] = True
    result = run_coordinate_descent(
        {"per-user": coord}, n_iterations=1,
        initial_models={"per-user": prev},
        active_sets={"per-user": active},
    )
    out = result.model.get_model("per-user")
    new = np.asarray(out.coeffs)
    # the deferred-guard select may normalize P('data', None) to the
    # equivalent P('data'): compare placements, not spec spellings
    assert out.coeffs.sharding.is_equivalent_to(
        ds_m.coeffs_sharding, out.coeffs.ndim
    )
    stats = coord.last_active_stats
    assert stats.n_active == 3
    # sub-bucket lane counts are mesh multiples (8 devices)
    assert stats.n_solved_lanes % 8 == 0
    inactive = np.array([i for i in range(N_USERS) if not active[i]])
    np.testing.assert_array_equal(new[inactive], prev_bits[inactive])
    np.testing.assert_array_equal(new[N_USERS:], prev_bits[N_USERS:])
    # the foreign warm table survives
    assert not prev.coeffs.is_deleted()


def test_mesh_lazy_tracker_excludes_padding_lanes(rng, eight_devices):
    """Mesh-placed buckets carry padding lanes (entity_rows == E): the fused
    path's lazily-materialized tracker must report the same per-entity stats
    as the per-bucket mesh path, which filters rows < E."""
    workload = make_workload(rng)
    coord, ds_m, _ = build_mesh_coord(workload)
    n_pad = int(ds_m.sample_entity_rows.shape[0])
    zeros = jax.device_put(
        jnp.zeros(n_pad, dtype=ds_m.sample_vals.dtype),
        coord.base_offsets.sharding,
    )
    _, _, lazy = coord.update_and_score(None, zeros, zeros)
    _, eager = train_random_effect(
        ds_m, TaskType.LOGISTIC_REGRESSION, CFG, coord.base_offsets
    )
    # the placed buckets DO carry padding lanes at this shape
    assert any(
        (np.asarray(jax.device_get(b.entity_rows)) >= N_USERS).any()
        for b in ds_m.buckets
    )
    assert lazy.n_entities == eager.n_entities == N_USERS
    assert lazy.convergence_reason_counts == eager.convergence_reason_counts
    assert lazy.iterations_mean == eager.iterations_mean
    assert lazy.iterations_max == eager.iterations_max


def test_mesh_reduced_precision_stores_sharded_tables(rng, eight_devices):
    """Storage precision is orthogonal to placement: a bf16 policy on a
    mesh-sharded dataset stores the donated table at bf16 UNDER the entity
    sharding and still trains finite coefficients."""
    workload = make_workload(rng)
    coord, ds_m, _ = build_mesh_coord(workload, precision="bf16")
    result = run_coordinate_descent({"per-user": coord}, n_iterations=2)
    out = result.model.get_model("per-user")
    assert out.coeffs.dtype == jnp.bfloat16
    assert out.coeffs.sharding == ds_m.coeffs_sharding
    assert np.isfinite(np.asarray(out.coeffs, dtype=np.float32)).all()


def test_mesh_zero_retraces_across_descent_iterations(rng, eight_devices):
    """Sharded steady state: after the warmup descent compiled the SPMD
    programs, further same-shape iterations are pure jit-cache hits."""
    workload = make_workload(rng)
    coord, _, _ = build_mesh_coord(workload)
    run_coordinate_descent({"per-user": coord}, n_iterations=1)
    with no_retrace(what="mesh descent iterations 2..N"):
        result = run_coordinate_descent({"per-user": coord}, n_iterations=3)
    assert np.isfinite(
        np.asarray(result.model.get_model("per-user").coeffs)
    ).all()


def assert_same_array(got, want):
    """Shape, dtype, placement and bits (+0.0 and -0.0 differ)."""
    assert (got.shape, got.dtype) == (want.shape, want.dtype)
    assert got.sharding == want.sharding
    assert got.committed == want.committed
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


@pytest.mark.parametrize("precision", [None, "bf16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("placement", ["one-device", "mesh"])
def test_zero_model_score_is_the_kernels_score_of_the_initial_model(
    rng, eight_devices, placement, precision
):
    """``zero_model_score()`` is ``score(initialize_model())`` in shape,
    dtype, sharding and bits without the kernel, a new array every call, and
    the first ``update_and_score`` after it runs the program it runs after a
    kernel-made score: no second cached program, no second compile."""
    from photon_ml_tpu.optimization.solver_cache import re_coordinate_update_program

    workload = make_workload(rng)
    if placement == "mesh":
        coord, _, _ = build_mesh_coord(workload, precision=precision)
    else:
        coord = build_coords(workload, use_program=True, precision=precision)["per-user"]
    model = coord.initialize_model()
    kernel_made = coord.score(model)
    answered = coord.zero_model_score()
    assert_same_array(answered, kernel_made)
    assert coord.zero_model_score() is not answered
    if placement == "mesh":
        assert answered.sharding == coord._resolve_update_program()[4][1]

    partial = jnp.zeros_like(coord.base_offsets)  # keeps the mesh placement
    m1, s1, _ = coord.update_and_score(model, partial, kernel_made)
    program = coord._resolve_update_program()[0]
    programs = re_coordinate_update_program.cache_info().currsize
    compiled = program._cache_size()
    with no_retrace(what="the first update after zero_model_score"):
        m2, s2, _ = coord.update_and_score(model, partial, coord.zero_model_score())
    assert re_coordinate_update_program.cache_info().currsize == programs
    assert program._cache_size() == compiled  # other tests share the cached program
    assert_same_array(s2, s1)
    assert_same_array(m2.coeffs, m1.coeffs)
    if precision is not None:
        assert m2.coeffs.dtype == jnp.bfloat16 != answered.dtype
    # update_and_score copies a score it does not own: the caller's survives
    assert not kernel_made.is_deleted() and not answered.is_deleted()


def test_per_bucket_fallback_logs_structured_reason_once(rng, caplog):
    """use_update_program=False demotes to the per-bucket loop with ONE
    structured warning per (dataset fingerprint, cause) — never silently,
    never per update (analysis/fallbacks.py)."""
    import logging

    from photon_ml_tpu.analysis.fallbacks import reset_fallback_log

    reset_fallback_log()
    workload = make_workload(rng)
    coord = build_coords(workload, use_program=False)["per-user"]
    zeros = jnp.zeros(N, dtype=coord.dataset.sample_vals.dtype)
    with caplog.at_level(logging.WARNING, logger="photon_ml_tpu.analysis.fallbacks"):
        assert coord.update_and_score(None, zeros, zeros) is None
        assert coord.update_and_score(None, zeros, zeros) is None
    hits = [r for r in caplog.records if "slow path" in r.getMessage()]
    assert len(hits) == 1
    msg = hits[0].getMessage()
    assert "use_update_program=False" in msg and "per-user" in msg


def test_variance_delta_pass_refuses_varianceless_warm_start(rng):
    """With variance computation on, only active entities receive solved
    variances — a warm start that carries none would export variance 0.0
    (infinite confidence) for every inactive entity, so the delta path must
    refuse unless every entity is active."""
    from photon_ml_tpu.algorithm.random_effect import train_random_effect_delta

    workload = make_workload(rng)
    X, X_re, users, y, _ = workload
    ds = build_random_effect_dataset(
        X_re, users, "userId", feature_shard_id="per-user", labels=y
    )
    prev, _ = train_random_effect(ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(N))
    assert prev.variances is None
    partial = np.zeros(ds.n_entities, dtype=bool)
    partial[0] = True
    with pytest.raises(ValueError, match="carries no variances"):
        train_random_effect_delta(
            ds, TaskType.LOGISTIC_REGRESSION, CFG,
            jnp.zeros(N, dtype=ds.sample_vals.dtype),
            prev, partial,
            variance_computation=VarianceComputationType.SIMPLE,
        )
    # the escape hatch named in the error: an all-active pass solves a real
    # variance for every entity, so it is allowed
    model, _, _ = train_random_effect_delta(
        ds, TaskType.LOGISTIC_REGRESSION, CFG,
        jnp.zeros(N, dtype=ds.sample_vals.dtype),
        prev, np.ones(ds.n_entities, dtype=bool),
        variance_computation=VarianceComputationType.SIMPLE,
    )
    assert model.variances is not None
    assert np.isfinite(np.asarray(model.variances)).all()


# ------------------------------- population programs: per-lane active flags


def _population_re_inputs(rng, P=4):
    from photon_ml_tpu.algorithm.random_effect import (
        build_l2_rows,
        precompute_norm_tables,
    )

    X, X_re, users, y, _ = make_workload(rng)
    ds = build_random_effect_dataset(
        X_re, users, "userId", feature_shard_id="per-user", labels=y
    )
    dtype = ds.sample_vals.dtype
    E, K = ds.n_entities, ds.max_k
    l2_rows = jnp.stack(
        [
            jnp.asarray(build_l2_rows(ds, float(p + 1), None, dtype, E))
            for p in range(P)
        ]
    )
    coeffs = jnp.asarray(rng.normal(size=(P, E, K)) * 0.01, dtype)
    score = jnp.asarray(rng.normal(size=(P, N)) * 0.01, dtype)
    offsets = jnp.zeros((P, N), dtype)
    norm_tables = precompute_norm_tables(ds, None, dtype)
    view = (ds.sample_entity_rows, ds.sample_local_cols, ds.sample_vals)
    return ds, dtype, l2_rows, coeffs, score, offsets, norm_tables, view


def test_re_population_with_active_freezes_lanes_bitwise(rng):
    """The early-exit lever at the program level: an inactive lane's bucket
    solves run ZERO iterations and the lane's donated table/score come back
    bit-for-bit (the select is load-bearing — a zero-iteration solve alone
    would round-trip the warm start through dtype/space conversions);
    active lanes train normally and the frozen lane reports no reject."""
    from photon_ml_tpu.optimization.solver_cache import (
        re_population_update_program,
    )

    ds, dtype, l2_rows, coeffs, score, offsets, norm_tables, view = (
        _population_re_inputs(rng)
    )
    # EXPLICIT copies: np.asarray on a CPU jax array may be zero-copy, and
    # the program DONATES these buffers — a view would silently alias the
    # outputs written into the reused buffer
    coeffs_host, score_host = np.array(coeffs), np.array(score)
    program = re_population_update_program(
        TaskType.LOGISTIC_REGRESSION,
        CFG.optimizer_config,
        False,
        VarianceComputationType.NONE,
        ds.n_entities,
        "lbfgs",
        with_active=True,
    )
    active = jnp.asarray([True, False, True, False])
    out_c, out_s, _var, ok, _reasons, iters = program(
        coeffs, score, None, offsets, l2_rows,
        jnp.zeros((4,), dtype), active,
        tuple(ds.buckets), norm_tables, view,
    )
    out_c, out_s, ok = np.asarray(out_c), np.asarray(out_s), np.asarray(ok)
    per_lane_iters = sum(np.asarray(b).sum(axis=-1) for b in iters)
    for p, is_active in enumerate([True, False, True, False]):
        if is_active:
            assert per_lane_iters[p] > 0
            assert not np.array_equal(out_c[p], coeffs_host[p])
        else:
            assert per_lane_iters[p] == 0
            np.testing.assert_array_equal(out_c[p], coeffs_host[p])
            np.testing.assert_array_equal(out_s[p], score_host[p])
        assert bool(ok[p])


def test_re_population_all_active_matches_flagless_program(rng):
    """active=all-true is the semantic identity: the with_active program
    family trains the same tables as the flagless family (same body, the
    masking selects reduce to pass-throughs)."""
    from photon_ml_tpu.optimization.solver_cache import (
        re_population_update_program,
    )

    ds, dtype, l2_rows, coeffs, score, offsets, norm_tables, view = (
        _population_re_inputs(rng)
    )
    args = (offsets, l2_rows, jnp.zeros((4,), dtype))
    flagless = re_population_update_program(
        TaskType.LOGISTIC_REGRESSION, CFG.optimizer_config, False,
        VarianceComputationType.NONE, ds.n_entities, "lbfgs",
    )
    c1, s1, _, ok1, _, _ = flagless(
        jnp.array(coeffs), jnp.array(score), None, *args,
        tuple(ds.buckets), norm_tables, view,
    )
    with_active = re_population_update_program(
        TaskType.LOGISTIC_REGRESSION, CFG.optimizer_config, False,
        VarianceComputationType.NONE, ds.n_entities, "lbfgs",
        with_active=True,
    )
    c2, s2, _, ok2, _, _ = with_active(
        jnp.array(coeffs), jnp.array(score), None, *args,
        jnp.ones((4,), dtype=bool),
        tuple(ds.buckets), norm_tables, view,
    )
    np.testing.assert_allclose(
        np.asarray(c1), np.asarray(c2), rtol=1e-12, atol=1e-12
    )
    np.testing.assert_allclose(
        np.asarray(s1), np.asarray(s2), rtol=1e-12, atol=1e-12
    )
    assert np.asarray(ok1).all() and np.asarray(ok2).all()


def test_fe_population_with_active_freezes_lanes_bitwise(rng):
    from photon_ml_tpu.data.dataset import LabeledData
    from photon_ml_tpu.normalization import NO_NORMALIZATION
    from photon_ml_tpu.optimization.solver_cache import (
        fe_population_update_program,
    )

    X, _, _, y, _ = make_workload(rng)
    data = LabeledData.build(X, y)
    dtype = data.labels.dtype
    P = 4
    coeffs = jnp.asarray(rng.normal(size=(P, D)) * 0.1, dtype)
    score = jnp.asarray(rng.normal(size=(P, N)) * 0.1, dtype)
    coeffs_host, score_host = np.array(coeffs), np.array(score)  # copies: donated buffers
    program = fe_population_update_program(
        TaskType.LOGISTIC_REGRESSION, CFG.optimizer_config, False,
        with_active=True,
    )
    active = jnp.asarray([False, True, False, True])
    out_c, out_s, coefs_ok, value_ok, _values, iters, _r = program(
        coeffs, score, jnp.zeros((P, N), dtype),
        jnp.ones((P,), dtype), jnp.zeros((P,), dtype), jnp.ones((P,), dtype),
        jnp.zeros((0,), jnp.float32), active, data, NO_NORMALIZATION,
    )
    out_c, out_s = np.asarray(out_c), np.asarray(out_s)
    iters = np.asarray(iters)
    for p, is_active in enumerate([False, True, False, True]):
        if is_active:
            assert iters[p] > 0
            assert not np.array_equal(out_c[p], coeffs_host[p])
        else:
            assert iters[p] == 0
            np.testing.assert_array_equal(out_c[p], coeffs_host[p])
            np.testing.assert_array_equal(out_s[p], score_host[p])
        assert bool(np.asarray(coefs_ok)[p]) and bool(np.asarray(value_ok)[p])
