"""Direct (batched Gram/Cholesky Newton) random-effect solves: parity matrix
against the LBFGS reference across all four GLM families x {raw, normalized}
x {uniform, per-entity} L2, solver-selection (auto) semantics, cross-run
determinism, and the divergence guard's rejection of singular / NaN-poisoned
Gram systems (optimization/normal_equations.py + the re_solver threading
through solver_cache / train_random_effect / the update program)."""

import numpy as np
import pytest
import scipy.sparse as sp

import jax.numpy as jnp

from photon_ml_tpu.algorithm.coordinate import RandomEffectCoordinate
from photon_ml_tpu.algorithm.coordinate_descent import run_coordinate_descent
from photon_ml_tpu.algorithm.random_effect import (
    random_effect_gradient_norms,
    train_random_effect,
    train_random_effect_delta,
)
from photon_ml_tpu.data.random_effect import build_random_effect_dataset
from photon_ml_tpu.normalization import FeatureDataStatistics, NormalizationContext
from photon_ml_tpu.optimization import normal_equations
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

ALL_TASKS = [
    TaskType.LINEAR_REGRESSION,
    TaskType.LOGISTIC_REGRESSION,
    TaskType.POISSON_REGRESSION,
    TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
]

N, E, D = 420, 12, 5


def l2_config(weight=1.0, iters=100):
    return GLMOptimizationConfiguration(
        optimizer_config=OptimizerConfig(max_iterations=iters, tolerance=1e-9),
        regularization_context=RegularizationContext(RegularizationType.L2),
        regularization_weight=weight,
    )


def make_problem(seed=0, n=N, n_entities=E, d=D):
    rng = np.random.default_rng(seed)
    ents = rng.integers(0, n_entities, size=n)
    X = np.concatenate([np.ones((n, 1)), rng.normal(size=(n, d - 1))], axis=1)
    z = np.einsum("nd,nd->n", X, rng.normal(size=(n_entities, d))[ents])
    labels = {
        TaskType.LINEAR_REGRESSION: z + 0.1 * rng.normal(size=n),
        TaskType.LOGISTIC_REGRESSION: (
            rng.random(n) < 1.0 / (1.0 + np.exp(-z))
        ).astype(float),
        TaskType.POISSON_REGRESSION: rng.poisson(
            np.exp(np.clip(0.3 * z, -3, 3))
        ).astype(float),
        TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM: (z > 0).astype(float),
    }
    return sp.csr_matrix(X), ents, labels, rng


def standardization(X):
    stats = FeatureDataStatistics.compute(
        np.asarray(X.todense(), dtype=np.float64), intercept_index=0
    )
    return NormalizationContext.build(NormalizationType.STANDARDIZATION, stats)


@pytest.mark.parametrize("task", ALL_TASKS, ids=lambda t: t.name.lower())
@pytest.mark.parametrize("normalized", [False, True], ids=["raw", "norm"])
@pytest.mark.parametrize("per_entity", [False, True], ids=["uniform-l2", "per-entity-l2"])
def test_direct_matches_lbfgs_optimum(task, normalized, per_entity):
    """The full parity matrix: for every family x normalization x L2 shape,
    the direct solve must land (at least) as close to the subproblem optimum
    as the LBFGS reference — measured by the per-entity gradient norms of the
    regularized objective at the trained coefficients — and agree with it to
    solver tolerance in the coefficients."""
    X, ents, labels, rng = make_problem(seed=ALL_TASKS.index(task) * 10 + int(normalized))
    norm = standardization(X) if normalized else None
    pe = (
        {int(e): float(v) for e, v in enumerate(rng.uniform(0.5, 2.0, size=E))}
        if per_entity
        else None
    )
    ds = build_random_effect_dataset(
        X, ents, "e", labels=labels[task],
        normalization=norm, intercept_index=0 if normalized else None,
    )
    off = jnp.zeros(N, dtype=jnp.float32)
    kwargs = dict(normalization=norm, per_entity_reg_weights=pe)
    m_l, _ = train_random_effect(
        ds, task, l2_config(), off, re_solver="lbfgs", **kwargs
    )
    m_d, _ = train_random_effect(
        ds, task, l2_config(), off, re_solver="direct", **kwargs
    )
    gn_kwargs = dict(l2=1.0, per_entity_reg_weights=pe, normalization=norm)
    g_l = random_effect_gradient_norms(ds, m_l, off, task, **gn_kwargs)
    g_d = random_effect_gradient_norms(ds, m_d, off, task, **gn_kwargs)
    # optimum agreement: direct is at least as converged as LBFGS (f32 slack)
    assert g_d.max() <= max(2.0 * g_l.max(), 5e-3), (g_d.max(), g_l.max())
    np.testing.assert_allclose(
        np.asarray(m_d.coeffs), np.asarray(m_l.coeffs), rtol=2e-2, atol=5e-3
    )
    assert np.isfinite(np.asarray(m_d.coeffs)).all()


def test_linear_closed_form_is_exact():
    """Linear regression takes the one-step closed form: the returned
    coefficients satisfy the normal equations to roundoff — gradient norms
    orders of magnitude below the iterative path's tolerance."""
    X, ents, labels, _ = make_problem(seed=7)
    ds = build_random_effect_dataset(X, ents, "e", labels=labels[TaskType.LINEAR_REGRESSION])
    off = jnp.zeros(N, dtype=jnp.float32)
    m_d, tracker = train_random_effect(
        ds, TaskType.LINEAR_REGRESSION, l2_config(), off, re_solver="direct"
    )
    g = random_effect_gradient_norms(ds, m_d, off, TaskType.LINEAR_REGRESSION, l2=1.0)
    assert g.max() < 1e-3
    assert tracker.iterations_mean == 1.0  # one Newton step, by construction


def test_direct_variances_match_lbfgs():
    """compute_variances is shared by both solvers: at (near-)identical
    optima the SIMPLE variances agree to solver tolerance."""
    X, ents, labels, _ = make_problem(seed=3)
    ds = build_random_effect_dataset(X, ents, "e", labels=labels[TaskType.LOGISTIC_REGRESSION])
    off = jnp.zeros(N, dtype=jnp.float32)
    kw = dict(variance_computation=VarianceComputationType.SIMPLE)
    m_l, _ = train_random_effect(
        ds, TaskType.LOGISTIC_REGRESSION, l2_config(), off, re_solver="lbfgs", **kw
    )
    m_d, _ = train_random_effect(
        ds, TaskType.LOGISTIC_REGRESSION, l2_config(), off, re_solver="direct", **kw
    )
    np.testing.assert_allclose(
        np.asarray(m_d.variances), np.asarray(m_l.variances), rtol=1e-2, atol=1e-4
    )


def test_warm_start_collapses_iterations():
    """The roofline claim's mechanism: a warm-started direct pass converges
    in far fewer Newton steps than the cold LBFGS pass takes quasi-Newton
    iterations (7-9 -> 1-2 solves)."""
    X, ents, labels, _ = make_problem(seed=11)
    ds = build_random_effect_dataset(X, ents, "e", labels=labels[TaskType.LOGISTIC_REGRESSION])
    off = jnp.zeros(N, dtype=jnp.float32)
    m_d, t_cold = train_random_effect(
        ds, TaskType.LOGISTIC_REGRESSION, l2_config(), off, re_solver="direct"
    )
    _, t_warm = train_random_effect(
        ds, TaskType.LOGISTIC_REGRESSION, l2_config(), off,
        initial_model=m_d, re_solver="direct",
    )
    assert t_warm.iterations_mean <= 3.0, t_warm.iterations_mean
    assert t_warm.iterations_mean < t_cold.iterations_mean


# ---------------------------------------------------------------- selection


def test_auto_picks_direct_for_small_k():
    """auto == direct bitwise when every bucket's K is under the threshold
    (the solver choice is a pure function of trace-time shape)."""
    X, ents, labels, _ = make_problem(seed=5)
    ds = build_random_effect_dataset(X, ents, "e", labels=labels[TaskType.LOGISTIC_REGRESSION])
    off = jnp.zeros(N, dtype=jnp.float32)
    m_d, _ = train_random_effect(
        ds, TaskType.LOGISTIC_REGRESSION, l2_config(), off, re_solver="direct"
    )
    m_a, _ = train_random_effect(
        ds, TaskType.LOGISTIC_REGRESSION, l2_config(), off, re_solver="auto"
    )
    np.testing.assert_array_equal(np.asarray(m_a.coeffs), np.asarray(m_d.coeffs))


def test_auto_falls_back_to_lbfgs_beyond_k_threshold():
    """A bucket wider than DIRECT_AUTO_K_MAX keeps the configured optimizer
    under auto (bitwise-equal to the lbfgs path), while explicit 'direct'
    still forces the normal equations."""
    rng = np.random.default_rng(17)
    n, d = 300, normal_equations.DIRECT_AUTO_K_MAX + 8
    ents = rng.integers(0, 4, size=n)
    X = sp.csr_matrix(rng.normal(size=(n, d)))
    y = (rng.random(n) > 0.5).astype(float)
    ds = build_random_effect_dataset(X, ents, "e", labels=y)
    assert ds.max_k > normal_equations.DIRECT_AUTO_K_MAX
    off = jnp.zeros(n, dtype=jnp.float32)
    cfg = l2_config(iters=30)
    m_l, _ = train_random_effect(ds, TaskType.LOGISTIC_REGRESSION, cfg, off, re_solver="lbfgs")
    m_a, _ = train_random_effect(ds, TaskType.LOGISTIC_REGRESSION, cfg, off, re_solver="auto")
    m_d, _ = train_random_effect(ds, TaskType.LOGISTIC_REGRESSION, cfg, off, re_solver="direct")
    np.testing.assert_array_equal(np.asarray(m_a.coeffs), np.asarray(m_l.coeffs))
    assert not np.array_equal(np.asarray(m_d.coeffs), np.asarray(m_l.coeffs))


def test_auto_with_l1_falls_back_and_direct_rejects():
    X, ents, labels, _ = make_problem(seed=2)
    y = labels[TaskType.LOGISTIC_REGRESSION]
    ds = build_random_effect_dataset(X, ents, "e", labels=y)
    off = jnp.zeros(N, dtype=jnp.float32)
    l1_cfg = GLMOptimizationConfiguration(
        optimizer_config=OptimizerConfig(
            optimizer_type="OWLQN", max_iterations=40
        ),
        regularization_context=RegularizationContext(RegularizationType.L1),
        regularization_weight=0.1,
    )
    m_l, _ = train_random_effect(ds, TaskType.LOGISTIC_REGRESSION, l1_cfg, off, re_solver="lbfgs")
    m_a, _ = train_random_effect(ds, TaskType.LOGISTIC_REGRESSION, l1_cfg, off, re_solver="auto")
    np.testing.assert_array_equal(np.asarray(m_a.coeffs), np.asarray(m_l.coeffs))
    with pytest.raises(ValueError, match="L1"):
        train_random_effect(ds, TaskType.LOGISTIC_REGRESSION, l1_cfg, off, re_solver="direct")


def test_unknown_solver_rejected():
    with pytest.raises(ValueError, match="unknown re_solver"):
        normal_equations.validate_re_solver("cholesky", False)


# ------------------------------------------------------------- determinism


def test_direct_f32_cross_run_bitwise_determinism():
    """The f32 direct path's exactness contract includes determinism: two
    fresh runs over identical inputs produce identical bytes (the bench's
    cross-run gate, in-process form)."""
    for task in (TaskType.LINEAR_REGRESSION, TaskType.POISSON_REGRESSION):
        X, ents, labels, _ = make_problem(seed=23)
        off = jnp.zeros(N, dtype=jnp.float32)
        runs = []
        for _ in range(2):
            ds = build_random_effect_dataset(X, ents, "e", labels=labels[task])
            m, _ = train_random_effect(ds, task, l2_config(), off, re_solver="direct")
            runs.append(np.asarray(m.coeffs))
        np.testing.assert_array_equal(runs[0], runs[1])


# ------------------------------------------------------- divergence guard


def _single_entity_coordinate(
    row, y, l2_weight, re_solver="direct", n_extra=6,
    task=TaskType.LINEAR_REGRESSION,
):
    """A coordinate whose FIRST entity has exactly one sample ``row`` (its
    Gram matrix is rank-1) plus well-posed filler entities, so the guard's
    coordinate-level reject semantics are observable."""
    rng = np.random.default_rng(0)
    k = len(row)
    rows = [row] + [rng.normal(size=k) for _ in range(n_extra * 3)]
    ents = np.asarray([0] + [1 + (i % n_extra) for i in range(n_extra * 3)])
    ys = np.asarray([y] + list((rng.random(n_extra * 3) > 0.5).astype(float)))
    X = sp.csr_matrix(np.asarray(rows))
    ds = build_random_effect_dataset(X, ents, "e", labels=ys)
    cfg = GLMOptimizationConfiguration(
        optimizer_config=OptimizerConfig(max_iterations=50),
        regularization_context=(
            RegularizationContext(RegularizationType.L2)
            if l2_weight
            else RegularizationContext()
        ),
        regularization_weight=l2_weight,
    )
    return {
        "re": RandomEffectCoordinate(
            coordinate_id="re",
            dataset=ds,
            task=task,
            configuration=cfg,
            base_offsets=jnp.zeros(len(ys), dtype=jnp.float32),
            re_solver=re_solver,
        )
    }


def test_singular_gram_rejected_by_divergence_guard():
    """An exactly singular Gram matrix (one sample [1, 2], two columns, no
    L2 — all values powers of two, so the rank deficiency survives f32
    arithmetic exactly) must produce a non-finite closed-form solve that the
    coordinate-level guard REJECTS: previous model kept, incident recorded —
    never a silently-damped 'solution' to a different problem."""
    coords = _single_entity_coordinate(np.array([1.0, 2.0]), 1.0, l2_weight=0.0)
    result = run_coordinate_descent(coords, n_iterations=1)
    assert any(i.kind == "divergence" for i in result.incidents), result.incidents
    coeffs = np.asarray(result.model.get_model("re").coeffs)
    # reject keeps the zero-initialized previous table bit for bit
    np.testing.assert_array_equal(coeffs, np.zeros_like(coeffs))


def test_l2_damping_makes_singular_gram_solvable():
    """The SAME rank-1 system with L2 > 0 is well-posed ('L2-damped'): the
    direct solve succeeds and no divergence incident is recorded."""
    coords = _single_entity_coordinate(np.array([1.0, 2.0]), 1.0, l2_weight=1.0)
    result = run_coordinate_descent(coords, n_iterations=1)
    assert not result.incidents
    assert np.isfinite(np.asarray(result.model.get_model("re").coeffs)).all()


def test_singular_gram_rejected_for_irls_families():
    """The Newton/IRLS loop poisons a lane whose direction solve comes back
    non-finite (singular logistic Hessian, one [1, 2] sample, l2=0): the
    guard rejects instead of a silent warm-start freeze."""
    coords = _single_entity_coordinate(
        np.array([1.0, 2.0]), 1.0, l2_weight=0.0,
        task=TaskType.LOGISTIC_REGRESSION,
    )
    result = run_coordinate_descent(coords, n_iterations=1)
    # the factorization of c*[[1,2],[2,4]] yields a non-finite direction on
    # this exact system; if rounding ever turns it into a finite-but-huge
    # direction the monotone revert freezes the lane instead (the documented
    # near-singular boundary) — either way no garbage coefficients escape
    coeffs = np.asarray(result.model.get_model("re").coeffs)
    rejected = any(i.kind == "divergence" for i in result.incidents)
    assert rejected or np.array_equal(coeffs, np.zeros_like(coeffs))
    assert np.isfinite(coeffs).all()


def test_nan_poisoned_gram_rejected():
    """A NaN feature value poisons the Gram assembly; the guard rejects the
    update for the non-quadratic (IRLS) families too."""
    coords = _single_entity_coordinate(
        np.array([np.nan, 1.0]), 1.0, l2_weight=1.0,
        task=TaskType.LOGISTIC_REGRESSION,
    )
    result = run_coordinate_descent(coords, n_iterations=1)
    assert any(i.kind == "divergence" for i in result.incidents)


# --------------------------------------------------------------- delta path


def test_continuous_trainer_threads_re_solver():
    """ContinuousTrainerConfig.re_solver reaches the internal estimator (and
    therefore both the bootstrap train and the delta sub-bucket solves)."""
    from photon_ml_tpu.continuous.trainer import (
        ContinuousTrainer,
        ContinuousTrainerConfig,
    )
    from photon_ml_tpu.estimators.config import (
        CoordinateConfiguration,
        RandomEffectDataConfiguration,
    )

    cfg = ContinuousTrainerConfig(
        corpus_paths=[],
        checkpoint_directory="/tmp/does-not-exist-re-solver-probe",
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations={
            "re": CoordinateConfiguration(
                data_config=RandomEffectDataConfiguration(
                    random_effect_type="e", feature_shard_id="s"
                ),
                optimization_config=l2_config(),
            )
        },
        shard_configurations={},
        re_solver="direct",
    )
    trainer = ContinuousTrainer(cfg)
    assert trainer.estimator.re_solver == "direct"


def test_active_set_delta_inherits_direct_solver():
    """The continuous-training delta path runs the same solver body: an
    all-active direct delta equals the full direct solve bitwise, and a
    partial active set keeps inactive entities' previous bytes."""
    X, ents, labels, _ = make_problem(seed=31)
    y = labels[TaskType.LOGISTIC_REGRESSION]
    off = jnp.zeros(N, dtype=jnp.float32)
    ds = build_random_effect_dataset(X, ents, "e", labels=y)
    warm, _ = train_random_effect(
        ds, TaskType.LOGISTIC_REGRESSION, l2_config(weight=4.0), off,
        re_solver="direct",
    )
    # SAME warm start both sides: the delta path's bitwise contract is
    # per-lane solver-body identity, and the solve is warm-start-dependent
    full, _ = train_random_effect(
        ds, TaskType.LOGISTIC_REGRESSION, l2_config(), off,
        initial_model=warm, re_solver="direct",
    )
    all_active, _, _ = train_random_effect_delta(
        ds, TaskType.LOGISTIC_REGRESSION, l2_config(), off, warm,
        np.ones(E, dtype=bool), re_solver="direct",
    )
    np.testing.assert_array_equal(
        np.asarray(all_active.coeffs), np.asarray(full.coeffs)
    )
    mask = np.zeros(E, dtype=bool)
    mask[:3] = True
    partial, _, _ = train_random_effect_delta(
        ds, TaskType.LOGISTIC_REGRESSION, l2_config(), off, warm, mask,
        re_solver="direct",
    )
    np.testing.assert_array_equal(
        np.asarray(partial.coeffs)[~mask], np.asarray(warm.coeffs)[~mask]
    )


# ----------------------------------------------------------- measured auto


def _auto_coordinate(re_solver="auto", seed=5, **kw):
    X, ents, labels, _ = make_problem(seed=seed)
    ds = build_random_effect_dataset(
        X, ents, "e", labels=labels[TaskType.LOGISTIC_REGRESSION]
    )
    return RandomEffectCoordinate(
        coordinate_id="re",
        dataset=ds,
        task=TaskType.LOGISTIC_REGRESSION,
        configuration=l2_config(),
        base_offsets=jnp.zeros(N, dtype=ds.sample_vals.dtype),
        re_solver=re_solver,
        **kw,
    )


def _one_update(coord):
    model = coord.initialize_model()
    score = coord.score(model)
    zeros = jnp.zeros(coord.dataset.n_samples, dtype=coord.dataset.sample_vals.dtype)
    return coord.update_and_score(model, zeros, score)


def test_measured_auto_records_per_bucket_iteration_counts():
    """re_solver='auto' on the coordinate MEASURES: the first update probes
    both solvers per bucket shape and records each one's iteration count;
    the recorded choice follows the measurement (fewer direct iterations
    with clean convergence -> direct), not a static K threshold."""
    coord = _auto_coordinate()
    assert coord.re_solver_stats() is None  # nothing measured yet
    _one_update(coord)
    stats = coord.re_solver_stats()
    assert stats and stats["per_shape"], stats
    for shape, rec in stats["per_shape"].items():
        assert set(rec) == {"choice", "lbfgs_iters", "direct_iters", "direct_clean"}
        expect = (
            "direct"
            if rec["direct_clean"] and rec["direct_iters"] <= rec["lbfgs_iters"]
            else "lbfgs"
        )
        assert rec["choice"] == expect, (shape, rec)


def test_measured_auto_seeded_decision_is_honored_bitwise():
    """A seeded decision REPLACES measurement: force-seeding an all-lbfgs
    record makes the auto coordinate bitwise-identical to an explicit
    lbfgs coordinate — proof a restored run replays recorded choices
    instead of re-probing (a re-probe against warm tables could flip)."""
    probe = _auto_coordinate()
    _one_update(probe)
    stats = probe.re_solver_stats()
    assert any(r["choice"] == "direct" for r in stats["per_shape"].values())
    forced = {
        "per_shape": {k: dict(v, choice="lbfgs") for k, v in stats["per_shape"].items()}
    }
    seeded = _auto_coordinate()
    seeded.seed_solver_decision(forced)
    m_seeded, s_seeded, _ = _one_update(seeded)
    ref = _auto_coordinate(re_solver="lbfgs")
    m_ref, s_ref, _ = _one_update(ref)
    np.testing.assert_array_equal(np.asarray(m_seeded.coeffs), np.asarray(m_ref.coeffs))
    np.testing.assert_array_equal(np.asarray(s_seeded), np.asarray(s_ref))


def test_measured_auto_decision_roundtrips_checkpoint_extra_state():
    """The measured record rides the checkpoint manifest's fingerprint-
    ADJACENT extra_state and a resumed descent seeds its coordinates from
    it. The resumed run honors the STORED record even when it disagrees
    with what a fresh probe would measure (the stored extra is rewritten
    to all-lbfgs between the runs)."""
    import glob
    import json
    import os
    import tempfile

    from photon_ml_tpu.io.checkpoint import CoordinateDescentCheckpointer

    ckdir = os.path.join(tempfile.mkdtemp(), "ck")
    cp = CoordinateDescentCheckpointer(ckdir, interval=1, fingerprint="fp")
    run_coordinate_descent({"re": _auto_coordinate()}, n_iterations=1, checkpointer=cp)
    manifests = sorted(glob.glob(os.path.join(ckdir, "gen-*", "state.json")))
    assert manifests
    state = json.loads(open(manifests[-1]).read())
    rec = state["extra"]["re_solver_auto"]["re"]
    assert rec["per_shape"]
    # rewrite the stored decision (and its integrity sidecar) to all-lbfgs
    import hashlib

    state["extra"]["re_solver_auto"]["re"] = {
        "per_shape": {k: dict(v, choice="lbfgs") for k, v in rec["per_shape"].items()}
    }
    blob = json.dumps(state, indent=2, sort_keys=True)
    with open(manifests[-1], "w") as f:
        f.write(blob)
    with open(manifests[-1] + ".sha256", "w") as f:
        f.write(hashlib.sha256(blob.encode()).hexdigest())
    resumed = _auto_coordinate()
    cp2 = CoordinateDescentCheckpointer(ckdir, interval=1, fingerprint="fp")
    run_coordinate_descent({"re": resumed}, n_iterations=2, checkpointer=cp2)
    stats = resumed.re_solver_stats()
    assert all(r["choice"] == "lbfgs" for r in stats["per_shape"].values()), stats


def test_measured_auto_l1_measures_nothing_and_stays_lbfgs():
    """L1 configurations have nothing to measure (the normal equations
    cannot express the subgradient): the record is empty and every bucket
    resolves to the configured optimizer, bitwise."""
    X, ents, labels, _ = make_problem(seed=2)
    y = labels[TaskType.LOGISTIC_REGRESSION]
    l1_cfg = GLMOptimizationConfiguration(
        optimizer_config=OptimizerConfig(optimizer_type="OWLQN", max_iterations=40),
        regularization_context=RegularizationContext(RegularizationType.L1),
        regularization_weight=0.1,
    )

    def build(solver):
        ds = build_random_effect_dataset(X, ents, "e", labels=y)
        return RandomEffectCoordinate(
            coordinate_id="re",
            dataset=ds,
            task=TaskType.LOGISTIC_REGRESSION,
            configuration=l1_cfg,
            base_offsets=jnp.zeros(N, dtype=ds.sample_vals.dtype),
            re_solver=solver,
        )

    auto = build("auto")
    m_a, s_a, _ = _one_update(auto)
    assert auto.re_solver_stats() == {"per_shape": {}}
    m_l, s_l, _ = _one_update(build("lbfgs"))
    np.testing.assert_array_equal(np.asarray(m_a.coeffs), np.asarray(m_l.coeffs))


def test_bucket_solver_plan_validates_length():
    from photon_ml_tpu.algorithm.random_effect import _bucket_solver_plan

    assert _bucket_solver_plan("lbfgs", 3) == ("lbfgs",) * 3
    assert _bucket_solver_plan(("direct", "lbfgs"), 2) == ("direct", "lbfgs")
    with pytest.raises(ValueError, match="covers 2 buckets"):
        _bucket_solver_plan(("direct", "lbfgs"), 3)


def test_measured_auto_per_bucket_plan_reaches_update_program():
    """A mixed per-bucket tuple plan is honored by the fused update
    program: pinning each bucket to its measured choice reproduces the
    auto coordinate's update bitwise."""
    coord = _auto_coordinate()
    m_auto, s_auto, _ = _one_update(coord)
    plan = coord._solver_plan()
    assert isinstance(plan, tuple) and set(plan) <= {"lbfgs", "direct"}
    pinned = _auto_coordinate(re_solver="lbfgs")  # placeholder, plan seeded below
    pinned.seed_solver_decision(coord.re_solver_stats())
    pinned.re_solver = "auto"
    m_pin, s_pin, _ = _one_update(pinned)
    np.testing.assert_array_equal(np.asarray(m_pin.coeffs), np.asarray(m_auto.coeffs))
    np.testing.assert_array_equal(np.asarray(s_pin), np.asarray(s_auto))
