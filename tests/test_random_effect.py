"""Random-effect engine tests: bucketed vmap solves vs independent per-entity fits,
reservoir cap determinism, lower-bound filtering, Pearson selection, scoring view,
warm start, normalization invariance. Mirrors RandomEffectDataset/Coordinate integ
tests in the reference (photon-api src/integTest algorithm/, data/).
"""

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.algorithm.random_effect import train_random_effect
from photon_ml_tpu.data.dataset import LabeledData
from photon_ml_tpu.data.random_effect import build_random_effect_dataset
from photon_ml_tpu.function.objective import GLMObjective, make_value_and_grad
from photon_ml_tpu.function.losses import logistic_loss
from photon_ml_tpu.normalization import FeatureDataStatistics, NormalizationContext
from photon_ml_tpu.optimization import minimize_lbfgs
from photon_ml_tpu.optimization.common import OptimizerConfig
from photon_ml_tpu.optimization.config import (
    GLMOptimizationConfiguration,
    RegularizationContext,
)
from photon_ml_tpu.types import (
    NormalizationType,
    OptimizerType,
    RegularizationType,
    TaskType,
    VarianceComputationType,
)


def make_re_data(rng, n_entities=12, d=10, min_s=3, max_s=40):
    """Per-entity logistic data with entity-specific true coefficients.

    Entity sizes are a DETERMINISTIC spread over [min_s, max_s) (values stay
    rng-driven): tests with the same (n_entities, d, min_s, max_s) then produce
    identical bucket shapes, so the vmapped solvers compile once per shape for
    the whole suite instead of once per test."""
    sizes = np.linspace(min_s, max(min_s, max_s - 1), n_entities).astype(int)
    rows = []
    ents = []
    labels = []
    true_w = {}
    for e in range(n_entities):
        w = rng.normal(size=d) * 0.8
        true_w[f"e{e}"] = w
        s = int(sizes[e])
        for _ in range(s):
            x = rng.normal(size=d) * (rng.uniform(size=d) < 0.5)
            x[0] = 1.0  # intercept-ish column, always observed
            z = x @ w + 0.3 * rng.normal()
            rows.append(x)
            ents.append(f"e{e}")
            labels.append(float(z > 0))
    X = sp.csr_matrix(np.asarray(rows))
    return X, np.asarray(ents, dtype=object), np.asarray(labels), true_w


CFG = GLMOptimizationConfiguration(
    optimizer_config=OptimizerConfig(max_iterations=100, tolerance=1e-10),
    regularization_context=RegularizationContext(RegularizationType.L2),
    regularization_weight=0.5,
)


def test_bucketed_solve_matches_independent(rng):
    # 8 entities: enough for >= 2 bucket shape classes, and the per-entity
    # reference solves (one compile, shared padded shape) stay cheap
    X, ents, labels, _ = make_re_data(rng, n_entities=8, max_s=32)
    ds = build_random_effect_dataset(
        X, ents, "entity", labels=labels, dtype=jnp.float64
    )
    assert len(ds.buckets) >= 2  # shape diversity actually exercises bucketing
    model, tracker = train_random_effect(
        ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0])
    )
    assert tracker.n_entities == ds.n_entities

    # Reference solves all share ONE compiled shape: full feature width (unseen
    # columns are all-zero for the entity, so L2 pins their coefficients at 0
    # without changing the others) and zero-weight row padding to a fixed S.
    obj = GLMObjective(logistic_loss)
    S = int(max(np.sum(ents == e) for e in ds.entity_ids))
    d = X.shape[1]
    for e_id in ds.entity_ids:
        mask = ents == e_id
        s = int(mask.sum())
        Xe = np.zeros((S, d))
        Xe[:s] = np.asarray(X[mask].todense())
        ye = np.zeros(S)
        ye[:s] = labels[mask]
        we = np.zeros(S)
        we[:s] = 1.0
        data = LabeledData.build(Xe, ye, weights=we)
        vg = make_value_and_grad(obj, data, l2_weight=0.5)
        ref = minimize_lbfgs(vg, jnp.zeros(d, dtype=jnp.float64), tolerance=1e-10, max_iterations=100)
        row = ds.entity_ids.index(e_id)
        cols = np.asarray(ds.proj_indices[row])
        cols = cols[cols >= 0]
        got = model.coefficients_for_entity(e_id)[: len(cols)]
        np.testing.assert_allclose(
            got, np.asarray(ref.coefficients)[cols], atol=5e-5, err_msg=str(e_id)
        )


def test_scoring_view_matches_manual(rng):
    X, ents, labels, _ = make_re_data(rng, n_entities=6)
    ds = build_random_effect_dataset(X, ents, "entity", labels=labels, dtype=jnp.float64)
    model, _ = train_random_effect(ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0]))
    scores = np.asarray(model.score_dataset(ds))
    for i in range(X.shape[0]):
        e_id = ents[i]
        w_full = np.zeros(X.shape[1])
        row = ds.entity_ids.index(e_id)
        cols = np.asarray(ds.proj_indices[row])
        w_proj = np.asarray(model.coeffs[row])
        for k, c in enumerate(cols):
            if c >= 0:
                w_full[c] = w_proj[k]
        expect = X[i].toarray().ravel() @ w_full
        assert scores[i] == pytest.approx(expect, abs=1e-9), i


def test_reservoir_cap_and_determinism(rng):
    X, ents, labels, _ = make_re_data(rng, n_entities=5, min_s=30, max_s=60)
    ds1 = build_random_effect_dataset(
        X, ents, "entity", labels=labels, active_data_upper_bound=10, seed=7, dtype=jnp.float64
    )
    ds2 = build_random_effect_dataset(
        X, ents, "entity", labels=labels, active_data_upper_bound=10, seed=7, dtype=jnp.float64
    )
    assert ds1.n_passive_samples > 0
    assert ds1.n_active_samples == 5 * 10
    for b1, b2 in zip(ds1.buckets, ds2.buckets):
        np.testing.assert_array_equal(np.asarray(b1.sample_ids), np.asarray(b2.sample_ids))
        # weight rescale: kept samples weighted n_e / cap
        w = np.asarray(b1.weights)
        assert np.all(w[np.asarray(b1.sample_ids) >= 0] > 1.0)
    # different seed -> different reservoir
    ds3 = build_random_effect_dataset(
        X, ents, "entity", labels=labels, active_data_upper_bound=10, seed=8, dtype=jnp.float64
    )
    same = all(
        np.array_equal(np.asarray(a.sample_ids), np.asarray(b.sample_ids))
        for a, b in zip(ds1.buckets, ds3.buckets)
    )
    assert not same


def test_lower_bound_filters_entities(rng):
    X, ents, labels, _ = make_re_data(rng, n_entities=8, min_s=2, max_s=20)
    ds = build_random_effect_dataset(
        X, ents, "entity", labels=labels, active_data_lower_bound=10, dtype=jnp.float64
    )
    counts = {e: int((ents == e).sum()) for e in set(ents)}
    expect_kept = sorted(e for e, c in counts.items() if c >= 10)
    assert list(ds.entity_ids) == expect_kept
    # samples of dropped entities score 0
    model, _ = train_random_effect(ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0]))
    scores = np.asarray(model.score_dataset(ds))
    dropped_mask = ~np.isin(ents, expect_kept)
    assert dropped_mask.any()
    np.testing.assert_array_equal(scores[dropped_mask], 0.0)


def test_pearson_feature_selection(rng):
    # one informative feature (col 1), several noise features
    n_per, d = 60, 6
    rows, ents, ys = [], [], []
    for e in range(3):
        for _ in range(n_per):
            x = np.zeros(d)
            x[0] = 1.0
            x[1] = rng.normal()
            x[2:] = rng.normal(size=d - 2) * 0.01
            y = float(x[1] > 0)
            rows.append(x)
            ents.append(f"e{e}")
            ys.append(y)
    X = sp.csr_matrix(np.asarray(rows))
    ds = build_random_effect_dataset(
        X, np.asarray(ents, dtype=object), "entity",
        labels=np.asarray(ys), features_max=2, intercept_index=0, dtype=jnp.float64,
    )
    for i in range(ds.n_entities):
        cols = set(int(c) for c in np.asarray(ds.proj_indices[i]) if c >= 0)
        assert 1 in cols, "informative feature must survive selection"
        assert 0 in cols, "intercept must always survive"
        assert len(cols) <= 3


def test_warm_start_mapping(rng):
    X, ents, labels, _ = make_re_data(rng, n_entities=5)
    ds = build_random_effect_dataset(X, ents, "entity", labels=labels, dtype=jnp.float64)
    model1, _ = train_random_effect(ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0]))
    # warm start from the converged model: should converge almost immediately
    model2, tracker2 = train_random_effect(
        ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0]), initial_model=model1
    )
    assert tracker2.iterations_mean <= 3.0
    np.testing.assert_allclose(
        np.asarray(model2.coeffs), np.asarray(model1.coeffs), atol=1e-4
    )


def test_normalization_invariance(rng):
    """Training in normalized space and converting back == training raw (well-
    conditioned problem, margin invariance of the normalization algebra)."""
    X, ents, labels, _ = make_re_data(rng, n_entities=4, min_s=25, max_s=40)
    stats = FeatureDataStatistics.compute(np.asarray(X.todense()), intercept_index=0)
    norm = NormalizationContext.build(NormalizationType.STANDARDIZATION, stats)
    ds = build_random_effect_dataset(X, ents, "entity", labels=labels, dtype=jnp.float64)
    ds_norm = build_random_effect_dataset(
        X, ents, "entity", labels=labels, normalization=norm,
        intercept_index=0, dtype=jnp.float64,
    )
    m_raw, _ = train_random_effect(ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0]))
    m_norm, _ = train_random_effect(
        ds_norm, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0]), normalization=norm
    )
    # scores agree in the original space (the models themselves differ because L2
    # acts in different spaces — same as the reference; compare predictions loosely)
    s_raw = np.asarray(m_raw.score_dataset(ds))
    s_norm = np.asarray(m_norm.score_dataset(ds_norm))
    corr = np.corrcoef(s_raw, s_norm)[0, 1]
    assert corr > 0.98, corr


def test_variances_simple(rng):
    X, ents, labels, _ = make_re_data(rng, n_entities=3, min_s=20, max_s=30)
    ds = build_random_effect_dataset(X, ents, "entity", labels=labels, dtype=jnp.float64)
    model, _ = train_random_effect(
        ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0]),
        variance_computation=VarianceComputationType.SIMPLE,
    )
    assert model.variances is not None
    row = 0
    cols = np.asarray(ds.proj_indices[row])
    v = np.asarray(model.variances[row])[cols >= 0]
    assert (v > 0).all() and np.isfinite(v).all()


# ------------------------------------------------- regression: review findings


def test_save_load_score_alignment(rng, tmp_path):
    """Loaded models (slot order = surviving means) must score identically, even
    with sparsity pruning shifting slots."""
    from photon_ml_tpu.io import load_game_model, save_game_model
    from photon_ml_tpu.data.index_map import IndexMap
    from photon_ml_tpu.models.game import GameModel

    X, ents, labels, _ = make_re_data(rng, n_entities=5)
    ds = build_random_effect_dataset(X, ents, "entity", labels=labels, dtype=jnp.float64)
    model, _ = train_random_effect(ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0]))
    s_orig = np.asarray(model.score_dataset(ds))

    imap = IndexMap([f"{j}\x01" for j in range(X.shape[1])])
    gm = GameModel(models={"per-entity": model})
    out = str(tmp_path / "game")
    save_game_model(out, gm, {"per-entity": imap}, sparsity_threshold=0.05)
    loaded = load_game_model(out, {"per-entity": imap}, dtype=jnp.float64)
    lm = loaded.get_model("per-entity")
    s_loaded = np.asarray(lm.score_dataset(ds))
    # pruned coefficients (<0.05) may perturb scores slightly; alignment bugs would
    # produce garbage, so assert tight agreement
    np.testing.assert_allclose(s_loaded, s_orig, atol=0.2)
    corr = np.corrcoef(s_loaded, s_orig)[0, 1]
    assert corr > 0.999


def test_per_sample_weights_respected(rng):
    X, ents, labels, _ = make_re_data(rng, n_entities=3, min_s=20, max_s=30)
    w = rng.uniform(0.5, 2.0, size=X.shape[0])
    ds_w = build_random_effect_dataset(X, ents, "entity", labels=labels, weights=w, dtype=jnp.float64)
    ds_u = build_random_effect_dataset(X, ents, "entity", labels=labels, dtype=jnp.float64)
    m_w, _ = train_random_effect(ds_w, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0]))
    m_u, _ = train_random_effect(ds_u, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0]))
    assert not np.allclose(np.asarray(m_w.coeffs), np.asarray(m_u.coeffs))
    # weighted fit must match an independent weighted solve for one entity
    e_id = ds_w.entity_ids[0]
    mask = ents == e_id
    cols = np.asarray(ds_w.proj_indices[0]); cols = cols[cols >= 0]
    Xe = np.asarray(X[mask][:, cols].todense())
    data = LabeledData.build(Xe, labels[mask], weights=w[mask])
    vg = make_value_and_grad(GLMObjective(logistic_loss), data, l2_weight=0.5)
    ref = minimize_lbfgs(vg, jnp.zeros(len(cols), dtype=jnp.float64), tolerance=1e-10, max_iterations=100)
    np.testing.assert_allclose(
        np.asarray(m_w.coeffs[0])[: len(cols)], ref.coefficients, atol=5e-5
    )


def test_truncated_avro_raises(rng, tmp_path):
    from photon_ml_tpu.data import avro_io

    recs = [{"name": f"n{i}", "term": "", "value": float(i)} for i in range(100)]
    p = str(tmp_path / "x.avro")
    avro_io.write_container(p, avro_io.NAME_TERM_VALUE_SCHEMA, recs)
    blob = open(p, "rb").read()
    open(p, "wb").write(blob[: len(blob) - 25])
    with pytest.raises((EOFError, ValueError, Exception)):
        list(avro_io.read_container(p))


def test_per_entity_reg_weights(rng):
    """Per-entity L2 overrides (the reference only envisioned these,
    RandomEffectOptimizationProblem.scala:34-37): a heavily regularized entity
    shrinks toward zero while the others match the uniform-weight solve."""
    X, ents, labels, _ = make_re_data(rng, n_entities=4, min_s=25, max_s=40)
    ds = build_random_effect_dataset(X, ents, "entity", labels=labels, dtype=jnp.float64)
    base, _ = train_random_effect(
        ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0])
    )
    heavy_id = ds.entity_ids[1]
    model, _ = train_random_effect(
        ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0]),
        per_entity_reg_weights={heavy_id: 1e4},
    )
    for e_id in ds.entity_ids:
        got = model.coefficients_for_entity(e_id)
        ref = base.coefficients_for_entity(e_id)
        if e_id == heavy_id:
            # crushed toward zero by the 2e4x larger L2
            assert np.linalg.norm(got) < 0.05 * max(np.linalg.norm(ref), 1e-9)
        else:
            np.testing.assert_allclose(got, ref, atol=1e-6)


def test_per_entity_reg_weights_array_form(rng):
    X, ents, labels, _ = make_re_data(rng, n_entities=3, min_s=20, max_s=30)
    ds = build_random_effect_dataset(X, ents, "entity", labels=labels, dtype=jnp.float64)
    uniform, _ = train_random_effect(
        ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0]),
        per_entity_reg_weights=np.full(3, CFG.l2_weight),
    )
    plain, _ = train_random_effect(
        ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0])
    )
    np.testing.assert_allclose(
        np.asarray(uniform.coeffs), np.asarray(plain.coeffs), atol=1e-9
    )
    with pytest.raises(ValueError, match="entries for"):
        train_random_effect(
            ds, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(X.shape[0]),
            per_entity_reg_weights=np.ones(7),
        )


def test_all_entities_filtered_returns_empty_dataset():
    """Lower bound above every entity's count: valid empty dataset, no crash
    (regression: the vectorized observed-column path raised IndexError)."""
    import scipy.sparse as sp

    X = sp.csr_matrix(np.ones((4, 3)))
    ents = np.asarray(["a", "a", "b", "c"])
    y = np.asarray([0.0, 1.0, 1.0, 0.0])
    ds = build_random_effect_dataset(
        X, ents, "e", labels=y, active_data_lower_bound=10
    )
    assert ds.n_entities == 0 and ds.buckets == []
    assert np.all(np.asarray(ds.sample_entity_rows) == -1)

    empty = build_random_effect_dataset(
        sp.csr_matrix((0, 3)), np.asarray([], dtype=object), "e", scoring_only=True
    )
    assert empty.n_entities == 0 and empty.n_samples == 0


def test_bucket_consolidation_parity_and_guard(rng):
    """Buckets merge where a bucket costs more than the padding that saves it,
    without changing results; a pathological huge entity must NOT inflate
    everyone's sample axis."""
    X, ents, labels, _ = make_re_data(rng, n_entities=40, min_s=4, max_s=9)
    # one rare large entity, a height of its own
    extra_n = 200
    Xe = sp.vstack([X, sp.csr_matrix(np.ones((extra_n, X.shape[1])))]).tocsr()
    ents_e = np.concatenate([ents, np.asarray(["big"] * extra_n, dtype=object)])
    labels_e = np.concatenate([labels, (np.arange(extra_n) % 2).astype(np.float64)])

    merged = build_random_effect_dataset(
        Xe, ents_e, "entity", labels=labels_e, dtype=jnp.float64,
        # explicit: on the CPU the backend's own answer is 0. Widening the
        # 8-row entities to their widest neighbours pads a few thousand cells;
        # raising all 40 to the big entity's 200 rows would pad over 30,000.
        bucket_cost=6000.0,
    )
    unmerged = build_random_effect_dataset(
        Xe, ents_e, "entity", labels=labels_e, dtype=jnp.float64,
        bucket_cost=0.0,
    )
    assert len(merged.buckets) < len(unmerged.buckets)  # a merge DID happen
    # guard: the big entity's 256-row bucket must not swallow the small
    # buckets' sample axis (the padding would cost more than the bucket)
    small_s = [b.X.shape[1] for b in merged.buckets if b.n_entities > 1]
    assert small_s and max(small_s) <= 64

    m1, _ = train_random_effect(
        merged, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(Xe.shape[0])
    )
    m0, _ = train_random_effect(
        unmerged, TaskType.LOGISTIC_REGRESSION, CFG, jnp.zeros(Xe.shape[0])
    )
    np.testing.assert_allclose(
        np.asarray(m1.coeffs), np.asarray(m0.coeffs), atol=1e-6
    )
