"""Device-resident working set: hierarchical entity-table training tests.

The streamed working set (data/working_set.py + RandomEffectCoordinate.
_update_and_score_streamed) must be a pure memory transformation of the
all-resident update program: bitwise-equal coefficients and scores across the
featureful configuration matrix, device table bytes MEASURED under the
configured budget at 4x oversubscription, zero retraces across steady-state
chunk rotation, warm starts that survive admission/eviction churn, logged
(never silent) demotions back to the all-resident path, and bitwise crash
recovery through every ``workingset.*`` fault point.

Two deliberate tolerance scopes (probed, documented in data/working_set.py and
solver_cache.re_chunk_update_program):

- FULL variances when a bucket is SPLIT across chunks: the Hessian build
  ``A.T @ (A * d)`` is a batched GEMM whose XLA lowering is batch-count-
  sensitive at the last bit (~1 ulp on a few lanes), so split-bucket variances
  are allclose-gated while coefficients and scores stay bitwise. Buckets that
  fit in one chunk keep their exact entity count (exact-lane rule) and carry
  the bitwise contract for ALL outputs, variances included.
- The ``direct`` solver's Gram accumulation is batch-shape-sensitive the same
  way; streamed-vs-resident direct solves are allclose-gated.
"""

import logging

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.algorithm import RandomEffectCoordinate
from photon_ml_tpu.analysis.fallbacks import reset_fallback_log
from photon_ml_tpu.analysis.runtime_guard import no_retrace
from photon_ml_tpu.data.game_data import GameInput
from photon_ml_tpu.data.random_effect import build_random_effect_dataset
from photon_ml_tpu.estimators import (
    CoordinateConfiguration,
    FixedEffectDataConfiguration,
    GameEstimator,
    RandomEffectDataConfiguration,
)
from photon_ml_tpu.normalization import FeatureDataStatistics, NormalizationContext
from photon_ml_tpu.optimization.common import OptimizerConfig
from photon_ml_tpu.optimization.config import (
    GLMOptimizationConfiguration,
    RegularizationContext,
)
from photon_ml_tpu.resilience import run_with_crash_at
from photon_ml_tpu.types import (
    NormalizationType,
    RegularizationType,
    TaskType,
    VarianceComputationType,
)

CFG = GLMOptimizationConfiguration(
    optimizer_config=OptimizerConfig(max_iterations=40, tolerance=1e-8),
    regularization_context=RegularizationContext(RegularizationType.L2),
    regularization_weight=0.7,
)

FALLBACK_LOGGER = "photon_ml_tpu.analysis.fallbacks"


# ------------------------------------------------------------------ workloads
#
# Two deliberate shapes:
#
# - SKEWED (N=420, E=20): entity e draws ~(e+1) shares, so entities spread
#   over ~6 pow2 sample-count bucket classes of <= 8 entities each. At
#   budget 17 (chunk cap 8) every bucket fits ONE chunk with its exact
#   entity count -> the streamed solve runs the all-resident batch shapes
#   and the bitwise contract covers coefficients, variances AND scores.
#   hot_budget = 17 - 2*8 = 1, so only 1-lane chunks are admitted — the
#   admit/evict fault points and hot-tier warm starts are on this surface.
# - SPLIT (N=640, E=64): round-robin entities, one 64-entity bucket that
#   budget 24 (cap 8) splits into 8 chunks -> the split-bucket tolerance
#   scope for FULL variances / the direct solver, and the 4x
#   oversubscription shape (budget 16 = E/4, zero resident rows).


def make_skewed_workload(rng, n=420, n_users=20):
    X = rng.normal(size=(n, 3))
    shares = np.repeat(np.arange(n_users), np.arange(1, n_users + 1))
    users = shares[np.arange(n) % len(shares)]
    w = rng.normal(size=3)
    y = (X @ w + 0.7 * rng.normal(size=n_users)[users] > 0).astype(np.float64)
    re_dense = np.concatenate([np.ones((n, 1)), 2.0 * X[:, :2] + 0.5], axis=1)
    stats = FeatureDataStatistics.compute(re_dense, intercept_index=0)
    norm = NormalizationContext.build(NormalizationType.STANDARDIZATION, stats)
    return sp.csr_matrix(re_dense), users, y, norm


def make_split_workload(rng, n=640, n_users=64):
    X = rng.normal(size=(n, 3))
    users = np.arange(n) % n_users
    w = rng.normal(size=3)
    y = (X @ w + 0.7 * rng.normal(size=n_users)[users] > 0).astype(np.float64)
    re_dense = np.concatenate([np.ones((n, 1)), 2.0 * X[:, :2] + 0.5], axis=1)
    return sp.csr_matrix(re_dense), users, y, None


def build_coordinate(
    workload,
    working_set_rows,
    *,
    normalization=None,
    per_entity=None,
    variance=VarianceComputationType.NONE,
    re_solver="lbfgs",
    priorities=None,
    overlap=True,
):
    X_re, users, y, _ = workload
    # a fresh dataset per coordinate: engaging the working set re-points
    # dataset.buckets at the host tier, so sharing one dataset between the
    # streamed and all-resident coordinates would alias their state
    ds = build_random_effect_dataset(
        X_re, users, "userId", feature_shard_id="per-user", labels=y,
        normalization=normalization,
        intercept_index=0 if normalization is not None else None,
    )
    return RandomEffectCoordinate(
        coordinate_id="per-user", dataset=ds,
        task=TaskType.LOGISTIC_REGRESSION, configuration=CFG,
        base_offsets=jnp.zeros(len(y), dtype=ds.sample_vals.dtype),
        normalization=normalization,
        variance_computation=variance,
        per_entity_reg_weights=per_entity,
        re_solver=re_solver,
        working_set_rows=working_set_rows,
        working_set_priorities=priorities,
        working_set_overlap=overlap,
    )


def run_passes(coord, n_passes, model=None, score=None):
    """The descent loop's view of one coordinate: update_and_score chained
    with donation, zero partial scores (single-coordinate descent)."""
    n = coord.dataset.n_samples
    partial = jnp.zeros(n, dtype=coord.dataset.sample_vals.dtype)
    if model is None:
        model = coord.initialize_model()
        score = coord.score(model)
    for _ in range(n_passes):
        model, score, tracker = coord.update_and_score(
            model, partial, score, donate=True
        )
        assert bool(np.asarray(tracker.guard_ok))
    return model, score


def state_of(model, score):
    out = {"coeffs": np.asarray(model.coeffs), "score": np.asarray(score)}
    if model.variances is not None:
        out["variances"] = np.asarray(model.variances)
    return out


# --------------------------------------------------------------- parity matrix


@pytest.mark.parametrize(
    "variance,with_per_entity,with_norm",
    [
        (VarianceComputationType.NONE, False, False),
        (VarianceComputationType.NONE, True, False),
        (VarianceComputationType.FULL, False, True),
        (VarianceComputationType.FULL, True, True),
    ],
    ids=[
        "novar-uniform-raw",
        "novar-per-entity-l2-raw",
        "fullvar-uniform-norm",
        "fullvar-per-entity-l2-norm",
    ],
)
def test_streamed_parity_matrix(rng, variance, with_per_entity, with_norm):
    """Bitwise-equal coefficients, variances and [N] scores vs the
    all-resident update program across the featureful configuration matrix,
    over multiple chained passes (score feedback would amplify any
    single-ulp divergence). Every bucket fits one chunk here, so the
    exact-lane rule makes the WHOLE state bitwise — variances included.

    Two (variance, normalization) trace cells — plain and fully-featureful
    — each with both L2 forms; each cell is one multi-second chunk-program
    trace, and the dropped cells' numerics are covered at split-bucket
    shapes by test_split_bucket_parity_scopes (FULL x raw) and by the
    rotation/churn tests (NONE x raw reused downstream)."""
    workload = make_skewed_workload(rng)
    norm = workload[-1] if with_norm else None
    per_entity = (
        {int(e): float(v) for e, v in enumerate(rng.uniform(0.4, 2.5, size=20))}
        if with_per_entity
        else None
    )

    def descend(ws):
        coord = build_coordinate(
            workload, ws, normalization=norm, per_entity=per_entity,
            variance=variance,
        )
        if ws is not None:
            assert coord.working_set_stats() is not None, "silently demoted"
            # the pinned-shape precondition: no bucket is split
            stats = coord.working_set_stats()
            assert stats["n_chunks"] == len(coord.dataset.buckets)
        return state_of(*run_passes(coord, 3))

    streamed = descend(17)
    resident = descend(None)
    assert set(streamed) == set(resident)
    for key in sorted(resident):
        np.testing.assert_array_equal(streamed[key], resident[key], err_msg=key)


def test_split_bucket_parity_scopes(rng):
    """A 64-entity bucket split into 8-lane chunks: coefficients and scores
    stay bitwise (lbfgs lane-count stability, probe-confirmed for batch >= 2),
    FULL variances are tolerance-bounded — the Hessian ``A.T @ (A * d)`` is a
    batched GEMM whose lowering is batch-count-sensitive at the last bit
    (~1 ulp drift on a few lanes; see solver_cache.re_chunk_update_program)."""
    workload = make_split_workload(rng)

    def descend(ws):
        coord = build_coordinate(
            workload, ws, variance=VarianceComputationType.FULL
        )
        if ws is not None:
            stats = coord.working_set_stats()
            assert stats is not None
            # the split precondition: more chunks than buckets
            assert stats["n_chunks"] > len(coord.dataset.buckets)
        return state_of(*run_passes(coord, 3))

    streamed = descend(24)
    resident = descend(None)
    np.testing.assert_array_equal(streamed["coeffs"], resident["coeffs"])
    np.testing.assert_array_equal(streamed["score"], resident["score"])
    np.testing.assert_allclose(
        streamed["variances"], resident["variances"], rtol=1e-5, atol=1e-7
    )


def test_direct_solver_streamed_tolerance(rng):
    """re_solver='direct' on the streamed path: the batched Gram accumulation
    is batch-shape-sensitive at the last ulp across chunk splits, so direct
    streamed-vs-resident parity is tolerance-gated (same scope as the
    all-resident direct-vs-lbfgs gate)."""
    workload = make_split_workload(rng)
    streamed = state_of(
        *run_passes(build_coordinate(workload, 24, re_solver="direct"), 3)
    )
    resident = state_of(
        *run_passes(build_coordinate(workload, None, re_solver="direct"), 3)
    )
    np.testing.assert_allclose(
        streamed["coeffs"], resident["coeffs"], rtol=1e-6, atol=1e-9
    )
    np.testing.assert_allclose(
        streamed["score"], resident["score"], rtol=1e-6, atol=1e-9
    )


def test_unoverlapped_streaming_is_bitwise_identical(rng):
    """``working_set_overlap=False`` (the bench's serialized stage -> solve
    denominator) is an execution-strategy toggle only: coefficients,
    variances and scores are bitwise-equal to the double-buffered stream —
    staging is pure data movement, so threading it cannot move a bit."""
    workload = make_skewed_workload(rng)
    serial_coord = build_coordinate(
        workload, 17, variance=VarianceComputationType.FULL, overlap=False
    )
    serial = state_of(*run_passes(serial_coord, 3))
    stats = serial_coord.working_set_stats()
    assert stats is not None and stats["overlap"] is False
    overlapped_coord = build_coordinate(
        workload, 17, variance=VarianceComputationType.FULL
    )
    overlapped = state_of(*run_passes(overlapped_coord, 3))
    assert overlapped_coord.working_set_stats()["overlap"] is True
    assert set(serial) == set(overlapped)
    for key in sorted(overlapped):
        np.testing.assert_array_equal(serial[key], overlapped[key], err_msg=key)


def test_measured_auto_streamed_matches_resident(rng):
    """re_solver='auto' on the streamed path: the first pass measures per
    bucket shape and every chunk solves with its bucket's recorded choice
    (one cached chunk program per distinct solver). Against the all-resident
    auto coordinate with the SAME seeded decision the streamed result agrees
    to direct-solver tolerance (coefficients are bitwise when every chunk
    keeps its exact all-resident batch shape — the skewed workload at budget
    17 — but the contract gated here is the tolerance one)."""
    workload = make_skewed_workload(rng)
    streamed_coord = build_coordinate(workload, 17, re_solver="auto")
    streamed = state_of(*run_passes(streamed_coord, 3))
    stats = streamed_coord.re_solver_stats()
    assert stats and stats["per_shape"], stats
    resident_coord = build_coordinate(workload, None, re_solver="auto")
    resident_coord.seed_solver_decision(stats)
    resident = state_of(*run_passes(resident_coord, 3))
    np.testing.assert_allclose(
        streamed["coeffs"], resident["coeffs"], rtol=1e-6, atol=1e-9
    )
    np.testing.assert_allclose(
        streamed["score"], resident["score"], rtol=1e-6, atol=1e-9
    )


# ------------------------------------------------- bounded device table bytes


def test_bounded_device_bytes_at_4x_oversubscription(rng):
    """The memory claim, MEASURED: at a 4x-oversubscribed budget (16 rows for
    64 entities — zero resident rows, pure streaming) the live device table
    bytes sampled at every chunk boundary never exceed the configured budget,
    while the full CD pass stays bitwise-correct."""
    workload = make_split_workload(rng)
    coord = build_coordinate(workload, 16)
    model, score = run_passes(coord, 3)
    stats = coord.working_set_stats()
    assert stats["budget_rows"] == 16
    assert stats["resident_rows"] == 0  # genuinely oversubscribed
    assert stats["passes"] == 3
    assert 0 < stats["peak_device_table_bytes"] <= stats["budget_bytes"]
    resident = state_of(*run_passes(build_coordinate(workload, None), 3))
    np.testing.assert_array_equal(np.asarray(model.coeffs), resident["coeffs"])
    np.testing.assert_array_equal(np.asarray(score), resident["score"])


def test_zero_retraces_across_chunk_rotation(rng):
    """Steady-state chunk rotation compiles nothing: the chunk program family
    is closed after the first pass (one lane count per bucket), so passes 2+
    trigger zero jaxpr traces."""
    workload = make_split_workload(rng)
    coord = build_coordinate(workload, 24)
    model, score = run_passes(coord, 1)  # warmup: compiles the chunk family
    with no_retrace(allow_retraces=0, what="working-set chunk rotation"):
        run_passes(coord, 2, model=model, score=score)


# --------------------------------------------------- admission/eviction churn


def test_warm_start_survives_reselect_churn(rng):
    """Admission/eviction churn between passes moves no coefficients: the
    host tier is authoritative, so re-ranking residency mid-descent (the
    continuous trainer's gradient-norm screen) leaves the final state
    bitwise-equal to an uninterrupted run."""
    workload = make_skewed_workload(rng, n_users=24)
    coord = build_coordinate(workload, 20)
    # this shape must actually admit a hot tier, or the churn is vacuous
    assert any(c.hot for c in coord._working_set().chunks)
    model, score = run_passes(coord, 2)
    # invert the ranking: previously-cold entities become the hot tier
    assert coord.reselect_working_set(np.arange(24, dtype=np.float64)[::-1])
    assert any(c.hot for c in coord._working_set().chunks)
    model, score = run_passes(coord, 1, model=model, score=score)
    churned = state_of(model, score)
    resident = state_of(*run_passes(build_coordinate(workload, None), 3))
    np.testing.assert_array_equal(churned["coeffs"], resident["coeffs"])
    np.testing.assert_array_equal(churned["score"], resident["score"])


def test_streamed_foreign_warm_start_and_score(rng):
    """A foreign model (checkpoint restore / external warm start) seeds the
    host tier and scores through the chunked view kernel — both bitwise
    against the all-resident path."""
    workload = make_skewed_workload(rng)
    warm_model, warm_score = run_passes(build_coordinate(workload, None), 2)

    resident = build_coordinate(workload, None)
    streamed = build_coordinate(workload, 17)
    # chunked scoring of a nonzero foreign table == the full-table kernel
    np.testing.assert_array_equal(
        np.asarray(streamed.score(warm_model)),
        np.asarray(resident.score(warm_model)),
    )
    # one warm-started pass each: the foreign seed round-trips bitwise
    s_state = state_of(*run_passes(streamed, 1, model=warm_model, score=warm_score))
    r_state = state_of(*run_passes(resident, 1, model=warm_model, score=warm_score))
    np.testing.assert_array_equal(s_state["coeffs"], r_state["coeffs"])
    np.testing.assert_array_equal(s_state["score"], r_state["score"])
    # donation safety: the caller-held warm start survived both runs
    assert np.isfinite(np.asarray(warm_model.coeffs)).all()


def test_streamed_zero_model_score_is_the_score_of_the_initial_model(rng):
    """The working-set coordinate answers its initial score like the
    all-resident one: the host-tier zero table is never read, and the array
    is what ``score(initialize_model())`` hands out, bit for bit."""
    workload = make_skewed_workload(rng)
    streamed = build_coordinate(workload, 17)
    resident = build_coordinate(workload, None)
    model = streamed.initialize_model()
    assert streamed._working_set() is not None and isinstance(model.coeffs, np.ndarray)
    answered = streamed.zero_model_score()
    for want in (streamed.score(model), resident.score(resident.initialize_model())):
        assert (answered.shape, answered.dtype) == (want.shape, want.dtype)
        assert answered.sharding == want.sharding
        assert np.asarray(answered).tobytes() == np.asarray(want).tobytes()
    assert streamed.zero_model_score() is not answered
    # the first streamed pass from it == from the scored one
    s_state = state_of(*run_passes(streamed, 1, model=model, score=answered))
    r_state = state_of(*run_passes(build_coordinate(workload, 17), 1))
    np.testing.assert_array_equal(s_state["coeffs"], r_state["coeffs"])
    np.testing.assert_array_equal(s_state["score"], r_state["score"])


# ----------------------------------------------------------- logged demotions


def _assert_one_demotion(caplog, cause_fragment):
    records = [
        r for r in caplog.records if "re_working_set" in r.getMessage()
    ]
    assert len(records) == 1, [r.getMessage() for r in caplog.records]
    assert cause_fragment in records[0].getMessage()


@pytest.mark.parametrize(
    "knob,n_users,cause",
    [
        # budget covers every entity: nothing to stream
        (64, 20, "tables fit"),
        # below the minimal double-buffered schedule (2 x 8 lanes)
        (9, 20, "below the minimal double-buffered schedule"),
        # "auto" on a backend with no memory_stats (CPU): assume tables fit
        ("auto", 20, "no memory limit"),
    ],
    ids=["tables-fit", "infeasible-budget", "auto-no-limit"],
)
def test_demotions_are_logged_never_silent(rng, caplog, knob, n_users, cause):
    """Every demotion back to the all-resident path goes through
    log_fallback_once — a silent demotion could fake the bounded-memory
    claim. The demoted coordinate still trains (all-resident semantics)."""
    workload = make_skewed_workload(rng, n_users=n_users)
    coord = build_coordinate(workload, knob)
    reset_fallback_log()
    with caplog.at_level(logging.WARNING, logger=FALLBACK_LOGGER):
        model, score = run_passes(coord, 1)
    _assert_one_demotion(caplog, cause)
    assert coord.working_set_stats() is None  # demoted == all-resident
    assert coord.reselect_working_set() is False
    assert np.isfinite(np.asarray(model.coeffs)).all()


def test_knob_validation():
    def coord(**kw):
        rng = np.random.default_rng(3)
        return build_coordinate(make_skewed_workload(rng), **kw)

    with pytest.raises(ValueError, match="positive row budget"):
        coord(working_set_rows=0)
    with pytest.raises(ValueError, match="positive row budget"):
        coord(working_set_rows="bogus")
    with pytest.raises(ValueError, match="use_update_program"):
        c = coord(working_set_rows=None)
        RandomEffectCoordinate(
            coordinate_id="per-user", dataset=c.dataset, task=c.task,
            configuration=CFG, base_offsets=c.base_offsets,
            use_update_program=False, working_set_rows=17,
        )
    with pytest.raises(ValueError, match="reference precision"):
        c = coord(working_set_rows=None)
        RandomEffectCoordinate(
            coordinate_id="per-user", dataset=c.dataset, task=c.task,
            configuration=CFG, base_offsets=c.base_offsets,
            precision="bf16", working_set_rows=17,
        )


# -------------------------------------------------------- estimator plumbing

OPT = GLMOptimizationConfiguration(
    optimizer_config=OptimizerConfig(max_iterations=40, tolerance=1e-8),
    regularization_context=RegularizationContext(RegularizationType.L2),
    regularization_weight=1.0,
)


def make_game_input(rng, n=420, n_users=20):
    X = rng.normal(size=(n, 4))
    shares = np.repeat(np.arange(n_users), np.arange(1, n_users + 1))
    users = shares[np.arange(n) % len(shares)]
    bias = rng.normal(size=n_users) * 1.5
    y = (X @ rng.normal(size=4) + bias[users] + 0.3 * rng.normal(size=n) > 0)
    uid = np.asarray([f"u{u:02d}" for u in users], dtype=object)
    return GameInput(
        features={"global": X, "per-user": sp.csr_matrix(np.ones((n, 1)))},
        labels=y.astype(np.float64),
        id_columns={"userId": uid},
    )


def make_estimator(working_set_rows, n_iterations=2, ckpt_dir=None, **kw):
    return GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations={
            "fixed": CoordinateConfiguration(
                data_config=FixedEffectDataConfiguration("global"),
                optimization_config=OPT,
            ),
            "per-user": CoordinateConfiguration(
                data_config=RandomEffectDataConfiguration("userId", "per-user"),
                optimization_config=OPT,
            ),
        },
        n_iterations=n_iterations,
        checkpoint_directory=ckpt_dir,
        re_working_set_rows=working_set_rows,
        **kw,
    )


def game_state(result):
    return {
        "fixed": np.asarray(
            result.model.get_model("fixed").model.coefficients.means
        ),
        "re": np.asarray(result.model.get_model("per-user").coeffs),
        "score": np.asarray(result.descent.training_scores["per-user"]),
    }


def test_estimator_fit_parity(rng):
    """End to end through GameEstimator: re_working_set_rows bounds the
    per-user table while the full two-coordinate descent stays bitwise."""
    data = make_game_input(rng)
    ws_state = game_state(make_estimator(17).fit(data)[0])
    ref_state = game_state(make_estimator(None).fit(data)[0])
    for key in sorted(ref_state):
        np.testing.assert_array_equal(ws_state[key], ref_state[key], err_msg=key)


def test_estimator_knob_validation():
    with pytest.raises(ValueError, match="re_update_program"):
        make_estimator(17, re_update_program=False)
    with pytest.raises(ValueError, match="reference precision"):
        make_estimator(17, re_precision="bf16")


# ------------------------------------------------------- continuous trainer


CT_USERS = [f"w{i:02d}" for i in range(24)]
_ct_rng = np.random.default_rng(7)
CT_W = _ct_rng.normal(size=3)
CT_BIAS = dict(zip(CT_USERS, _ct_rng.normal(size=len(CT_USERS)) * 1.5))


def _write_ct_part(path, rng, n):
    """TrainingExampleAvro part over 24 entities (enough to oversubscribe a
    17-row working set); every entity appears at least once."""
    from photon_ml_tpu.data import avro_io

    X = rng.normal(size=(n, 3))
    picks = [CT_USERS[i] for i in rng.integers(0, len(CT_USERS), size=n)]
    us = CT_USERS + picks[len(CT_USERS):]
    z = X @ CT_W + np.array([CT_BIAS[u] for u in us])
    y = (z + 0.3 * rng.normal(size=n) > 0).astype(np.float64)

    def records():
        import os

        base = os.path.basename(str(path))
        for i in range(n):
            yield {
                "uid": f"{base}#{i}",
                "label": float(y[i]),
                "features": [
                    {"name": f"f{j}", "term": "", "value": float(X[i, j])}
                    for j in range(3)
                ],
                "metadataMap": {"userId": us[i]},
                "weight": 1.0,
                "offset": 0.0,
            }

    avro_io.write_container(str(path), avro_io.TRAINING_EXAMPLE_SCHEMA, records())


def test_continuous_trainer_delta_passes_bitwise(rng, tmp_path):
    """The unbounded-horizon deployment shape: a bounded working set under
    the continuous trainer's bootstrap + delta passes is bitwise-equal to
    the all-resident trainer — across the checkpoint commit between polls
    (the knob is an execution strategy, deliberately outside the checkpoint
    fingerprint)."""
    from tests.test_continuous import make_trainer

    corpus = tmp_path / "corpus"
    corpus.mkdir()
    _write_ct_part(corpus / "part-0.avro", np.random.default_rng(11), 360)

    t_ws = make_trainer(corpus, tmp_path / "ck_ws", re_working_set_rows=17)
    t_ref = make_trainer(corpus, tmp_path / "ck_ref")
    assert t_ws.poll_once().kind == "bootstrap"
    assert t_ref.poll_once().kind == "bootstrap"
    np.testing.assert_array_equal(
        np.asarray(t_ws.models["per-user"].coeffs),
        np.asarray(t_ref.models["per-user"].coeffs),
    )
    _write_ct_part(corpus / "part-1.avro", np.random.default_rng(12), 240)
    assert t_ws.poll_once().kind == "delta"
    assert t_ref.poll_once().kind == "delta"
    np.testing.assert_array_equal(
        np.asarray(t_ws.models["per-user"].coeffs),
        np.asarray(t_ref.models["per-user"].coeffs),
    )


# -------------------------------------------- eviction / archive interplay


def _write_ct_part_users(path, rng, users, heavy=None, heavy_rows=0):
    """TrainingExampleAvro part over an explicit entity list: every entity in
    ``users`` appears exactly once, plus ``heavy_rows`` extra rows for the
    single ``heavy`` entity (data-mass hotness under the working set's
    default admission priority)."""
    from photon_ml_tpu.data import avro_io

    us = list(users) + [heavy] * heavy_rows
    n = len(us)
    X = rng.normal(size=(n, 3))
    z = X @ CT_W + np.array([CT_BIAS[u] for u in us])
    y = (z + 0.3 * rng.normal(size=n) > 0).astype(np.float64)

    def records():
        import os

        base = os.path.basename(str(path))
        for i in range(n):
            yield {
                "uid": f"{base}#{i}",
                "label": float(y[i]),
                "features": [
                    {"name": f"f{j}", "term": "", "value": float(X[i, j])}
                    for j in range(3)
                ],
                "metadataMap": {"userId": us[i]},
                "weight": 1.0,
                "offset": 0.0,
            }

    avro_io.write_container(str(path), avro_io.TRAINING_EXAMPLE_SCHEMA, records())


def _spy_per_user_coordinates(trainer, captured):
    """Wrap ``estimator.build_coordinate`` so each pass's freshly built
    per-user coordinate lands in ``captured`` — the trainer rebuilds
    coordinates every pass, so this is the only window onto the pass's
    working-set tiering."""
    orig = trainer.estimator.build_coordinate

    def spy(cid, dataset, opt_config, base_offsets, initial_model=None):
        coord = orig(cid, dataset, opt_config, base_offsets,
                     initial_model=initial_model)
        if cid == "per-user":
            captured.append(coord)
        return coord

    trainer.estimator.build_coordinate = spy


def _hot_entities(coord):
    """Entity ids whose rows are device-resident (hot chunks) on ``coord``'s
    working set after a pass; padding lanes duplicate real rows so the set
    is exact."""
    ws = coord._working_set()
    assert ws is not None, "working set never built — budget not engaged?"
    ids = coord.dataset.entity_ids
    return {ids[int(r)] for c in ws.chunks if c.hot for r in c.rows}


def _streamed_entities(coord):
    ws = coord._working_set()
    assert ws is not None
    ids = coord.dataset.entity_ids
    return {ids[int(r)] for c in ws.chunks if not c.hot for r in c.rows}


def test_eviction_removes_entity_from_hot_set_same_pass(rng, tmp_path):
    """An entity archived by the idle-eviction scan must leave the device
    working set the SAME pass: the eviction pass's dataset (and therefore
    every chunk, hot or cold) excludes it — an archived entity is never
    pinned on device past its archival."""
    from tests.test_continuous import make_trainer

    corpus = tmp_path / "corpus"
    corpus.mkdir()
    # pass 1: every entity, with w00 heavy enough to claim device residency
    _write_ct_part_users(corpus / "part-0.avro", np.random.default_rng(21),
                         CT_USERS, heavy="w00", heavy_rows=60)
    others = [u for u in CT_USERS if u != "w00"]

    caps = []
    t = make_trainer(
        corpus, tmp_path / "ck", re_working_set_rows=17,
        evict_idle_generations=1, window_mode="sliding",
        window_generations=1,
    )
    _spy_per_user_coordinates(t, caps)

    assert t.poll_once().kind == "bootstrap"
    assert "w00" in caps[-1].dataset.entity_ids
    assert "w00" in _hot_entities(caps[-1]), (
        "heavy entity should be device-resident under data-mass priority"
    )

    # pass 2: w00 idle (last_active=1 > cutoff=0 — survives)
    _write_ct_part_users(corpus / "part-1.avro", np.random.default_rng(22),
                         others)
    assert t.poll_once().kind == "delta"
    assert "w00" not in t.evicted["per-user"]

    # pass 3: w00 idle again (last_active=1 <= cutoff=1 — archived). The
    # pass that archives it must also build its working set WITHOUT it.
    _write_ct_part_users(corpus / "part-2.avro", np.random.default_rng(23),
                         others)
    assert t.poll_once().kind == "delta"
    assert "w00" in t.evicted["per-user"]
    assert "w00" not in caps[-1].dataset.entity_ids
    assert "w00" not in _hot_entities(caps[-1]) | _streamed_entities(caps[-1])
    assert "w00" not in t.models["per-user"].entity_ids


def test_readmission_enters_cold_and_matches_all_resident_bitwise(rng, tmp_path):
    """A warm re-admitted entity (archive-seeded coefficients) re-enters
    through the COLD streaming path — one trailing row ranks last under
    data-mass priority — and the whole evict → archive → readmit arc is
    bitwise-identical to the all-resident trainer running the same eviction
    policy: tiering is an execution strategy, not a numerics fork."""
    from tests.test_continuous import make_trainer

    def fill(corpus):
        corpus.mkdir()
        _write_ct_part_users(corpus / "part-0.avro", np.random.default_rng(31),
                             CT_USERS, heavy="w00", heavy_rows=60)

    others = [u for u in CT_USERS if u != "w00"]
    c_ws, c_ref = tmp_path / "c_ws", tmp_path / "c_ref"
    fill(c_ws)
    fill(c_ref)
    kw = dict(evict_idle_generations=1, window_mode="sliding",
              window_generations=1)
    caps = []
    t_ws = make_trainer(c_ws, tmp_path / "ck_ws", re_working_set_rows=17, **kw)
    t_ref = make_trainer(c_ref, tmp_path / "ck_ref", **kw)
    _spy_per_user_coordinates(t_ws, caps)

    def step(part, users, **wkw):
        for corpus in (c_ws, c_ref):
            _write_ct_part_users(corpus / part, np.random.default_rng(33),
                                 users, **wkw)
        assert t_ws.poll_once().kind == "delta"
        assert t_ref.poll_once().kind == "delta"
        np.testing.assert_array_equal(
            np.asarray(t_ws.models["per-user"].coeffs),
            np.asarray(t_ref.models["per-user"].coeffs),
        )

    assert t_ws.poll_once().kind == "bootstrap"
    assert t_ref.poll_once().kind == "bootstrap"
    step("part-1.avro", others)
    step("part-2.avro", others)  # w00 archived here
    assert "w00" in t_ws.evicted["per-user"]
    assert "w00" in t_ref.evicted["per-user"]

    # pass 4: w00 returns with ONE row — readmitted warm from the archive on
    # both trainers, entering the working-set trainer via cold streaming
    step("part-3.avro", CT_USERS)
    assert "w00" not in t_ws.evicted["per-user"]
    assert "w00" in t_ws.models["per-user"].entity_ids
    assert "w00" in caps[-1].dataset.entity_ids
    assert "w00" in _streamed_entities(caps[-1]), (
        "one-row readmitted entity should stream cold, not pin hot"
    )
    assert "w00" not in _hot_entities(caps[-1])


# ------------------------------------------------------------- chaos recovery


@pytest.mark.chaos
@pytest.mark.parametrize(
    "point,occurrence",
    [
        ("workingset.admit", 1),
        ("workingset.h2d", 1),
        ("workingset.h2d", 8),  # mid-stream, pass 2: a checkpoint exists
        ("workingset.scatter", 8),
    ],
    ids=["admit-1", "h2d-1", "h2d-mid", "scatter-mid"],
)
def test_workingset_crash_recovers_bitwise(rng, tmp_path, point, occurrence):
    """Crash the checkpointed fit at each streaming fault point (H2D crashes
    fire on the prefetch THREAD and must surface on the training thread),
    restart against the same checkpoint directory, and land bitwise on the
    uninterrupted run's model — the host-authoritative tier's recovery
    claim: a mid-stream death loses at most the in-flight pass."""
    # 24 entities at a 20-row budget: one admitted (hot) chunk so
    # workingset.admit actually fires, three streamed chunks per pass so the
    # mid-stream occurrences land inside a pass
    data = make_game_input(rng, n_users=24)
    ref = game_state(make_estimator(20, n_iterations=3).fit(data)[0])

    def run_once():
        return make_estimator(
            20, n_iterations=3, ckpt_dir=str(tmp_path / "ck")
        ).fit(data)[0]

    result, outcome = run_with_crash_at(run_once, point, occurrence=occurrence)
    assert outcome.crashed, f"{point} never fired — untested recovery"
    assert outcome.restarts >= 1
    got = game_state(result)
    for key in sorted(ref):
        np.testing.assert_array_equal(got[key], ref[key], err_msg=key)


@pytest.mark.chaos
def test_workingset_evict_crash_recovers_bitwise(rng):
    """The eviction fault point fires on admission churn (reselect): a crash
    there loses only device caches — a clean rerun of the same descent lands
    bitwise on the uninterrupted result (host tables never move on churn)."""
    workload = make_skewed_workload(rng, n_users=24)
    new_priorities = np.arange(24, dtype=np.float64)[::-1]

    def run_once():
        coord = build_coordinate(workload, 20)
        model, score = run_passes(coord, 1)
        assert coord.reselect_working_set(new_priorities)
        model, score = run_passes(coord, 1, model=model, score=score)
        return state_of(model, score)

    ref = run_once()
    result, outcome = run_with_crash_at(run_once, "workingset.evict")
    assert outcome.crashed
    np.testing.assert_array_equal(result["coeffs"], ref["coeffs"])
    np.testing.assert_array_equal(result["score"], ref["score"])
