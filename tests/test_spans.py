"""Spans and counters inside the training path (util/timed): the ``Timed``
primitive, the span names and nesting of a fit, the sync-free contract, and
the solver / ingest counters. All counts, none timing-sensitive."""

import collections
import importlib
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.algorithm.random_effect import RandomEffectTracker
from photon_ml_tpu.analysis.runtime_guard import sync_discipline
from photon_ml_tpu.data.game_data import GameInput
from photon_ml_tpu.data.random_effect import build_random_effect_dataset
from photon_ml_tpu.estimators import (
    CoordinateConfiguration,
    FixedEffectDataConfiguration,
    GameEstimator,
    RandomEffectDataConfiguration,
)
from photon_ml_tpu.optimization.common import OptimizerConfig
from photon_ml_tpu.optimization.config import (
    GLMOptimizationConfiguration,
    RegularizationContext,
)
from photon_ml_tpu.optimization.lbfgs import minimize_lbfgs
from photon_ml_tpu.types import RegularizationType, TaskType
from photon_ml_tpu.util.timed import MAX_RECORDS, Record, Timed, count, records, span, summary

# the module itself: ``photon_ml_tpu.util.timed`` as an attribute is the decorator
timed_module = importlib.import_module("photon_ml_tpu.util.timed")

PASSES = 2
COORDINATES = ("fixed", "per-user", "per-item")
# table (b) of the span contract; descent.checkpoint needs a checkpointer
FIT_SPANS = {
    "fit", "fit.prepare", "fit.build",
    "descent.init", "descent.init_score", "descent.update", "descent.guard",
    "descent.validate", "descent.validate_score", "descent.evaluate", "descent.snapshot",
    "descent.finish",
    "ingest.re_index", "ingest.re_buckets", "ingest.h2d",
}
PARENT = {
    "fit.prepare": "fit", "fit.build": "fit", "descent.init": "fit", "descent.update": "fit",
    "descent.validate": "fit", "descent.finish": "fit",
    "descent.init_score": "descent.init", "descent.guard": "descent.update",
    "descent.validate_score": "descent.validate", "descent.evaluate": "descent.validate",
    "descent.snapshot": "descent.validate",
    "ingest.re_index": "fit.prepare", "ingest.re_buckets": "fit.prepare",
    "ingest.h2d": "fit.prepare",
}


# ------------------------------------------------------------------ Timed


def test_timed_nests_and_stamps_the_wall_clock():
    t0 = time.time_ns()
    with Timed("outer", stage="a") as outer:
        with span("inner", cid="x") as inner:
            sum(range(1000))
    got = {r.name: r for r in records(since_ns=t0)}
    assert set(got) == {"outer", "inner"}
    for r in got.values():
        assert r.end_ns >= r.start_ns >= t0 and r.value is None
    assert got["outer"].start_ns <= got["inner"].start_ns
    assert got["inner"].end_ns <= got["outer"].end_ns
    assert got["inner"].attrs == {"cid": "x"} and got["outer"].attrs == {"stage": "a"}
    assert outer.seconds >= inner.seconds >= 0
    assert [r.name for r in records(since_ns=t0, name="inner")] == ["inner"]
    assert records(since_ns=t0, until_ns=got["inner"].end_ns, name="outer") == []


@pytest.mark.parametrize("make, level", [(Timed, logging.INFO), (span, logging.DEBUG)])
def test_timed_keeps_its_log_line(caplog, make, level):
    with caplog.at_level(logging.DEBUG, logger="photon.timed"):
        with make("phase"):
            pass
    lines = [r for r in caplog.records if "phase took" in r.message]
    assert len(lines) == 1 and lines[0].levelno == level


def test_timed_records_a_failed_section(caplog):
    t0 = time.time_ns()
    with caplog.at_level(logging.INFO, logger="photon.timed"):
        with pytest.raises(ValueError):
            with Timed("doomed"):
                raise ValueError("boom")
    assert [r.name for r in records(since_ns=t0)] == ["doomed"]
    assert any("doomed took" in r.message and "(failed)" in r.message for r in caplog.records)


def test_recorder_is_bounded(monkeypatch):
    assert timed_module._records.maxlen == MAX_RECORDS
    monkeypatch.setattr(timed_module, "_records", collections.deque(maxlen=4))
    for i in range(10):
        with span("s", i=i):
            pass
    assert [r.attrs["i"] for r in records()] == [6, 7, 8, 9]


def test_count_and_summary():
    t0 = time.time_ns()
    before = summary().get("summed", (0, 0.0))
    count("things", 3, cid="a")
    for _ in range(2):
        with span("summed"):
            pass
    (counter,) = records(since_ns=t0, name="things")
    assert counter == Record("things", counter.start_ns, counter.start_ns, {"cid": "a"}, 3.0)
    n, seconds = summary()["summed"]
    assert n == before[0] + 2 and seconds >= before[1]


# ---------------------------------------------------------------- the fit

OPT = GLMOptimizationConfiguration(
    optimizer_config=OptimizerConfig(max_iterations=30, tolerance=1e-7),
    regularization_context=RegularizationContext(RegularizationType.L2),
    regularization_weight=1.0,
)


def _data():
    rng = np.random.default_rng(7)
    n, d = 600, 4
    X = rng.normal(size=(n, d))
    users, items = np.arange(n) % 7, (np.arange(n) * 3) % 5
    z = X @ rng.normal(size=d) + rng.normal(size=7)[users] + rng.normal(size=5)[items]
    data = GameInput(
        features={"global": X, "re": sp.csr_matrix(np.hstack([np.ones((n, 1)), X[:, :2]]))},
        labels=(z > 0).astype(np.float64),
        id_columns={
            "userId": np.asarray([f"u{u}" for u in users], dtype=object),
            "itemId": np.asarray([f"i{i}" for i in items], dtype=object),
        },
    )
    return data.select(np.arange(0, 450)), data.select(np.arange(450, n))


def _estimator(**kwargs):
    return GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configurations={
            "fixed": CoordinateConfiguration(
                data_config=FixedEffectDataConfiguration("global"), optimization_config=OPT
            ),
            "per-user": CoordinateConfiguration(
                data_config=RandomEffectDataConfiguration("userId", "re"), optimization_config=OPT
            ),
            "per-item": CoordinateConfiguration(
                data_config=RandomEffectDataConfiguration("itemId", "re"), optimization_config=OPT
            ),
        },
        n_iterations=PASSES,
        **kwargs,
    )


def _coefficients(results):
    from photon_ml_tpu.algorithm.coordinate import coefficient_arrays

    return [np.array(a) for _cid, m in results[0].model for a in coefficient_arrays(m)]


@pytest.fixture(scope="module")
def fit():
    """One warm fit (compiles), then a recorded one: its records, its results
    and the coefficients it ended at."""
    train, val = _data()
    est = _estimator()
    est.fit(train, validation_data=val)
    t0 = time.time_ns()
    results = est.fit(train, validation_data=val)
    # taken BEFORE any test reads a tracker or a counter
    coefficients = _coefficients(results)
    return {"est": est, "train": train, "val": val, "results": results,
            "coefficients": coefficients, "records": records(since_ns=t0)}


def _spans(fit, name):
    return [r for r in fit["records"] if r.name == name and r.value is None]


def test_fit_yields_exactly_the_span_names_of_the_contract(fit):
    assert {r.name for r in fit["records"] if r.value is None} == FIT_SPANS


@pytest.mark.parametrize(
    "name, n",
    [
        ("fit", 1),
        ("descent.init", 1),
        ("descent.init_score", len(COORDINATES)),
        ("descent.update", PASSES * len(COORDINATES)),
        ("descent.guard", PASSES * len(COORDINATES)),  # validating runs read per update
        ("descent.validate", PASSES * len(COORDINATES)),
        ("descent.finish", 1),
        ("fit.prepare", 2),  # training datasets; scoring datasets + suite
        ("fit.build", 2),  # what the sweep shares; the one configuration
    ],
)
def test_span_counts(fit, name, n):
    assert len(_spans(fit, name)) == n


def test_update_spans_carry_coordinate_kind_and_iteration(fit):
    got = [(r.attrs["iteration"], r.attrs["cid"], r.attrs["kind"])
           for r in _spans(fit, "descent.update")]
    kinds = {"fixed": "fe", "per-user": "re", "per-item": "re"}
    assert got == [(i, cid, kinds[cid]) for i in range(PASSES) for cid in COORDINATES]
    assert [r.attrs["cid"] for r in _spans(fit, "descent.init_score")] == list(COORDINATES)


def test_evaluate_spans_say_where_the_metric_was_computed(fit):
    """The default AUC suite over one-device scores, unit weights and zero
    offsets is served on the device (``EvaluationSuite.metric_path``)."""
    got = [(r.attrs["cid"], r.attrs["metric_path"]) for r in _spans(fit, "descent.evaluate")]
    assert got == [(cid, "device") for _ in range(PASSES) for cid in COORDINATES]


@pytest.mark.parametrize("start", ["fresh", "warm"])
def test_init_score_spans_say_whether_the_kernel_scored(fit, start):
    """A fresh fit's random effects answer their initial score themselves
    (``scored`` False); the fixed effect, and every coordinate of a warm fit,
    is scored."""
    spans = _spans(fit, "descent.init_score")
    want = {"fixed": True, "per-user": False, "per-item": False}
    if start == "warm":
        t0 = time.time_ns()
        fit["est"].fit(
            fit["train"], validation_data=fit["val"], initial_model=fit["results"][0].model
        )
        spans = records(since_ns=t0, name="descent.init_score")
        want = dict.fromkeys(COORDINATES, True)
    assert {r.attrs["cid"]: r.attrs["scored"] for r in spans} == want
    assert len(spans) == len(COORDINATES)


@pytest.mark.parametrize("child", sorted(PARENT))
def test_children_lie_inside_their_parents(fit, child):
    parents = _spans(fit, PARENT[child])
    for r in _spans(fit, child):
        assert any(p.start_ns <= r.start_ns and r.end_ns <= p.end_ns for p in parents), r


def test_checkpointing_fit_adds_the_checkpoint_span(tmp_path):
    train, val = _data()
    t0 = time.time_ns()
    _estimator(checkpoint_directory=str(tmp_path)).fit(train, validation_data=val)
    names = {r.name for r in records(since_ns=t0) if r.value is None}
    assert names == FIT_SPANS | {"descent.checkpoint"}
    assert len(records(since_ns=t0, name="descent.checkpoint")) == PASSES


def test_no_span_adds_a_sync_or_a_trace(fit):
    """The warmed fit again under the guard: no implicit device-to-host read
    (a span that synced would be one) and not one new trace."""
    with sync_discipline(what="a warmed fit") as region:
        fit["est"].fit(fit["train"], validation_data=fit["val"])
        assert region.traces == 0


def test_coefficients_are_bitwise_the_same_with_and_without_reading_the_counters(fit):
    for trackers in fit["results"][0].descent.trackers.values():
        for t in trackers:
            t.summary()  # reads every counter the tracker carries
    again = fit["est"].fit(fit["train"], validation_data=fit["val"])
    for a, b in zip(fit["coefficients"], _coefficients(again)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(fit["coefficients"], _coefficients(fit["results"])):
        np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------- the counters


def test_fit_publishes_the_solver_counters_once_per_update(fit):
    evaluations = [r for r in fit["records"] if r.name == "solver.evaluations"]
    assert sorted((r.attrs["cid"], r.attrs["kind"]) for r in evaluations) == sorted(
        [("fixed", "fe")] * PASSES + [("per-user", "re"), ("per-item", "re")] * PASSES
    )
    waste = [r for r in fit["records"] if r.name == "solver.lane_waste"]
    assert len(waste) == 2 * PASSES and all(0.0 <= r.value < 1.0 for r in waste)
    finish = _spans(fit, "descent.finish")[0]  # published as the trackers materialise
    assert all(finish.start_ns <= r.start_ns <= finish.end_ns for r in evaluations + waste)
    padding = [r for r in fit["records"] if r.name == "ingest.padding_waste"]
    assert [r.attrs["cid"] for r in padding] == ["per-user", "per-item"]


def test_trackers_carry_evaluations_beside_iterations(fit):
    trackers = fit["results"][0].descent.trackers
    for t in trackers["fixed"]:
        assert t.evaluations >= t.iterations + 1
    for cid in ("per-user", "per-item"):
        for t in trackers[cid]:
            assert t.evaluations_mean >= t.iterations_mean + 1
            assert 0.0 <= t.lane_waste < 1.0


def _counted_problem():
    rng = np.random.default_rng(3)
    A = rng.normal(size=(60, 5))
    y = (rng.random(60) > 0.5).astype(np.float64)
    calls = [0]

    def value_and_grad(w):
        calls[0] += 1
        z = A @ w
        return (
            jnp.sum(jnp.logaddexp(0.0, z) - y * z) + 0.5 * jnp.sum(w * w),
            A.T @ (jax.nn.sigmoid(z) - y) + w,
        )

    return value_and_grad, calls


@pytest.mark.parametrize("bounded", [False, True], ids=["free", "box"])
def test_evaluations_equal_a_python_count_of_objective_calls(bounded):
    value_and_grad, calls = _counted_problem()
    bounds = (
        dict(lower_bounds=jnp.full(5, -0.05), upper_bounds=jnp.full(5, 0.05)) if bounded else {}
    )
    with jax.disable_jit():
        result = minimize_lbfgs(value_and_grad, jnp.zeros(5), max_iterations=20, **bounds)
    assert int(result.evaluations) == calls[0]
    assert int(result.evaluations) >= int(result.iterations) + 1
    jitted = jax.jit(lambda x: minimize_lbfgs(value_and_grad, x, max_iterations=20, **bounds))(
        jnp.zeros(5)
    )
    assert int(jitted.evaluations) == int(result.evaluations)
    np.testing.assert_array_equal(np.asarray(jitted.iterations), np.asarray(result.iterations))


@pytest.mark.parametrize(
    "evaluations, lane_rows, waste, mean",
    [
        ([np.array([7])], [8], 0.0, 7.0),  # one lane: nothing waits for it
        ([np.array([4, 4, 4])], [16], 0.0, 4.0),  # lanes that stop together
        # two buckets: 8 x (3 + 5) + 16 x 4 = 128 row-evaluations were some
        # lane's own of the 8 x 2 x 5 + 16 x 1 x 4 = 144 that ran
        ([np.array([3, 5]), np.array([4])], [8, 16], 1.0 - 128 / 144, 4.0),
    ],
    ids=["one-lane", "equal-lanes", "two-buckets"],
)
def test_lane_waste_matches_a_hand_count(evaluations, lane_rows, waste, mean):
    lanes = sum(len(e) for e in evaluations)
    tracker = RandomEffectTracker.from_arrays(
        np.ones(lanes, np.int32), np.ones(lanes, np.int32), evaluations, lane_rows
    )
    assert tracker.lane_waste == pytest.approx(waste, abs=1e-12)
    assert tracker.evaluations_mean == pytest.approx(mean)
    assert tracker.padded_rows == sum(s * len(e) for s, e in zip(lane_rows, evaluations))


def test_a_minimiser_that_counts_nothing_leaves_the_counters_none():
    tracker = RandomEffectTracker.from_arrays(
        np.ones(2, np.int32), np.ones(2, np.int32), [None], [8]
    )
    assert tracker.evaluations_mean is None and tracker.lane_waste is None
    t0 = time.time_ns()
    tracker.publish("per-user")
    assert records(since_ns=t0) == []


def test_padding_waste_matches_a_hand_built_dataset():
    # entities of 3, 5 and 9 rows pad to 8, 8 and 16: 17 samples in 32 rows
    sizes = {"a": 3, "b": 5, "c": 9}
    ids = np.asarray([e for e, s in sizes.items() for _ in range(s)], dtype=object)
    n = len(ids)
    t0 = time.time_ns()
    dataset = build_random_effect_dataset(
        sp.csr_matrix(np.ones((n, 1))), ids, "entity", labels=np.zeros(n),
        bucket_cost=0.0,
    )
    assert sorted((b.n_entities, b.shape[0]) for b in dataset.buckets) == [(1, 16), (2, 8)]
    assert dataset.padding_waste == pytest.approx(1.0 - 17 / 32)
    assert [r.name for r in records(since_ns=t0)] == [
        "ingest.re_index", "ingest.re_buckets", "ingest.h2d"
    ]
    scoring = build_random_effect_dataset(
        sp.csr_matrix(np.ones((n, 1))), ids, "entity", scoring_only=True
    )
    assert scoring.buckets == [] and scoring.padding_waste == 0.0
