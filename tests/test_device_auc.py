"""The validation AUC computed where the scores are (evaluation/evaluators.py):
the suite's device path equals the host ``auc_roc`` BIT FOR BIT, the rule that
admits an input to it, and a validating descent that cannot tell the two apart.
Counts and equalities, none timing-sensitive."""

import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from photon_ml_tpu.algorithm import run_coordinate_descent
from photon_ml_tpu.analysis.runtime_guard import sync_discipline
from photon_ml_tpu.data.dataset import FixedEffectDataset, LabeledData
from photon_ml_tpu.data.random_effect import build_random_effect_dataset
from photon_ml_tpu.evaluation import EvaluatorType, evaluator_for_type
from photon_ml_tpu.evaluation import evaluators as ev
from photon_ml_tpu.evaluation.evaluators import (
    DEVICE_AUC_MAX_ROWS,
    EvaluationSuite,
    MultiEvaluator,
    auc_roc,
)
from photon_ml_tpu.util.timed import records
from tests.test_coordinate_descent import build_coordinates, glmix_data

AUC = evaluator_for_type(EvaluatorType.AUC)
SPECIAL = np.array(
    [0.0, -0.0, np.inf, -np.inf, np.nan, -np.nan, 1e-45, -1e-45, 1e-39, 1.0, -1.0], np.float32
)


def _suite(labels, evaluators=(AUC,), weights=None, offsets=None, id_columns=None):
    n = len(labels)
    return EvaluationSuite(
        evaluators=list(evaluators),
        labels=np.asarray(labels),
        offsets=np.zeros(n) if offsets is None else offsets,
        weights=np.ones(n) if weights is None else weights,
        id_columns=id_columns,
    )


def _host_auc(scores, labels):
    with warnings.catch_warnings():  # inf - inf inside np.diff
        warnings.simplefilter("ignore", RuntimeWarning)
        return auc_roc(np.asarray(scores)[: len(labels)], labels)


def _same(a: float, b: float) -> bool:
    return a == b or (np.isnan(a) and np.isnan(b))


def _labels(rng, n, share=0.3):
    """Both classes present wherever n allows it."""
    y = (rng.random(n) < share).astype(np.float64)
    if n >= 2:
        y[0], y[1] = 1.0, 0.0
    return y


# ------------------------------------------------------------- exactness


def _random(n):
    def make(rng):
        return rng.normal(size=n).astype(np.float32), _labels(rng, n)

    return make


def _quantised(rng):
    return rng.integers(0, 16, size=5000).astype(np.float32) / 4 - 2, _labels(rng, 5000)


def _all_equal(rng):
    return np.full(1000, 0.25, np.float32), _labels(rng, 1000)


def _signed_zeros(rng):
    s = np.where(rng.random(1000) < 0.5, 0.0, -0.0).astype(np.float32)
    s[::7] = rng.normal(size=len(s[::7]))
    return s, _labels(rng, 1000, 0.5)


def _inf_and_nan(rng):
    s = rng.normal(size=2000).astype(np.float32)
    s[:600] = rng.choice(SPECIAL, 600)
    return s, _labels(rng, 2000, 0.5)


def _one_class(label):
    def make(rng):
        return rng.normal(size=500).astype(np.float32), np.full(500, label)

    return make


def _float32_labels(rng):
    return rng.normal(size=1000).astype(np.float32), _labels(rng, 1000).astype(np.float32)


def _bool_labels(rng):
    return rng.normal(size=1000).astype(np.float32), _labels(rng, 1000) > 0.5


def _padded_tail(rng):
    s = rng.normal(size=1008).astype(np.float32)
    s[1000:] = [np.inf, -np.inf, np.nan, 0.0, 9.0, -9.0, 1.0, 2.0]  # must not count
    return s, _labels(rng, 1000)


def _float64_scores(rng):
    s = rng.normal(size=3000)
    s[:300] = rng.choice(SPECIAL.astype(np.float64), 300)
    s[300:600] = 5e-324 * rng.integers(-3, 4, 300)  # subnormals are distinct scores
    return s, _labels(rng, 3000)


def _largest_admitted(_rng):
    """All negatives below all positives over the largest admitted row count:
    the integer sum at its largest, 2 * (2^23)^2 = 2^47."""
    n = DEVICE_AUC_MAX_ROWS
    labels = np.zeros(n)
    labels[n // 2 :] = 1.0
    return np.arange(n, dtype=np.float32), labels


@pytest.mark.parametrize(
    "make",
    [
        pytest.param(_random(1), id="random-1-row"),
        pytest.param(_random(2), id="random-2-rows"),
        pytest.param(_random(1000), id="random-1000-rows"),
        pytest.param(_random(200_003), id="random-200003-rows"),
        pytest.param(_quantised, id="16-values-heavy-ties"),
        pytest.param(_all_equal, id="all-scores-equal"),
        pytest.param(_signed_zeros, id="minus-zero-beside-zero"),
        pytest.param(_inf_and_nan, id="inf-nan-subnormal-scores"),
        pytest.param(_one_class(1.0), id="positives-only"),
        pytest.param(_one_class(0.0), id="negatives-only"),
        pytest.param(_float32_labels, id="labels-as-float32"),
        pytest.param(_bool_labels, id="labels-as-bool"),
        pytest.param(_padded_tail, id="scores-longer-than-labels"),
        pytest.param(_float64_scores, id="float64-scores"),
        pytest.param(_largest_admitted, id="largest-admitted-count-largest-sum"),
    ],
)
def test_device_path_equals_host_auc_bit_for_bit(rng, make):
    scores, labels = make(rng)
    suite = _suite(labels)
    on_device = jnp.asarray(scores)
    assert on_device.dtype == scores.dtype
    assert suite.metric_path(on_device) == "device"
    got = suite.evaluate(on_device)["AUC"]
    want = _host_auc(scores, labels)
    assert _same(got, want), (got, want)
    # and the suite's own host path, on the same scores as a NumPy array
    assert suite.metric_path(scores) == "host"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        assert _same(suite.evaluate(scores)["AUC"], want)


def test_limbs_cannot_overflow_at_the_admitted_count():
    """The no-overflow proof's two inequalities, from the module's constants
    (the largest-count case above runs the program at the bound)."""
    n, limb_max = DEVICE_AUC_MAX_ROWS, (1 << ev._LIMB_BITS) - 1
    assert n * limb_max < 2**31  # a limb's sum over every row fits an int32
    assert 2 * (n - 1) < 1 << (ev._LIMB_BITS * ev._LIMBS)  # a row's term fits the limbs


# ------------------------------------------------------------------ the rule


def _sharded(scores):
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    return jax.device_put(scores, NamedSharding(mesh, PartitionSpec("data")))


N = 1600  # divisible by the 8 devices of the CPU test mesh
HALF = np.where(np.arange(N) % 2 == 0, 1.0, 0.5)

HOST_CASES = {
    "weights-not-one": dict(weights=HALF),
    "offsets-not-zero": dict(offsets=HALF - 0.5),
    "multi-evaluator": dict(evaluators=[MultiEvaluator(AUC, "userId")]),
    "aupr": dict(evaluators=[evaluator_for_type(EvaluatorType.AUPR)]),
    "rmse": dict(evaluators=[evaluator_for_type(EvaluatorType.RMSE)]),
    "precision-at-k": dict(evaluators=[evaluator_for_type(EvaluatorType.PRECISION_AT_K)]),
    "logistic-loss": dict(evaluators=[evaluator_for_type(EvaluatorType.LOGISTIC_LOSS)]),
    "same-name-other-function": dict(evaluators=[ev.Evaluator("AUC", ev.auc_pr, True)]),
    "numpy-scores": dict(place=np.asarray),
    "sharded-scores": dict(place=_sharded),
    "integer-scores": dict(place=lambda s: jnp.asarray(np.round(s * 8).astype(np.int32))),
    "scores-shorter-than-labels": dict(place=lambda s: jnp.asarray(s[: N // 2]), raises=ValueError),
}


@pytest.mark.parametrize("case", sorted(HOST_CASES))
def test_rule_keeps_the_host_path_and_its_numbers(rng, case, monkeypatch):
    spec = dict(HOST_CASES[case])
    place, raises = spec.pop("place", jnp.asarray), spec.pop("raises", None)
    scores = rng.normal(size=N).astype(np.float32)
    labels = _labels(rng, N)
    groups = {"userId": np.arange(N) % 9}
    suite = _suite(labels, id_columns=groups, **spec)
    placed = place(scores)
    monkeypatch.setattr(
        ev, "_auc_rank_sums", lambda *a: pytest.fail("the device program ran on the host path")
    )
    assert suite.metric_path(placed) == "host"
    if raises is not None:  # today's behaviour on a malformed score
        with pytest.raises(raises):
            suite.evaluate(placed)
        return
    got = suite.evaluate(placed)
    total = np.asarray(placed)[:N] + suite.offsets
    (evaluator,) = suite.evaluators
    if isinstance(evaluator, MultiEvaluator):
        want = evaluator.evaluate_grouped(total, labels, suite.weights, groups["userId"])
    else:
        want = evaluator.fn(total, labels, suite.weights)
    assert got == {evaluator.name: want}


def test_rule_admits_the_cell_s_shape(rng):
    """float32 scores on one device, unit weights, zero offsets, plain AUC:
    what ``glmix-ml20m.train`` validates with; a padded tail changes nothing."""
    labels = _labels(rng, N)
    suite = _suite(labels)
    scores = jnp.asarray(rng.normal(size=N + 8).astype(np.float32))
    assert suite.metric_path(scores) == suite.metric_path(scores[:N]) == "device"
    assert _suite(labels[:0]).metric_path(scores) == "host"  # nothing to rank


def test_rule_stops_at_the_proven_row_count():
    """One row past the bound the integer sums are proven for keeps the host
    path (the rule reads the count; no program runs here)."""

    def suite(n):
        return EvaluationSuite(
            evaluators=[AUC],
            labels=np.broadcast_to(np.float64(1.0), (n,)),
            offsets=np.broadcast_to(np.float64(0.0), (n,)),
            weights=np.broadcast_to(np.float64(1.0), (n,)),
        )

    scores = jnp.zeros((DEVICE_AUC_MAX_ROWS + 1,), jnp.float32)
    assert suite(DEVICE_AUC_MAX_ROWS).metric_path(scores) == "device"
    assert suite(DEVICE_AUC_MAX_ROWS + 1).metric_path(scores) == "host"


@pytest.mark.parametrize(
    "names, path, reads",
    [
        (("AUC",), "device", 0),
        (("AUC", "RMSE"), "host", 1),
        (("RMSE", "AUC", "AUPR"), "host", 1),
        (("AUPR", "RMSE"), "host", 1),
    ],
)
def test_suite_reads_the_scores_once_or_not_at_all(rng, monkeypatch, names, path, reads):
    labels = _labels(rng, N)
    scores = rng.normal(size=N).astype(np.float32)
    suite = _suite(labels, evaluators=[evaluator_for_type(EvaluatorType[n]) for n in names])
    sizes = []
    device_get = jax.device_get

    def counted(x):
        sizes.append(int(np.size(x)))
        return device_get(x)

    monkeypatch.setattr(jax, "device_get", counted)
    placed = jnp.asarray(scores)
    assert suite.metric_path(placed) == path
    got = suite.evaluate(placed)
    assert sizes.count(N) == reads
    # every other read is the device AUC's handful of integers
    assert all(s == ev._LIMBS + 2 for s in sizes if s != N)
    assert len(sizes) == reads + ("AUC" in names)
    for name in names:
        fn = evaluator_for_type(EvaluatorType[name]).fn
        assert got[name] == fn(scores.astype(np.float64), labels, np.ones(N))


# ------------------------------------------------- a validating descent


@pytest.fixture(scope="module")
def glmix():
    rng = np.random.default_rng(36)
    X, X_re, users, y = glmix_data(rng, n=400)
    tr, va = slice(0, 300), slice(300, 400)
    val_ds = {
        "fixed": FixedEffectDataset(LabeledData.build(X[va], y[va]), feature_shard_id="global"),
        "per-user": build_random_effect_dataset(
            X_re[va], users[va], "userId", feature_shard_id="per-user", scoring_only=True
        ),
    }

    def descend():
        coords, _, _ = build_coordinates(X[tr], X_re[tr], users[tr], y[tr])
        suite = _suite(y[va])
        t0 = time.time_ns()
        result = run_coordinate_descent(
            coords, n_iterations=2, validation_datasets=val_ds, evaluation_suite=suite
        )
        paths = [r.attrs["metric_path"] for r in records(since_ns=t0, name="descent.evaluate")]
        return result, paths

    return descend


def _tables(model):
    from photon_ml_tpu.algorithm.coordinate import coefficient_arrays

    return [np.array(a) for _cid, m in model for a in coefficient_arrays(m)]


def test_descent_cannot_tell_the_device_metric_from_the_host_s(glmix, monkeypatch):
    device, device_paths = glmix()
    monkeypatch.setattr(EvaluationSuite, "_on_device", lambda self, evaluator, raw: False)
    host, host_paths = glmix()
    assert device_paths == ["device"] * 4 and host_paths == ["host"] * 4
    assert device.metrics_history == host.metrics_history
    assert len(device.metrics_history) == 4
    assert device.best_metric == host.best_metric and 0.5 < device.best_metric <= 1.0
    for a, b in zip(_tables(device.best_model), _tables(host.best_model)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(_tables(device.model), _tables(host.model)):
        np.testing.assert_array_equal(a, b)


def test_validating_descent_holds_under_sync_discipline(glmix):
    """The device→host reads of a validation round stay named
    ``jax.device_get`` calls, and a second suite over the same shapes traces
    nothing (the program is the module's, not the suite's)."""
    glmix()  # compiles
    with sync_discipline(what="a warmed validating descent") as region:
        result, paths = glmix()
        assert region.traces == 0
    assert paths == ["device"] * 4 and result.best_metric > 0.5
