"""The random-effect index by rows against the index by non-zeros.

``build_random_effect_dataset`` indexes a shard whose rows all store the same
columns by its N rows (``_index_rows``): one grouping of the samples, row
copies into the blocks. Any other shard, and every Pearson-selected one, goes
through the index by stored entries (``_index_nnz``). Both must build the
same dataset, bit for bit: every array, its dtype and shape, the layout on
the ``ingest.re_buckets`` span. Each case here builds one input through both:
the index steps called directly on one grouping, and the whole function with
the shard's shared columns hidden from it (so that it takes the non-zero
index) against the function as it stands. The last test fits each benchmark
cell's configuration at its rehearsal size on both and compares the fits.
"""

import importlib
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.data import random_effect
from photon_ml_tpu.data.random_effect import (
    _entity_seed,
    _group_by_entity,
    _index_nnz,
    _index_rows,
    _shared_columns,
    build_random_effect_dataset,
)
from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.util.timed import records

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def uniform_csr(vals: np.ndarray, cols, d: int) -> sp.csr_matrix:
    """Every row stores the columns ``cols``: the CSR the benchmark hands over."""
    n, k = vals.shape
    return sp.csr_matrix(
        (vals.reshape(-1), np.tile(np.asarray(cols, dtype=np.int32), n), np.arange(0, n * k + 1, k)),
        shape=(n, d),
    )


def long_tailed_ids(rng, n: int, n_entities: int) -> np.ndarray:
    """Entity ids with rows per entity from 1 to about n / 4."""
    weights = 1.0 / np.arange(1, n_entities + 1) ** 1.2
    return rng.choice(n_entities, size=n, p=weights / weights.sum())


def build(X, ent, *, index: str, **kw):
    """``(dataset, [attrs of the two index spans])``, through the index asked
    for: the non-zero one by hiding the shard's shared columns."""
    saved = random_effect._shared_columns
    if index == "nnz":
        random_effect._shared_columns = lambda X: None
    t0 = time.time_ns()
    try:
        ds = build_random_effect_dataset(X, ent, "u", **kw)
    finally:
        random_effect._shared_columns = saved
    spans = [
        dict(r.attrs)
        for r in records(since_ns=t0)
        if r.name in ("ingest.re_index", "ingest.re_buckets")
    ]
    return ds, spans


def host(x):
    return None if x is None else np.asarray(jax.device_get(x))


def assert_same_array(a, b, what):
    a, b = host(a), host(b)
    if a is None or b is None:
        assert a is None and b is None, what
        return
    assert (a.dtype, a.shape) == (b.dtype, b.shape), what
    assert a.tobytes() == b.tobytes(), what  # bit for bit: -0.0 is not +0.0


def assert_same_dataset(a, b):
    assert a.entity_ids == b.entity_ids
    assert [type(e) for e in a.entity_ids] == [type(e) for e in b.entity_ids]
    assert (a.n_samples, a.n_active_samples, a.n_passive_samples) == (
        b.n_samples, b.n_active_samples, b.n_passive_samples,
    )
    assert a.padding_waste == b.padding_waste
    assert len(a.buckets) == len(b.buckets)
    for i, (x, y) in enumerate(zip(a.buckets, b.buckets)):
        for field in ("entity_rows", "X", "labels", "weights", "sample_ids"):
            assert_same_array(getattr(x, field), getattr(y, field), f"bucket {i} {field}")
    for field in (
        "proj_indices", "sample_entity_rows", "sample_local_cols", "sample_vals", "sample_slots",
    ):
        assert_same_array(getattr(a, field), getattr(b, field), field)


def _harness_form(rng):
    n, k = 600, 8
    vals = rng.normal(size=(n, k)).astype(np.float32)
    return uniform_csr(vals, np.arange(k), k), long_tailed_ids(rng, n, 40), {}


def _noncontiguous(rng):
    n = 500
    X = uniform_csr(rng.normal(size=(n, 4)), [1, 4, 5, 9], 12)
    return X, long_tailed_ids(rng, n, 30), {}


def _stored_zeros(rng):
    n, k = 400, 6
    vals = rng.normal(size=(n, k)).astype(np.float32)
    vals[rng.random((n, k)) < 0.2] = 0.0
    vals[rng.random((n, k)) < 0.2] = -0.0
    vals[:, 0] = -0.0  # a whole column of negative zeros
    return uniform_csr(vals, np.arange(k), k), long_tailed_ids(rng, n, 25), {}


def _binding_caps(rng):
    n, k = 600, 5
    X = uniform_csr(rng.normal(size=(n, k)), np.arange(k), k)
    return X, long_tailed_ids(rng, n, 30), {"active_data_upper_bound": 7}


def _lower_bound(rng):
    n, k = 500, 5
    X = uniform_csr(rng.normal(size=(n, k)), np.arange(k), k)
    kw = {"active_data_lower_bound": 6, "active_data_upper_bound": 40}
    return X, long_tailed_ids(rng, n, 40), kw


def _order_and_exclusions(rng):
    n, k = 500, 4
    X = uniform_csr(rng.normal(size=(n, k)), np.arange(k), k)
    ent = long_tailed_ids(rng, n, 30)
    kw = {
        "entity_order": [int(e) for e in rng.permutation(40)[:25]],  # 10 not in the data
        "exclude_entities": {0, 3, 17},
    }
    return X, ent, kw


def _normalization(rng):
    n, d = 500, 7
    vals = rng.normal(size=(n, d))
    vals[:, d - 1] = 1.0  # the intercept
    vals[rng.random((n, d)) < 0.1] = -0.0
    factors = rng.uniform(0.5, 2.0, d)
    shifts = rng.normal(size=d)
    factors[d - 1], shifts[d - 1] = 1.0, 0.0
    norm = NormalizationContext(factors=factors, shifts=shifts, intercept_index=d - 1)
    kw = {"normalization": norm, "intercept_index": d - 1, "active_data_upper_bound": 12}
    return uniform_csr(vals, np.arange(d), d), long_tailed_ids(rng, n, 30), kw


def _scoring_only(rng):
    n, k = 400, 8
    X = uniform_csr(rng.normal(size=(n, k)).astype(np.float32), np.arange(k), k)
    return X, long_tailed_ids(rng, n, 30), {"scoring_only": True, "active_data_upper_bound": 3}


def _empty(rng):
    return uniform_csr(np.zeros((0, 8), np.float32), np.arange(8), 8), np.zeros(0, np.int64), {}


def _single_entity(rng):
    n, k = 50, 3
    X = uniform_csr(rng.normal(size=(n, k)), [0, 2, 5], 6)
    return X, np.full(n, 7), {"active_data_upper_bound": 20}


def _string_ids(rng):
    n, k = 400, 8
    X = uniform_csr(rng.normal(size=(n, k)).astype(np.float32), np.arange(k), k)
    ent = np.asarray([f"user-{e}" for e in long_tailed_ids(rng, n, 30)])
    return X, ent, {"active_data_upper_bound": 9}


def _object_ids(rng):
    n, k = 300, 4
    X = uniform_csr(rng.normal(size=(n, k)), np.arange(k), k)
    ent = np.asarray([f"e{e}" for e in long_tailed_ids(rng, n, 20)], dtype=object)
    return X, ent, {"entity_order": ["e5", "e1", "e99"]}


CASES = {
    "harness_form": _harness_form,
    "noncontiguous_columns": _noncontiguous,
    "stored_zeros_and_negative_zeros": _stored_zeros,
    "binding_caps": _binding_caps,
    "lower_bound_drops_entities": _lower_bound,
    "entity_order_and_exclusions": _order_and_exclusions,
    "normalization_factors_and_shifts": _normalization,
    "scoring_only": _scoring_only,
    "empty_input": _empty,
    "single_entity": _single_entity,
    "string_ids": _string_ids,
    "object_ids": _object_ids,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_row_index_builds_the_non_zero_index_dataset(case, rng):
    X, ent, kw = CASES[case](rng)
    n = X.shape[0]
    labels = (rng.random(n) < 0.4).astype(np.float64)
    weights = rng.uniform(0.5, 2.0, n)
    kw = dict(kw, labels=labels, weights=weights, dtype=jnp.float64, bucket_cost=2e3)

    # the two index steps, called directly on one grouping
    shared = _shared_columns(X)
    assert shared is not None
    scoring = kw.get("scoring_only", False)
    groups = _group_by_entity(
        ent,
        upper=None if scoring else kw.get("active_data_upper_bound"),
        lower=1 if scoring else kw.get("active_data_lower_bound", 1),
        exclude=kw.get("exclude_entities"),
        entity_order=kw.get("entity_order"),
        seed=0,
    )
    by_rows = _index_rows(X, groups, shared)
    by_nnz = _index_nnz(X, groups, None, labels, None)
    for field in ("cols", "k_counts", "s_cols", "s_vals"):
        a, b = getattr(by_rows, field), getattr(by_nnz, field)
        assert (a.dtype, a.shape, a.tobytes()) == (b.dtype, b.shape, b.tobytes()), field

    # the whole function, each way
    rows_ds, rows_spans = build(X, ent, index="rows", **kw)
    nnz_ds, nnz_spans = build(X, ent, index="nnz", **kw)
    assert [s.pop("index") for s in rows_spans] == ["rows", "rows"]
    assert [s.pop("index") for s in nnz_spans] == ["nnz", "nnz"]
    assert rows_spans == nnz_spans  # re_type, buckets, padded_rows, lanes
    assert_same_dataset(rows_ds, nnz_ds)


def _ragged(rng):
    X = sp.random(300, 10, density=0.4, format="csr", random_state=3)
    return X, long_tailed_ids(rng, 300, 20), {}


def _pearson(rng):
    X = uniform_csr(rng.normal(size=(300, 6)), np.arange(6), 6)
    return X, long_tailed_ids(rng, 300, 20), {"features_max": 3}


def _re_csr(rng):
    sys.path.insert(0, ROOT)
    try:
        from chipbench.generate import Table
    finally:
        sys.path.remove(ROOT)
    n = 300
    table = Table(
        fe_X=None,
        labels=np.zeros(n),
        re_vals=rng.normal(size=(n, 8)).astype(np.float32),
    )
    return table.re_csr(), long_tailed_ids(rng, n, 20), {}


@pytest.mark.parametrize(
    "case, index",
    [("ragged_csr", "nnz"), ("pearson_selected", "nnz"), ("harness_re_csr", "rows")],
)
def test_the_spans_name_the_index_that_ran(case, index, rng):
    X, ent, kw = {"ragged_csr": _ragged, "pearson_selected": _pearson, "harness_re_csr": _re_csr}[
        case
    ](rng)
    labels = (rng.random(X.shape[0]) < 0.5).astype(np.float64)
    t0 = time.time_ns()
    build_random_effect_dataset(X, ent, "u", labels=labels, **kw)
    spans = [r for r in records(since_ns=t0) if r.name.startswith("ingest.re_")]
    assert [(r.name, r.attrs["index"]) for r in spans] == [
        ("ingest.re_index", index), ("ingest.re_buckets", index),
    ]


def test_a_shard_with_differing_rows_shares_no_columns():
    same = uniform_csr(np.ones((3, 2)), [0, 2], 4)
    assert _shared_columns(same).tolist() == [0, 2]
    assert _shared_columns(sp.csr_matrix((0, 4))).tolist() == []
    unsorted = sp.csr_matrix((np.ones(4), [2, 0, 2, 0], [0, 2, 4]), shape=(2, 4))
    other_columns = sp.csr_matrix((np.ones(4), [0, 1, 0, 2], [0, 2, 4]), shape=(2, 4))
    other_counts = sp.csr_matrix((np.ones(3), [0, 1, 0], [0, 2, 3]), shape=(2, 4))
    duplicated = sp.csr_matrix((np.ones(4), [1, 1, 1, 1], [0, 2, 4]), shape=(2, 4))
    for X in (unsorted, other_columns, other_counts, duplicated):
        assert _shared_columns(X) is None


def _groups_one_by_one(ent, upper, lower, exclude, entity_order, seed):
    """The grouping entity by entity, as a plain loop: each entity's rows,
    its seeded reservoir cap, the filters, the row order."""
    active, scale, passive = {}, {}, 0
    for e in sorted(set(ent.tolist())):
        rows = np.flatnonzero(ent == e)
        if upper is not None and len(rows) > upper:
            rng = np.random.default_rng(_entity_seed(str(e), seed))
            keep = rows[np.argsort(rng.random(len(rows)), kind="mergesort")[:upper]]
            active[e], scale[e] = np.sort(keep), len(rows) / upper
            passive += len(rows) - upper
        else:
            active[e], scale[e] = rows, 1.0
    entities = [e for e in active if len(active[e]) >= lower and e not in (exclude or ())]
    if entity_order is not None:
        known = [e for e in entity_order if e in set(entities)]
        entities = known + sorted(e for e in entities if e not in set(known))
    row_of = {e: i for i, e in enumerate(entities)}
    return (
        entities,
        [len(active[e]) for e in entities],
        [scale[e] for e in entities],
        np.concatenate([active[e] for e in entities]) if entities else np.zeros(0, np.int64),
        [row_of.get(e, -1) for e in ent.tolist()],
        passive,
    )


@pytest.mark.parametrize(
    "upper, lower, exclude, entity_order",
    [
        (None, 1, None, None),
        (5, 1, None, None),
        (4, 3, {2, 9}, None),
        (6, 2, {1}, [7, 3, 100, 0]),
    ],
)
def test_the_grouping_is_the_entity_by_entity_one(upper, lower, exclude, entity_order, rng):
    ent = long_tailed_ids(rng, 700, 30)
    g = _group_by_entity(
        ent, upper=upper, lower=lower, exclude=exclude, entity_order=entity_order, seed=11
    )
    entities, lens, scale, act_rows, sample_rows, passive = _groups_one_by_one(
        ent, upper, lower, exclude, entity_order, 11
    )
    assert [int(e) for e in g.entities] == entities
    assert g.lens.tolist() == lens and g.scale.tolist() == scale
    assert g.act_rows.tolist() == act_rows.tolist()
    assert g.sample_rows.tolist() == sample_rows
    assert g.n_passive == passive


@pytest.fixture(scope="module")
def chipbench_modules():
    sys.path.insert(0, ROOT)
    try:
        yield {
            name: importlib.import_module("chipbench." + name)
            for name in ("run", "entry", "generate", "compare")
        }
    finally:
        sys.path.remove(ROOT)


@pytest.mark.parametrize(
    "workload, reference, numbers",
    [
        ("glmix-ml20m.train", "reference", "compare"),
        ("glmix-ml20m-rating.train_rmse", "reference_squared", "compare_squared"),
    ],
)
def test_a_rehearsal_fit_is_the_same_on_both_indexes(
    workload, reference, numbers, chipbench_modules, monkeypatch
):
    """Each benchmark cell's configuration at its rehearsal size: the same
    coefficients, training scores and verdict of the comparison with the
    plain reference, whichever index built the datasets."""
    run, entry, generate, compare = (
        chipbench_modules[k] for k in ("run", "entry", "generate", "compare")
    )
    cell = run.load_cell(workload)
    cfg = dict(cell["cfg"], **cell["cfg"]["rehearsal"])
    dataset = generate.generate(cfg, 2**31 + 38)
    fits = {}
    for index in ("rows", "nnz"):
        with monkeypatch.context() as patch:
            if index == "nnz":
                patch.setattr(random_effect, "_shared_columns", lambda X: None)
            t0 = time.time_ns()
            system = entry.System(cfg, dataset)
            system.prepare()
            spans = {r.attrs["index"] for r in records(since_ns=t0, name="ingest.re_index")}
            assert spans == {index}
            fits[index] = system.fit_unit()
            system.release()

    from photon_ml_tpu.algorithm.coordinate import coefficient_arrays

    for a, b in zip(fits["rows"], fits["nnz"]):
        for (cid, m_a), (cid_b, m_b) in zip(a.model, b.model):
            assert cid == cid_b
            for x, y in zip(coefficient_arrays(m_a), coefficient_arrays(m_b)):
                assert_same_array(x, y, f"{cid} coefficients")
        assert a.descent.training_scores.keys() == b.descent.training_scores.keys()
        for cid, score in a.descent.training_scores.items():
            assert_same_array(score, b.descent.training_scores[cid], f"{cid} training scores")

    kind = importlib.import_module("chipbench.kinds." + cell["traffic_spec"]["kind"])
    ref = importlib.import_module("chipbench." + reference).fit(cfg, dataset, dtype=cfg["precision"])
    verdicts = {}
    for index, results in fits.items():
        units, losses = kind.collect(cfg, dataset, [results])
        values = importlib.import_module("chipbench." + numbers).numbers(units[-1], ref, losses)
        values["unit_repeat"] = compare.unit_repeat(units)
        verdicts[index] = compare.judge(values, cell["limits"])
    assert verdicts["rows"] == verdicts["nnz"]
