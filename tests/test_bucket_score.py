"""The all-resident update program scores from its bucket blocks.

Where a dataset carries ``sample_slots`` (the inverse of its buckets'
``sample_ids``), the precision policy is the float32 reference and the
coordinate is not normalized (``algorithm/random_effect.bucket_score_slots``),
``solver_cache._re_coordinate_update_fn`` computes the ``[N]`` training score
as ``sum_k X_b * w_b`` over the blocks it has just solved and ONE ``[N]``
gather through the slots; the view kernel's ``[N, K]`` gather is gone from
the program. Everything else (passive rows, mesh placement, scoring-only
data: no slots; reduced precision, normalization: slots ignored) scores
through ``random_effect_view_score`` with the bits it always had. The bucket
score is taken only where it is, bit for bit, the view score of the returned
table, which is what a resumed fit recomputes: the resume gate is here too.
"""

import dataclasses
import re

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.algorithm import RandomEffectCoordinate, run_coordinate_descent
from photon_ml_tpu.data.random_effect import build_random_effect_dataset
from photon_ml_tpu.io.checkpoint import CoordinateDescentCheckpointer
from photon_ml_tpu.models.game import random_effect_view_score
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
from photon_ml_tpu.util.timed import records

CFG = GLMOptimizationConfiguration(
    optimizer_config=OptimizerConfig(max_iterations=50, tolerance=1e-9),
    regularization_context=RegularizationContext(RegularizationType.L2),
    regularization_weight=1.0,
)

N, N_USERS = 420, 12


def make_workload(rng, sparse=False):
    """Entity e gets ~(e+1) shares of the rows: several bucket shape classes.
    ``sparse``: 7 columns of which a row holds 2 or 3, fewer than its
    entity's K."""
    shares = np.repeat(np.arange(N_USERS), np.arange(1, N_USERS + 1))
    users = shares[np.arange(N) % len(shares)]
    if sparse:
        dense = rng.normal(size=(N, 7)) * (rng.uniform(size=(N, 7)) < 0.35)
        dense[:, 0] = 1.0
    else:
        x = rng.normal(size=(N, 2))
        dense = np.concatenate([np.ones((N, 1)), 2.0 * x + 0.5], axis=1)
    y = (dense[:, 1] + 0.7 * rng.normal(size=N_USERS)[users] > 0).astype(np.float64)
    stats = FeatureDataStatistics.compute(dense, intercept_index=0)
    norm = NormalizationContext.build(NormalizationType.STANDARDIZATION, stats)
    return sp.csr_matrix(dense), users, y, norm


def build_dataset(workload, normalization=None, **kwargs):
    X_re, users, y, _ = workload
    return build_random_effect_dataset(
        X_re, users, "userId", feature_shard_id="per-user", labels=y,
        normalization=normalization,
        intercept_index=0 if normalization is not None else None,
        **kwargs,
    )


def build_coord(ds, normalization=None, base_offsets=None, **kwargs):
    return RandomEffectCoordinate(
        coordinate_id="per-user", dataset=ds,
        task=TaskType.LOGISTIC_REGRESSION, configuration=CFG,
        base_offsets=(
            jnp.zeros(ds.n_samples, dtype=ds.sample_vals.dtype)
            if base_offsets is None
            else base_offsets
        ),
        normalization=normalization,
        **kwargs,
    )


def first_update(coord, partial=None):
    """One ``update_and_score`` from the zero model, as the descent loop makes
    it: ``(model, score)``."""
    n = int(coord.zero_model_score().shape[0])
    if partial is None:
        partial = jnp.zeros(n, dtype=coord.dataset.sample_vals.dtype)
    model, score, tracker = coord.update_and_score(
        coord.initialize_model(), partial, coord.zero_model_score()
    )
    assert bool(np.asarray(tracker.guard_ok))
    return model, score


# ------------------------------------------------------------ (a) the index


def _none(users):
    return np.zeros(len(users), dtype=bool)


def _under_twenty_rows(users):
    return np.bincount(users)[users] < 20


def _of_users_3_and_7(users):
    return np.isin(users, [3, 7])


@pytest.mark.parametrize(
    "sparse,kwargs,bucketless",
    [
        (False, {}, _none),
        (True, {}, _none),
        (False, {"features_max": 2}, _none),
        (False, {"active_data_lower_bound": 20}, _under_twenty_rows),
        (False, {"exclude_entities": {3, 7}}, _of_users_3_and_7),
        (False, {"entity_order": [9, 2, 11, 0]}, _none),
    ],
    ids=[
        "dense", "sparse-rows", "pearson-features-max", "lower-bound",
        "exclude-entities", "entity-order",
    ],
)
def test_sample_slots_invert_sample_ids(rng, sparse, kwargs, bucketless):
    """Slot ``base_b + e * S_b + s`` of the concatenated blocks holds sample
    ``sample_ids[e, s]``; a sample of no bucket points at the one zero slot
    past the blocks."""
    workload = make_workload(rng, sparse=sparse)
    ds = build_dataset(workload, **kwargs)
    slots = np.asarray(ds.sample_slots)
    assert slots.shape == (N,) and slots.dtype == np.int32
    total = sum(b.n_entities * b.shape[0] for b in ds.buckets)
    flat_ids = np.concatenate(
        [np.asarray(b.sample_ids).reshape(-1) for b in ds.buckets]
    )
    assert flat_ids.shape == (total,)
    in_bucket = slots < total
    # the inverse, both ways: every real slot is named by its own sample...
    real = np.flatnonzero(flat_ids >= 0)
    np.testing.assert_array_equal(slots[flat_ids[real]], real)
    # ...and every bucketed sample names a slot that holds it
    np.testing.assert_array_equal(
        flat_ids[slots[in_bucket]], np.flatnonzero(in_bucket)
    )
    # the rest: exactly the samples whose entity trains no model, at slot T
    np.testing.assert_array_equal(slots[~in_bucket], total)
    np.testing.assert_array_equal(~in_bucket, bucketless(workload[1]))
    assert bool((~in_bucket).any()) == (bucketless is not _none)
    np.testing.assert_array_equal(~in_bucket, np.asarray(ds.sample_entity_rows) < 0)
    assert ds.n_active_samples == int(in_bucket.sum())


# ------------------------------------------------- (b) the program's score


def coord_score(ds, norm, model):
    """``score(model)`` of a new coordinate: what a warm start or a resume
    recomputes from the stored table."""
    return build_coord(ds, normalization=norm).score(model)


@pytest.mark.parametrize("with_norm", [False, True], ids=["raw", "norm"])
@pytest.mark.parametrize("with_per_entity", [False, True], ids=["uniform", "per-entity-l2"])
@pytest.mark.parametrize(
    "variance",
    [VarianceComputationType.NONE, VarianceComputationType.SIMPLE],
    ids=["novar", "simplevar"],
)
def test_bucket_score_is_the_view_score_of_the_updated_table(
    rng, with_norm, with_per_entity, variance
):
    """On raw blocks the program scores from them, and its score is the view
    kernel's score of the table it returns: what ``coord.score(model)`` gives
    a resumed fit, so bit for bit (an equality of two differently fused
    programs, observed on XLA:CPU and the v5e, held here). A normalized
    coordinate keeps the view path although its dataset has slots (its
    blocks would score in the solve's space, ulps apart). Either way the
    FIRST update's coefficients and variances are the slot-less dataset's."""
    workload = make_workload(rng)
    norm = workload[-1] if with_norm else None
    per_entity = (
        {int(e): float(v) for e, v in enumerate(rng.uniform(0.4, 2.5, size=N_USERS))}
        if with_per_entity
        else None
    )
    ds = build_dataset(workload, normalization=norm)
    partial = jnp.asarray(rng.normal(size=N), dtype=ds.sample_vals.dtype)

    def run(dataset):
        coord = build_coord(
            dataset, normalization=norm, per_entity_reg_weights=per_entity,
            variance_computation=variance,
        )
        return coord.score_path, *first_update(coord, partial)

    path, model, score = run(ds)
    view_path, view_model, view_score = run(dataclasses.replace(ds, sample_slots=None))
    assert (path, view_path) == ("view" if with_norm else "bucket", "view")
    np.testing.assert_array_equal(np.asarray(model.coeffs), np.asarray(view_model.coeffs))
    if variance != VarianceComputationType.NONE:
        np.testing.assert_array_equal(
            np.asarray(model.variances), np.asarray(view_model.variances)
        )
    assert float(jnp.abs(model.coeffs).max()) > 1e-3  # not vacuous
    assert score.dtype == view_score.dtype and score.shape == (N,)
    want = np.asarray(random_effect_view_score(model.coeffs, *ds.scoring_view()))
    np.testing.assert_array_equal(np.asarray(view_score), want)
    np.testing.assert_allclose(np.asarray(score), want, rtol=0, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(score), want)
    np.testing.assert_array_equal(np.asarray(score), np.asarray(coord_score(ds, norm, model)))


def test_bucket_score_zeroes_samples_of_no_bucket(rng):
    """Samples whose entity trains no model score exactly 0, as the view's
    ``entity_rows == -1`` makes them."""
    ds = build_dataset(make_workload(rng), active_data_lower_bound=20)
    coord = build_coord(ds)
    assert coord.score_path == "bucket"
    model, score = first_update(coord)
    no_model = np.asarray(ds.sample_entity_rows) < 0
    assert 0 < no_model.sum() < N
    np.testing.assert_array_equal(np.asarray(score)[no_model], 0.0)
    want = np.asarray(random_effect_view_score(model.coeffs, *ds.scoring_view()))
    np.testing.assert_allclose(np.asarray(score), want, rtol=0, atol=2e-6)


# ----------------------------------------------------------- (c) the bypasses


def _capped(workload):
    ds = build_dataset(workload, active_data_upper_bound=16)
    assert ds.n_passive_samples > 0
    return build_coord(ds)


def _mesh_placed(workload):
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.parallel.placement import (
        pad_and_shard_vector,
        place_random_effect_dataset,
    )

    host_ds = build_dataset(workload)
    assert host_ds.sample_slots is not None
    mesh = make_mesh(8)
    ds = place_random_effect_dataset(host_ds, mesh)
    base = pad_and_shard_vector(np.zeros(N), mesh, dtype=ds.sample_vals.dtype)
    return build_coord(ds, base_offsets=base)


def _bf16(workload):
    ds = build_dataset(workload)
    assert ds.sample_slots is not None  # carried, and ignored
    return build_coord(ds, precision="bf16")


def _normalized(workload):
    ds = build_dataset(workload, normalization=workload[-1])
    assert ds.sample_slots is not None  # carried, and ignored
    return build_coord(ds, normalization=workload[-1])


@pytest.mark.parametrize(
    "build,has_slots",
    [(_capped, False), (_mesh_placed, False), (_bf16, True), (_normalized, True)],
    ids=["passive-rows", "mesh-placed", "bf16-storage", "normalized"],
)
def test_bypasses_score_through_the_view(rng, eight_devices, build, has_slots):
    """Passive rows sit in no bucket, a placed dataset's blocks are padded
    past the index, a reduced policy has to score the ROUNDED table and a
    normalized coordinate's blocks live in another space than the stored
    table: all four keep the view kernel, whose score of the returned table
    they give bit for bit, and their ``descent.update`` spans say so."""
    coord = build(make_workload(rng))
    ds = coord.dataset
    assert (ds.sample_slots is not None) == has_slots
    assert coord.score_path == "view"
    assert coord._fused_update_static()["sample_slots"] is None
    since = records(name="descent.update")
    result = run_coordinate_descent({"per-user": coord}, n_iterations=2)
    spans = records(name="descent.update")[len(since):]
    assert [s.attrs["score_path"] for s in spans] == ["view", "view"]
    model = result.model.get_model("per-user")
    score = np.asarray(result.training_scores["per-user"])
    entity_rows, local_cols, vals = ds.scoring_view()
    want = random_effect_view_score(
        model.coeffs.astype(jnp.float32), entity_rows, local_cols,
        coord.precision.to_storage(vals).astype(jnp.float32),
    )
    np.testing.assert_array_equal(score, np.asarray(want))
    assert np.abs(score).max() > 1e-3
    if ds.n_passive_samples:
        # the rows only the view reaches: scored, in no bucket
        in_bucket = np.zeros(score.shape[0], dtype=bool)
        for b in ds.buckets:
            ids = np.asarray(b.sample_ids).reshape(-1)
            in_bucket[ids[ids >= 0]] = True
        assert (~in_bucket).sum() == ds.n_passive_samples
        assert np.abs(score[~in_bucket]).max() > 1e-3


def test_descent_update_span_says_bucket(rng):
    coord = build_coord(build_dataset(make_workload(rng)))
    since = records(name="descent.update")
    run_coordinate_descent({"per-user": coord}, n_iterations=2)
    spans = records(name="descent.update")[len(since):]
    assert [(s.attrs["kind"], s.attrs["score_path"]) for s in spans] == [
        ("re", "bucket"), ("re", "bucket"),
    ]


def test_score_path_builds_no_program_input(rng):
    """The descent loop reads ``score_path`` BEFORE it opens the update's
    span: the answer must not build the update program's static inputs
    there, outside the span that times them."""
    coord = build_coord(build_dataset(make_workload(rng)))
    assert coord.score_path == "bucket"
    assert coord._fused_static is None


@pytest.mark.parametrize("how", ["normalized", "bf16"])
def test_update_body_refuses_slots_it_must_not_score_from(rng, how):
    """``bucket_score_slots`` never hands them over; a caller that does is
    told, not answered with another score than the stored table's."""
    coord = (_normalized if how == "normalized" else _bf16)(make_workload(rng))
    st = coord._fused_update_static()
    program, dtype, *_ = coord._resolve_update_program()
    ds = coord.dataset
    with pytest.raises(ValueError, match="sample_slots"):
        program.lower(
            jnp.zeros((ds.n_entities, ds.max_k), dtype=dtype), coord.zero_model_score(),
            None, coord.base_offsets, st["l2_rows"], st["l1"], st["buckets"],
            st["norm_tables"], st["view"], ds.sample_slots,
        )


# ------------------------------------------------------------- the resume gate


@pytest.mark.parametrize(
    "with_norm,path", [(False, "bucket"), (True, "view")], ids=["raw-bucket", "norm-view"]
)
def test_resumed_descent_is_bit_identical(rng, tmp_path, with_norm, path):
    """An uninterrupted descent CARRIES each update program's score; a resumed
    one recomputes ``coord.score(model)`` from the checkpointed tables and
    goes on. Two random effects that see each other's scores (dense rows per
    user, sparse rows per item), three passes against two, a restore and the
    third: the same tables and scores bit for bit, on the bucket path (raw)
    and where normalization keeps the view path."""
    X_user, users, y, norm_user = make_workload(rng)
    X_item, _, _, norm_item = make_workload(rng, sparse=True)
    items = (np.arange(N) * 7) % 9
    workloads = {
        "per-user": (X_user, users, y, norm_user),
        "per-item": (X_item, items, y, norm_item),
    }

    def coordinates():
        out = {}
        for cid, workload in workloads.items():
            norm = workload[-1] if with_norm else None
            coord = build_coord(build_dataset(workload, normalization=norm), normalization=norm)
            assert coord.score_path == path
            out[cid] = coord
        return out

    full = run_coordinate_descent(coordinates(), n_iterations=3)
    ckpt = str(tmp_path / "ck")
    run_coordinate_descent(
        coordinates(), n_iterations=2, checkpointer=CoordinateDescentCheckpointer(ckpt)
    )
    since = records(name="descent.update")
    resumed = run_coordinate_descent(
        coordinates(), n_iterations=3, checkpointer=CoordinateDescentCheckpointer(ckpt)
    )
    spans = records(name="descent.update")[len(since):]
    assert [(s.attrs["iteration"], s.attrs["score_path"]) for s in spans] == [(2, path)] * 2
    for cid in workloads:
        got = np.asarray(resumed.model.get_model(cid).coeffs)
        want = np.asarray(full.model.get_model(cid).coeffs)
        assert np.abs(want).max() > 1e-3
        np.testing.assert_array_equal(got, want, err_msg=cid)
        np.testing.assert_array_equal(
            np.asarray(resumed.training_scores[cid]), np.asarray(full.training_scores[cid]),
            err_msg=cid,
        )


def test_scoring_only_dataset_has_no_slots(rng):
    X_re, users, _y, _ = make_workload(rng)
    ds = build_random_effect_dataset(X_re, users, "userId", scoring_only=True)
    assert ds.sample_slots is None and not ds.buckets


# ------------------------------------------------- (d) the lowered program

_GATHER_RESULT = re.compile(r'"stablehlo\.gather"\(.*->\s*tensor<([0-9x]*)x[a-z]+[0-9]+>')


def gather_result_shapes(coord) -> list:
    """Result shapes of every gather in the update program as traced."""
    text = coord.lowered_update_program().as_text()
    n_ops = text.count('"stablehlo.gather"(')
    shapes = [
        tuple(int(d) for d in m.group(1).split("x"))
        for m in map(_GATHER_RESULT.search, text.splitlines())
        if m
    ]
    assert n_ops and len(shapes) == n_ops, "the gather pattern missed an op"
    return shapes


@pytest.mark.parametrize("with_norm", [False, True], ids=["raw", "norm"])
def test_update_program_holds_one_sample_gather_and_no_table_gather(rng, with_norm):
    """Scoring from its blocks (raw) the program holds no gather with an
    ``[N, ...]`` result (the view's ``coeffs[rows]`` and ``take_along_axis``)
    and exactly one ``[N]`` gather more than the view program, which has
    none; the per-bucket gathers are the same. A normalized coordinate's
    program is the view program, slots or not."""
    workload = make_workload(rng)
    norm = workload[-1] if with_norm else None
    ds = build_dataset(workload, normalization=norm)
    assert all(N not in (b.n_entities, b.n_entities * b.shape[0]) for b in ds.buckets)
    with_slots = gather_result_shapes(build_coord(ds, normalization=norm))
    with_view = gather_result_shapes(
        build_coord(dataclasses.replace(ds, sample_slots=None), normalization=norm)
    )

    def per_sample(shapes):
        return sorted(s for s in shapes if s[0] == N)

    assert per_sample(with_view) == sorted([(N, ds.max_k), (N, ds.sample_vals.shape[1])])
    assert per_sample(with_slots) == (per_sample(with_view) if with_norm else [(N,)])
    assert sorted(s for s in with_slots if s[0] != N) == sorted(
        s for s in with_view if s[0] != N
    )
