"""The random-effect bucket layout as counts (data/random_effect.bucket_layout).

``build_random_effect_dataset`` assigns entities to padded ``[E_b, S_b, K_b]``
buckets so that ``padded cells + bucket_cost * buckets`` is least. Everything
here is a count on XLA:CPU: which entity sits where and how many rows that
pads. What a bucket costs on an accelerator is a chip reading (PERF.md
section 6, PR 32); the tests hand it over explicitly, because on the CPU the
backend's own answer is 0.
"""

import itertools
import json
import os
import sys
import time

import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.data.random_effect import (
    C_BUCKET_CELLS,
    _bucket_policy,
    _next_pow2,
    bucket_layout,
    build_random_effect_dataset,
)
from photon_ml_tpu.util.timed import records

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cells(layout) -> int:
    return sum(len(m) * s * k for (s, k), m in layout.items())


def padded_rows(layout) -> int:
    return sum(len(m) * s for (s, _k), m in layout.items())


def cost(layout, bucket_cost) -> float:
    return cells(layout) + bucket_cost * len(layout)


# ------------------------------------------------ (a) the benchmark cell's sizes


def cell_sizes() -> dict:
    """Rows per entity of ``glmix-ml20m.train``: the configuration's multiset,
    the same on every seed."""
    sys.path.insert(0, ROOT)
    try:
        from chipbench.generators.glmix_planted import entity_sizes
    finally:
        sys.path.remove(ROOT)
    with open(os.path.join(ROOT, "chipbench", "configs", "glmix-ml20m.json")) as f:
        cfg = json.load(f)
    return {
        tag: entity_sizes(cfg["n_train_rows"], n, cfg["entity_sizes"][tag])
        for tag, n in cfg["entities"].items()
    }


def test_the_cells_sizes_pad_half_of_what_the_entity_share_merge_padded():
    """The rule this replaced (powers of two, then every class under 5 % of the
    ENTITIES folded into a neighbour under a 1.0x budget) gave the cell
    33,426,376 padded rows in 14 buckets: 16,982,368 per-user, 16,444,008
    per-item (ledger, PR 31: ``ingest.re_padding_waste`` 64.091 %)."""
    old_rows, old_buckets, n_rows = 33_426_376, 14, 6_000_000
    sizes = cell_sizes()
    assert {t: len(s) for t, s in sizes.items()} == {"userId": 41_548, "itemId": 8_183}
    cost, pow2_heights = ACCELERATOR_POLICY
    layouts = {
        tag: bucket_layout(s, np.full(len(s), 8), cost, pow2_heights=pow2_heights)
        for tag, s in sizes.items()
    }
    rows = {tag: padded_rows(layout) for tag, layout in layouts.items()}
    buckets = sum(len(layout) for layout in layouts.values())
    assert sum(rows.values()) <= 18_000_000 < old_rows
    assert buckets <= 50
    # what the chip's ``ingest.re_padding_waste`` must read: 1 - active / padded
    # rows, averaged over the two coordinates by their active rows (equal here)
    waste = np.mean([1.0 - n_rows / r for r in rows.values()])
    assert waste <= 0.33
    assert (rows, buckets) == (EXPECTED_CELL_ROWS, EXPECTED_CELL_BUCKETS), (rows, buckets, waste)
    assert old_buckets == 14  # said, not computed: the old rule is gone


# what the backend answers off the CPU, and the layout that gives the cell (a
# CPU count): 17,908,680 padded rows, ``ingest.re_padding_waste`` 32.993 %
ACCELERATOR_POLICY = (C_BUCKET_CELLS, False)
EXPECTED_CELL_ROWS = {"userId": 8_954_184, "itemId": 8_954_496}
EXPECTED_CELL_BUCKETS = 16


# ---------------------------------------------------------------- (b) properties


def long_tailed(rng, n):
    return np.minimum((rng.pareto(1.1, n) * 6 + 1).astype(np.int64), 70_000)


def uniform(rng, n):
    return rng.integers(40, 60, n)


def one_giant(rng, n):
    return np.concatenate([rng.integers(3, 10, n - 1), [50_000]])


def allowed_height(n, pow2_heights):
    return _next_pow2(int(n), 8) if pow2_heights else max(8, -(-int(n) // 8) * 8)


@pytest.mark.parametrize("bucket_cost", [0.0, 300.0, 40_000.0, 4e6, 1e12])
@pytest.mark.parametrize("widths", ["one-width", "three-widths"])
@pytest.mark.parametrize("pow2_heights", [False, True], ids=["multiples-of-8", "powers-of-two"])
@pytest.mark.parametrize("sizes", [long_tailed, uniform, one_giant])
def test_layout_properties(rng, sizes, pow2_heights, widths, bucket_cost):
    rows = sizes(rng, 400)
    k_pads = (
        np.full(len(rows), 8)
        if widths == "one-width"
        else rng.choice([4, 8, 32], len(rows), p=[0.6, 0.3, 0.1])
    )
    layout = bucket_layout(rows, k_pads, bucket_cost, pow2_heights=pow2_heights)
    # a partition of the entities, each in a bucket that holds it
    members = np.concatenate(list(layout.values()))
    np.testing.assert_array_equal(np.sort(members), np.arange(len(rows)))
    for (s, k), m in layout.items():
        assert len(m) and np.all(np.diff(m) > 0)
        assert rows[m].max() <= s and k_pads[m].max() == k
        # an allowed height, and no taller than its tallest member needs
        assert s == allowed_height(rows[m].max(), pow2_heights)
    # no dearer than every occupied (height, width) its own bucket, nor than
    # one bucket per width, nor than one bucket of all
    s_pads = np.asarray([allowed_height(r, pow2_heights) for r in rows])
    rungs = {
        (int(s), int(k)): np.flatnonzero((s_pads == s) & (k_pads == k))
        for s, k in set(zip(s_pads.tolist(), k_pads.tolist()))
    }
    per_width = {
        (int(s_pads[k_pads == k].max()), int(k)): np.flatnonzero(k_pads == k)
        for k in np.unique(k_pads)
    }
    one = {(int(s_pads.max()), int(k_pads.max())): np.arange(len(rows))}
    for other in (rungs, per_width, one):
        # (across widths the joins are greedy, pair by pair: one bucket of all
        # is beaten for certain only where a bucket costs more than its cells)
        if other is not one or widths == "one-width" or bucket_cost >= cells(one):
            assert cost(layout, bucket_cost) <= cost(other, bucket_cost)
    if bucket_cost == 0.0:
        assert {key: m.tolist() for key, m in layout.items()} == {
            key: m.tolist() for key, m in rungs.items()
        }
    if bucket_cost == 1e12:
        assert len(layout) == 1


@pytest.mark.parametrize("bucket_cost", [0.0, 50.0, 400.0, 3000.0])
def test_layout_is_the_exact_minimum_within_one_width(rng, bucket_cost):
    """Against every contiguous partition of the occupied heights."""
    rows = np.concatenate([rng.integers(1, 9, 30), rng.integers(9, 300, 12), [1000, 1100]])
    s_pads = np.asarray([allowed_height(r, False) for r in rows])
    heights = np.unique(s_pads)
    best = np.inf
    for cuts in itertools.product([False, True], repeat=len(heights) - 1):
        tops = [h for h, cut in zip(heights, (*cuts, True)) if cut]
        which = np.searchsorted(tops, s_pads)
        total = sum(
            int((which == b).sum()) * int(top) * 4 + bucket_cost for b, top in enumerate(tops)
        )
        best = min(best, total)
    layout = bucket_layout(rows, np.full(len(rows), 4), bucket_cost)
    assert cost(layout, bucket_cost) == best


def test_a_width_class_joins_the_next_only_where_that_is_cheaper():
    rows = np.asarray([8] * 10 + [8] * 10)
    k_pads = np.asarray([4] * 10 + [8] * 10)
    # joining widens ten entities from 4 to 8 columns: 10 * 8 * 4 = 320 cells
    assert sorted(bucket_layout(rows, k_pads, 319.0)) == [(8, 4), (8, 8)]
    assert sorted(bucket_layout(rows, k_pads, 321.0)) == [(8, 8)]


def test_the_backend_answers_the_bucket_cost_unless_the_caller_does(monkeypatch):
    # XLA:CPU: a bucket is free, and every occupied power of two is one
    assert _bucket_policy(None) == (0.0, True)
    assert _bucket_policy(123.0) == (123.0, False)
    monkeypatch.setattr("jax.default_backend", lambda: "tpu")
    assert _bucket_policy(None) == ACCELERATOR_POLICY and C_BUCKET_CELLS > 0


# ------------------------------------------- (c) the dataset under a merged layout


def test_sample_slots_invert_sample_ids_under_a_merged_layout(rng):
    sizes = np.concatenate([rng.integers(1, 40, 60), [300, 700]])
    ids = rng.permutation(np.repeat(np.arange(len(sizes)), sizes))
    n = len(ids)
    X = sp.csr_matrix(rng.normal(size=(n, 3)))
    t0 = time.time_ns()
    free = build_random_effect_dataset(X, ids, "e", labels=np.zeros(n), bucket_cost=0.0)
    merged = build_random_effect_dataset(X, ids, "e", labels=np.zeros(n), bucket_cost=2000.0)
    assert 1 < len(merged.buckets) < len(free.buckets)
    spans = [r.attrs for r in records(since_ns=t0, name="ingest.re_buckets")]
    for ds, attrs in zip((free, merged), spans):
        total = sum(b.n_entities * b.shape[0] for b in ds.buckets)
        assert attrs["buckets"] == len(ds.buckets) and attrs["padded_rows"] == total
        assert ds.padding_waste == pytest.approx(1.0 - n / total)
        flat_ids = np.concatenate([np.asarray(b.sample_ids).reshape(-1) for b in ds.buckets])
        slots = np.asarray(ds.sample_slots)
        real = np.flatnonzero(flat_ids >= 0)
        np.testing.assert_array_equal(slots[flat_ids[real]], real)
        np.testing.assert_array_equal(flat_ids[slots], np.arange(n))
        # every entity's rows fit its bucket, in their own order
        for b in ds.buckets:
            held = (np.asarray(b.sample_ids) >= 0).sum(axis=1)
            np.testing.assert_array_equal(held, sizes[np.asarray(b.entity_rows)])
