"""Data from the seed: the same seed gives the same bytes, every seed draws
other rows, and no seed changes the shape of the work (the multiset of rows per
entity is the configuration's)."""

import json
import os

import numpy as np
import pytest

from chipbench import generate, run
from chipbench.generators.glmix_planted import entity_sizes


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(run.HERE, "configs", "glmix-ml20m.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearsal"])
    return cfg


def test_sizes_follow_the_law_at_the_cells_own_size():
    with open(os.path.join(run.HERE, "configs", "glmix-ml20m.json")) as f:
        full = json.load(f)
    n = full["n_train_rows"]
    for tag, law in full["entity_sizes"].items():
        sizes = entity_sizes(n, full["entities"][tag], law)
        assert sizes.sum() == n and len(sizes) == full["entities"][tag]
        assert law["min"] <= sizes.min() and sizes.max() <= law["max"]
        assert sizes.max() > 50 * np.median(sizes)  # long-tailed, not uniform


def test_a_seed_changes_the_draws_and_not_the_shape(cfg):
    a, b, c = (generate.generate(cfg, s) for s in (2**31 + 5, 2**31 + 5, 8))
    for tag in cfg["entities"]:
        assert np.array_equal(a.train.ids[tag], b.train.ids[tag])
        assert not np.array_equal(a.train.ids[tag], c.train.ids[tag])
        assert np.array_equal(
            np.sort(np.bincount(a.train.ids[tag])), np.sort(np.bincount(c.train.ids[tag]))
        )
    assert np.array_equal(a.train.labels, b.train.labels)
    assert np.array_equal(np.asarray(a.train.fe_X), np.asarray(b.train.fe_X))
    assert not np.array_equal(np.asarray(a.train.fe_X), np.asarray(c.train.fe_X))
    assert not np.array_equal(a.validation.labels, c.validation.labels)
