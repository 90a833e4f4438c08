"""The readers of the program's own spans and counters, on a synthetic run
and a synthetic recorder: each returns the hand-computed number, and None
where its records are missing (or the program has no recorder at all)."""

import collections
import importlib
import json
import os

import pytest

from chipbench.run import HERE, load_reader

timed = importlib.import_module("photon_ml_tpu.util.timed")

MS = 1_000_000
EPOCH = 1_790_000_000 * 1_000_000_000  # the recorder's clock: time.time_ns()
NEW = [
    "descent.init_score_s", "descent.validate_s", "fit.host_s", "solver.fe_evaluations",
    "solver.re_lane_waste", "ingest.re_padding_waste", "ingest.re_index_s", "ingest.h2d_s",
    "kernels.re_update_roofline",
]


def _span(name, start_ms, end_ms, **attrs):
    return timed.Record(name, EPOCH + start_ms * MS, EPOCH + end_ms * MS, attrs)


def _counter(name, at_ms, value, **attrs):
    return timed.Record(name, EPOCH + at_ms * MS, EPOCH + at_ms * MS, attrs, float(value))


def _unit(t0):
    """One fit of 1000 ms starting at ``t0``: init 100, fe update 100, re
    update 400 (device busy 300 of it), validate 2 x 50, the rest host."""
    return [
        _span("descent.init", t0 + 10, t0 + 110),
        _span("descent.update", t0 + 120, t0 + 220, cid="fixed", kind="fe"),
        _span("descent.validate", t0 + 220, t0 + 270),
        _span("descent.update", t0 + 300, t0 + 700, cid="per-user", kind="re"),
        _span("descent.validate", t0 + 700, t0 + 750),
        _counter("solver.evaluations", t0 + 900, 23, cid="fixed", kind="fe"),
        _counter("solver.evaluations", t0 + 900, 12.5, cid="per-user", kind="re"),
        _counter("solver.lane_waste", t0 + 900, 0.5, cid="per-user", kind="re", rows=3000),
        _span("fit", t0, t0 + 1000),
    ]


def _setup():
    return [
        _span("ingest.re_index", 0, 4000), _span("ingest.re_buckets", 4000, 5000),
        _span("ingest.h2d", 5000, 5500),
        _counter("ingest.padding_waste", 5500, 0.25, cid="per-user", rows=1000),
        _counter("ingest.padding_waste", 5600, 0.75, cid="per-item", rows=3000),
    ] + _unit(10_000)  # the warm-up unit: before the window, never read


def _run():
    # the trace's clock starts with the profiler session: the window's first
    # fit at 50 ms; the device is busy 300 ms inside each re update
    shift = 20_000 - 50
    fits = [[(20_000 - shift) * MS, (21_000 - shift) * MS], [(21_000 - shift) * MS, (22_000 - shift) * MS]]
    busy = [[(t + 350 - shift) * MS, (t + 650 - shift) * MS] for t in (20_000, 21_000)]
    return {
        "units": 2,
        "cfg": {"n_train_rows": 1000, "random_effect_dim": 8},
        "peaks": {"hbm_bytes_per_s": 1e9},
        "trace": {"spans": {"fit": fits}, "busy": [busy]},
    }


@pytest.fixture()
def recorder(monkeypatch):
    records = collections.deque(_setup() + _unit(20_000) + _unit(21_000))
    monkeypatch.setattr(timed, "_records", records)
    return records


def test_each_reader_gives_the_hand_computed_number(recorder):
    run = _run()
    want = {
        "descent.init_score_s": 0.100,
        "descent.validate_s": 0.100,
        "fit.host_s": 1.0 - 0.1 - 0.5 - 0.1,
        "solver.fe_evaluations": 23.0,
        "solver.re_lane_waste": 50.0,
        "ingest.re_padding_waste": 100.0 * (0.25 * 1000 + 0.75 * 3000) / 4000,
        "ingest.re_index_s": 5.0,
        "ingest.h2d_s": 0.5,
        # per update 12.5 x 1000 x 8 x 4 B + 1000 x 72 B = 472,000 B; two
        # updates over 1 GB/s = 0.944 ms, over 0.6 s busy
        "kernels.re_update_roofline": 100.0 * 2 * 472_000 / 1e9 / 0.6,
    }
    for name in NEW:
        assert load_reader(name)(run) == pytest.approx(want[name]), name


def test_readers_return_none_where_their_records_are_missing(recorder, monkeypatch):
    run = _run()
    fits_only = collections.deque(r for r in recorder if r.name == "fit")
    monkeypatch.setattr(timed, "_records", fits_only)
    for name in NEW:
        assert load_reader(name)(run) is None, name
    monkeypatch.setattr(timed, "_records", collections.deque())  # not even the fits
    for name in NEW:
        assert load_reader(name)(run) is None, name


def test_a_program_without_the_recorder_reads_none(recorder, monkeypatch):
    monkeypatch.delattr(timed, "records")  # the parent's util/timed.py
    for name in NEW:
        assert load_reader(name)(_run()) is None, name


def test_every_new_metric_is_declared_with_its_files():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        with open(os.path.join(HERE, "metrics", name + ".json")) as f:
            meta = json.load(f)
        for key in ("unit", "layer", "moves", "source"):
            assert declared[name][key] == meta[key], (name, key)
        assert declared[name]["workloads"] == ["glmix-ml20m.train"]
