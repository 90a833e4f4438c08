"""The readers of set-up's compile pipeline (``jit.*``) on synthetic records:
each gives the hand-computed number, reads set-up's records alone, gives each
second to one stage, and returns None where the program records no stage (the
parent of the PR that added them) or has no recorder."""

import collections
import importlib
import json
import os

import pytest

from chipbench.run import HERE, load_reader

timed = importlib.import_module("photon_ml_tpu.util.timed")

MS = 1_000_000
EPOCH = 1_790_000_000 * 1_000_000_000  # the recorder's clock: time.time_ns()
NEW = ["jit.trace_s", "jit.lower_s", "jit.compile_s", "jit.programs"]


def _span(name, start_ms, end_ms, **attrs):
    return timed.Record(name, EPOCH + start_ms * MS, EPOCH + end_ms * MS, attrs)


def _setup():
    return [
        # a trace that calls a jitted function on concrete values: it lowers
        # and compiles inside the trace (700 ms of it is tracing)
        _span("jit.lower", 200, 300, fun="jit(inner)"),
        _span("jit.compile", 300, 500, fun="jit(inner)"),
        _span("jit.trace", 0, 1000, fun="outer", nested=3),
        _span("jit.lower", 1000, 1100, fun="jit(outer)"),
        _span("jit.compile", 1100, 1150, fun="jit(outer)"),
        # two threads trace at once: the union, 1000 ms, not the sum
        _span("jit.trace", 2000, 2600, fun="a", nested=0),
        _span("jit.trace", 2400, 3000, fun="b", nested=0),
        # a lowering with a compile inside it
        _span("jit.lower", 4000, 4500, fun="jit(c)"),
        _span("jit.compile", 4100, 4300, fun="jit(c)"),
        _span("fit", 10_000, 11_000),  # the warm-up unit: before the window
    ]


def _window():
    """Two fits; a program traced and compiled inside the window is not set-up's."""
    return [
        _span("fit", 20_000, 21_000),
        _span("jit.trace", 21_100, 21_200, fun="late", nested=0),
        _span("jit.lower", 21_200, 21_300, fun="jit(late)"),
        _span("jit.compile", 21_300, 21_400, fun="jit(late)"),
        _span("fit", 21_000, 22_000),
    ]


def _run():
    shift = 20_000 - 50  # the trace's clock starts with the profiler session
    fits = [[(t - shift) * MS, (t + 1000 - shift) * MS] for t in (20_000, 21_000)]
    return {"units": 2, "trace": {"spans": {"fit": fits}}}


@pytest.fixture()
def recorder(monkeypatch):
    records = collections.deque(_setup() + _window())
    monkeypatch.setattr(timed, "_records", records)
    return records


def test_each_reader_gives_the_hand_computed_number(recorder):
    want = {
        "jit.trace_s": 0.7 + 1.0,  # self time, union of the overlapping pair
        "jit.lower_s": 0.1 + 0.1 + 0.3,  # the compile inside the last lowering left out
        "jit.compile_s": 0.2 + 0.05 + 0.2,
        "jit.programs": 3,
    }
    for name in NEW:
        assert load_reader(name)(_run()) == pytest.approx(want[name]), name


def test_the_three_times_never_overlap(recorder):
    total = sum(load_reader(name)(_run()) for name in NEW[:3])
    # the union of every jit.* record of set-up: [0, 1150] + [2000, 3000] + [4000, 4500]
    assert total == pytest.approx(1.15 + 1.0 + 0.5)


def test_only_set_up_is_read(recorder, monkeypatch):
    """Records inside the window change nothing; records of set-up alone do."""
    before = {name: load_reader(name)(_run()) for name in NEW}
    monkeypatch.setattr(timed, "_records", collections.deque(r for r in recorder if not (
        r.name.startswith("jit.") and r.start_ns >= EPOCH + 20_000 * MS)))
    assert {name: load_reader(name)(_run()) for name in NEW} == before


def test_readers_return_none_where_the_program_records_no_stage(recorder, monkeypatch):
    no_jit = collections.deque(r for r in recorder if not r.name.startswith("jit."))
    monkeypatch.setattr(timed, "_records", no_jit)
    for name in NEW:
        assert load_reader(name)(_run()) is None, name
    monkeypatch.setattr(timed, "_records", collections.deque())  # not even the fits
    for name in NEW:
        assert load_reader(name)(_run()) is None, name


def test_a_program_without_the_recorder_reads_none(recorder, monkeypatch):
    monkeypatch.delattr(timed, "records")
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
        assert declared[name]["layer"] == "compile pipeline"
        assert declared[name]["moves"] == "setup_s" and declared[name]["better"] == "lower"
        assert "workloads" not in declared[name]  # both cells read them
