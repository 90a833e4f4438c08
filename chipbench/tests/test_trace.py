"""The trace reduction, on a hand-made trace and on a small recorded one."""

import gzip
import json
import os

import pytest

from chipbench import trace

MS = 1_000_000


def _trace():
    host = [
        ["chipbench:fit", 0, 100 * MS],
        ["chipbench:fe_update", 10 * MS, 30 * MS],  # 10..40
        ["chipbench:re_update", 40 * MS, 40 * MS],  # 40..80
        ["chipbench:validate", 80 * MS, 10 * MS],  # 80..90
        ["not a span", 0, 500 * MS],
    ]
    ops = [
        ["while.1", 12 * MS, 20 * MS],  # 12..32, spans its body
        ["fusion.a", 12 * MS, 8 * MS],
        ["fusion.b", 22 * MS, 10 * MS],
        ["gather.c", 45 * MS, 30 * MS],  # 45..75
        ["copy.before", -20 * MS, 5 * MS],  # outside the window
    ]
    modules = [["jit_solve(1)", 12 * MS, 20 * MS], ["jit_update(2)", 45 * MS, 30 * MS]]
    return {
        "planes": [
            {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
            {
                "name": "/device:TPU:0",
                "lines": [
                    {"name": "XLA Ops", "events": ops},
                    {"name": "XLA Modules", "events": modules},
                    {"name": "Steps", "events": [["step", 0, 100 * MS]]},
                ],
            },
        ]
    }


def test_busy_is_the_union_and_idle_goes_to_the_innermost_span():
    r = trace.reduce_trace(_trace())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.050)  # 12..32 and 45..75
    idle = dict(r["idle_gaps"])
    assert idle["fe_update"] == pytest.approx(0.010)  # 10..12 and 32..40
    assert idle["re_update"] == pytest.approx(0.010)  # 40..45 and 75..80
    assert idle["validate"] == pytest.approx(0.010)
    assert idle["fit"] == pytest.approx(0.020)  # 0..10 and 90..100
    assert "_no_span_" not in idle
    assert sum(idle.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_self_time_takes_nested_ops_out_of_their_parent():
    ops = dict(trace.reduce_trace(_trace())["device_ops"])
    assert ops["while.1"] == pytest.approx(0.002)  # 20 ms less 8 and 10
    assert ops["fusion.b"] == pytest.approx(0.010)
    assert ops["gather.c"] == pytest.approx(0.030)
    assert ops["module:jit_update(2)"] == pytest.approx(0.030)
    assert "copy.before" not in ops


def test_busy_inside_a_span():
    r = trace.reduce_trace(_trace())
    assert trace.busy_inside(r, "fe_update") == pytest.approx(0.020)
    assert trace.busy_inside(r, "re_update") == pytest.approx(0.030)
    assert trace.busy_inside(r, "no_such_span") == 0.0


def test_nothing_to_read_returns_none():
    t = _trace()
    t["planes"][1]["lines"][0]["events"] = []
    t["planes"][1]["lines"][1]["events"] = []
    assert trace.reduce_trace(t) is None
    assert trace.reduce_trace({"planes": t["planes"][:1]}) is None


def test_recorded_chip_trace():
    """A cut of a real ``--trace 1`` run of logistic-a1a.train on a TPU v5 lite
    (recorded in PR 25 by a tool since removed)."""
    path = os.path.join(os.path.dirname(__file__), "data", "a1a_trace_cut.json.gz")
    with gzip.open(path, "rt") as f:
        recorded = json.load(f)
    r = trace.reduce_trace(recorded)
    assert r is not None and 0 < r["busy_s"] <= r["window_s"]
    idle = dict(r["idle_gaps"])
    assert sum(idle.values()) + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-6)
    assert trace.busy_inside(r, "fe_update") > 0
    assert recorded["expected"]["busy_s"] == pytest.approx(r["busy_s"], rel=1e-9)


def test_every_reader_reads_a_recorded_chip_trace(monkeypatch):
    """The per-layer path of a traced run, walked on the CPU: the rehearsal's
    own trace has no device plane, so it is handed the recorded one. Values
    are blanked ("not measured"); what is held is that every reader of every
    cell finds its inputs."""
    from chipbench import run

    path = os.path.join(os.path.dirname(__file__), "data", "a1a_trace_cut.json.gz")
    with gzip.open(path, "rt") as f:
        recorded = json.load(f)
    monkeypatch.setattr(trace, "load_xplane", lambda _dir: recorded)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    for workload in cells:
        cell = run.load_cell(workload)
        result = run.run_cell(cell, seed=12, seconds=0.2, traced=True, rehearsal=True)
        # the recorded trace is of a cell with no random effect: its reader returns nothing
        assert set(result["metrics"]) >= {m["name"] for m in cell["per_layer"]} - {"descent.re_update_s"}
        assert {"busy_s", "window_s"} <= set(result["device"])
