"""The last line's keys, a tiny CPU rehearsal of both cells, and the faults:
each drives a whole run underneath ``run.run_cell`` (everything but the look
for a chip) and must come out ``correct: false``."""

import json
import subprocess
import sys
import os

import pytest

from chipbench import run
from chipbench.tests import faults

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]  # every cell, also those later PRs add


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_rehearsal_result_keys(workload, traced, bench):
    cell = run.load_cell(workload)
    result = run.run_cell(cell, seed=2**31 + 77, seconds=0.3, traced=traced, rehearsal=True)
    keys = list(result)
    assert keys[:3] == ["correct", "attempted", "failed"]
    assert set(keys) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert keys[-1] == "compared"  # the numbers beside their limits come last
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 1
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    want = {m["name"] for m in bench["end_to_end"] if workload in m.get("workloads", [workload])}
    # the CPU's trace has no device plane: a traced rehearsal reads no per-layer metric
    assert set(result["metrics"]) == (set() if traced else want)
    for name, c in result["compared"].items():
        assert set(c) == {"value", "limit"}, name


def test_rehearsal_process_can_never_be_mistaken_for_a_chip_result():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", CELLS[-1],
         "--seed", "3", "--seconds", "0.2", "--trace", "0", "--rehearsal"],
        capture_output=True, text=True, timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert lines and all(line.startswith("REHEARSAL platform=cpu") for line in lines)
    with pytest.raises(json.JSONDecodeError):
        json.loads(lines[-1])
    assert '"value": "not measured"' in lines[-1]


def test_without_a_tpu_there_is_no_result():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", CELLS[-1],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_comes_out_not_correct(workload, fault):
    cell = run.load_cell(workload)
    if fault == "no_exchange" and len(cell["cfg"]["coordinates"]) == 1:
        pytest.skip("one coordinate exchanges no scores")
    result = run.run_cell(
        cell, seed=91, seconds=0.2, traced=False, rehearsal=True, faults=[faults.FAULTS[fault]]
    )
    assert result["correct"] is False
    failing = [n for n, c in result["compared"].items()
               if c["limit"] is not None and not c["value"] <= c["limit"]]
    assert failing, result["compared"]
