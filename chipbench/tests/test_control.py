"""The control of the comparison that decides ``correct``: the reference
computed in bfloat16, put in the program's place, has to fail at least one
compared number of the committed limits — here at rehearsal size on the CPU,
on three seeds; at the cells' own size tools/readings.py judges it on the chip
(verdicts in PERF.md)."""

import json
import os

import pytest

from chipbench import compare, generate, reference, run
from chipbench.tools.readings import as_answers

with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]  # every cell, also those later PRs add


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [5, 2**31 + 6, 7])
def test_bfloat16_control_is_not_correct(workload, seed):
    cell = run.load_cell(workload)
    cfg = dict(cell["cfg"])
    cfg.update(cfg["rehearsal"])
    dataset = generate.generate(cfg, seed)
    ref = reference.fit(cfg, dataset, dtype="float32")
    answers, losses = as_answers(reference.fit(cfg, dataset, dtype="bfloat16"))
    values = compare.numbers(answers, ref, losses)
    correct, compared = compare.judge(values, cell["limits"])
    assert correct is False, compared


@pytest.mark.parametrize("workload", CELLS)
def test_reference_in_its_own_place_is_correct(workload):
    cell = run.load_cell(workload)
    cfg = dict(cell["cfg"])
    cfg.update(cfg["rehearsal"])
    dataset = generate.generate(cfg, 11)
    ref = reference.fit(cfg, dataset, dtype="float32")
    answers, losses = as_answers(ref)
    values = compare.numbers(answers, ref, losses)
    residual = values.pop("reference_residual")  # the reference's own check, not a gap
    assert 0.0 < residual < 1e-5
    assert all(v == 0.0 for v in values.values()), values
