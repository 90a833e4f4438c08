"""Readings for the limits of the comparison that decides ``correct``, at the
cell's own size, many seeds in one process (set-up is long):

    python3 chipbench/tools/readings.py --workload W --seeds 1,2,3 \\
        --control-seeds 1,2,3 --out chiprun_out/readings_W.jsonl

For every seed: the data, the prepared system, a warm-up unit and ``--units``
timed fit units of the program through the window's own call, the reference,
and the numbers of ``compare.numbers`` (the LOWER readings) for the last unit. For every control seed also the control — the reference computed in
bfloat16 put in the program's place — and the faults that can be planted in the
reference put in the program's place without touching it (the UPPER readings):

- ``half_batch``: the reference trained on the first half of the rows;
- ``no_exchange``: every coordinate fitted alone, as if the others scored 0;
- ``answer_altered``: the reference's own answer with one fixed-effect
  coefficient moved by 5 % of the vector's norm;
- a state left unchanged needs no run: a coordinate that stays at zero reads
  exactly 1 on its coefficient gap.

One JSON line per reading, each judged by ``compare.judge`` against the
committed ``limits/<workload>.json`` exactly as a run is (``correct``,
``failing``): the control and every fault have to read ``correct: false``. ``--rehearsal`` runs tiny sizes on the CPU.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def as_answers(records: list) -> tuple:
    """Reference-shaped records in the program's place."""
    answers = [dict(r, validation_metric=r["heldout_auc"]) for r in records]
    losses = {k: [r[k] for r in records] for k in ("train_loss", "heldout_loss")}
    return answers, losses


def half_rows(dataset):
    half = dataset.train.n // 2
    t = dataset.train
    train = dataclasses.replace(
        t,
        fe_X=t.fe_X[:half],
        labels=t.labels[:half],
        re_vals=None if t.re_vals is None else t.re_vals[:half],
        ids={k: v[:half] for k, v in t.ids.items()},
    )
    return dataclasses.replace(dataset, train=train)


def alone(cfg, dataset, reference):
    """Each coordinate fitted with no other's scores: one record shaped like
    the full fit's, stitched from single-coordinate fits."""
    out = None
    for c in cfg["coordinates"]:
        solo = dict(cfg, coordinates=[c], coordinate_descent_passes=1)
        recs = reference.fit(solo, dataset, dtype=cfg["precision"])
        if out is None:
            out = copy.deepcopy(recs)
            for r in out:
                r["fe_objectives"] = {k: v * int(cfg["coordinate_descent_passes"])
                                      for k, v in r["fe_objectives"].items()}
            continue
        for r, s in zip(out, recs):
            r["reg"].update(s["reg"])
            r["fixed"].update(s["fixed"])
            r["random"].update(s["random"])
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--out", required=True)
    p.add_argument("--faults", default="half_batch,no_exchange,answer_altered",
                   help="which faults to read on the control seeds (half_batch recompiles the "
                        "reference for every seed: its entity sizes are the seed's)")
    p.add_argument("--no-program", action="store_true",
                   help="read only the control and the faults: no fit of the program")
    p.add_argument("--units", type=int, default=2, help="timed fit units a seed, after one warm-up")
    p.add_argument("--rehearsal", action="store_true")
    args = p.parse_args(argv)
    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np

    from chipbench import compare, entry, generate, reference, run
    from chipbench.kinds import fit_units
    from chipbench.tests.faults import ALTERED_SHARE

    cell = run.load_cell(args.workload)
    cfg = dict(cell["cfg"])
    if args.rehearsal:
        cfg.update(cfg["rehearsal"])
    else:
        run.require_devices(int(cell["chips"]))
    entry.configure_compilation_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    faults = set(args.faults.split(","))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    def emit(rec):
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)

    limits = cell["limits"]

    def reading(who, seed, values, **extra):
        """One reading, judged as a run would judge it."""
        correct, compared = compare.judge(values, limits)
        failing = [n for n, c in compared.items()
                   if c["limit"] is not None and not (c["value"] is not None and c["value"] <= c["limit"])]
        emit({"workload": args.workload, "seed": seed, "who": who, "correct": correct,
              "failing": failing, "values": values, **extra})

    def stand_in(who, seed, records, ref, **extra):
        """Reference-shaped ``records`` put in the program's place."""
        answers, losses = as_answers(records)
        reading(who, seed, dict(compare.numbers(answers, ref, losses), unit_repeat=0.0), **extra)

    def program_reading(seed, dataset, t0):
        """The program's fit units on ``dataset`` against the reference, which
        it returns."""
        system = entry.System(cfg, dataset)
        system.prepare()
        t1 = time.perf_counter()
        system.fit_unit()  # warm-up: compiles on the first seed
        unit_seconds, window_results = [], []
        for _ in range(args.units):
            t = time.perf_counter()
            window_results.append(system.fit_unit())
            unit_seconds.append(time.perf_counter() - t)
        units, losses = fit_units.collect(cfg, dataset, window_results)
        del window_results
        system.release()
        del system
        gc.collect()
        t2 = time.perf_counter()
        ref = reference.fit(cfg, dataset, dtype=cfg["precision"])
        t3 = time.perf_counter()
        values = compare.numbers(units[-1], ref, losses)
        values["unit_repeat"] = compare.unit_repeat(units)
        reading("program", seed, values,
                iterations=[a["iterations"] for a in units[-1]],
                unit_seconds=unit_seconds,
                reference_distances=[r["distances"] for r in ref],
                seconds={"data_and_prepare": t1 - t0, "reference": t3 - t2})
        return ref

    for seed in seeds:
        t0 = time.perf_counter()
        dataset = generate.generate(cfg, seed)
        if args.no_program:
            ref = reference.fit(cfg, dataset, dtype=cfg["precision"])
        else:
            ref = program_reading(seed, dataset, t0)
        if seed in control_seeds:
            t4 = time.perf_counter()
            control = reference.fit(cfg, dataset, dtype="bfloat16")
            stand_in("control_bfloat16", seed, control, ref,
                     seconds={"control": time.perf_counter() - t4})
            del control
            if "half_batch" in faults:
                stand_in("fault_half_batch", seed,
                         reference.fit(cfg, half_rows(dataset), dtype=cfg["precision"]), ref)
            if "no_exchange" in faults and len(cfg["coordinates"]) > 1:
                stand_in("fault_no_exchange", seed, alone(cfg, dataset, reference), ref)
            if "answer_altered" in faults:
                altered = copy.deepcopy(ref)
                for r in altered:
                    for w in r["fixed"].values():
                        w[0] += ALTERED_SHARE * np.linalg.norm(w)
                stand_in("fault_answer_altered", seed, altered, ref)
        del ref, dataset
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
