"""Runs of one cell as the driver makes them, each a new process, for the
bounds (PERF.md section 2):

    python3 chipbench/tools/sets.py --workload W --seeds 1,2,3,4,5,6 --sets 2 \\
        --seconds 20 --out chiprun_out/sets_W.jsonl [--traced-seeds 7,8,9]

Every set runs the same seeds. One JSON line per run (the result line plus the
run's ``set-up`` and ``window`` log lines), then for each end-to-end metric and
set the median and the spread (distance of the first and third quartile by
``statistics.quantiles(values, n=4)``, as a share of the median). This process
never imports jax: the chip belongs to the run.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one_run(workload: str, seed: int, seconds: int, trace: int) -> dict:
    t0 = time.perf_counter()
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True, cwd=ROOT,
    )
    lines = out.stdout.strip().splitlines()
    rec = {"workload": workload, "seed": seed, "trace": trace, "rc": out.returncode,
           "wall_s": time.perf_counter() - t0,
           "log": [ln for ln in lines[:-1] if ln.startswith(("set-up", "window", "solver"))]}
    try:
        rec["result"] = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        rec["stderr"] = out.stderr[-2000:]
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--seconds", type=int, required=True)
    p.add_argument("--traced-seeds", default="")
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    def emit(rec):
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec)[:1500], flush=True)

    seeds = [int(s) for s in args.seeds.split(",") if s]
    ok = True
    for k in range(args.sets):
        runs = []
        for seed in seeds:
            rec = dict(one_run(args.workload, seed, args.seconds, 0), set=k)
            emit(rec)
            runs.append(rec)
        good = [r["result"] for r in runs if r.get("result", {}).get("correct")]
        ok = ok and len(good) == len(runs)
        for name in sorted({n for r in good for n in r["metrics"]}):
            values = [r["metrics"][name]["value"] for r in good]
            if len(values) >= 2:
                q = statistics.quantiles(values, n=4)
                med = statistics.median(values)
                emit({"workload": args.workload, "set": k, "metric": name, "median": med,
                      "spread": (q[2] - q[0]) / med, "values": values})
    for seed in [int(s) for s in args.traced_seeds.split(",") if s]:
        rec = one_run(args.workload, seed, args.seconds, 1)
        ok = ok and bool(rec.get("result", {}).get("correct"))
        emit(rec)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
