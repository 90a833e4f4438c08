"""chipbench: one cell of BENCHMARK.json, one process, one result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is looked up by name: the cell in BENCHMARK.json,
its configuration in the file BENCHMARK.json gives for it, its traffic in
``chipbench/traffic/<traffic>.json``, each per-layer metric in
``chipbench/metrics/<metric>.py`` and the limits of the comparison that
decides ``correct`` in ``chipbench/limits/<workload>.json``. Adding any of them
is adding files and entries (README.md).

Without a TPU (or with fewer chips than the cell asks for) the run exits 1 and
prints no result. ``--rehearsal`` is the tiny CPU walk-through the tests use:
every line it prints says ``REHEARSAL platform=cpu`` and its last line is not
a result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is counted from here: before jax is imported

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(*parts) -> None:
    print(*parts, flush=True)


def load_cell(workload: str) -> dict:
    """The cell with everything its names point to."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; have {sorted(cells)}")
    cell = dict(cells[workload])
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        cell["cfg"] = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        cell["traffic_spec"] = json.load(f)
    limits_path = os.path.join(HERE, "limits", workload + ".json")
    with open(limits_path) as f:
        cell["limits"] = json.load(f)

    def reports(metric):
        return workload in metric.get("workloads", [workload])

    cell["end_to_end"] = [m for m in bench["end_to_end"] if reports(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if reports(m)]
    return cell


def load_reader(metric: str):
    """``read(run)`` of ``chipbench/metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("chipbench_metric_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def require_devices(chips: int) -> dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(
            f"chipbench needs {chips} TPU chip(s); jax reports {len(devices)} x "
            f"{devices[0].platform} ({devices[0].device_kind}): refusing to measure",
            file=sys.stderr,
        )
        raise SystemExit(1)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


def run_cell(cell: dict, seed: int, seconds: float, traced: bool, rehearsal: bool,
             faults=()) -> dict:
    """The result object (not printed) of one run, made by the driver of the
    cell's traffic kind: ``chipbench/kinds/<kind>.py``. ``faults`` is for the
    tests: the timed path broken underneath."""
    kind = cell["traffic_spec"]["kind"]
    try:
        driver = importlib.import_module("chipbench.kinds." + kind)
    except ImportError:
        raise SystemExit(f"traffic {cell['traffic']!r} is of kind {kind!r}: "
                         f"no chipbench/kinds/{kind}.py") from None
    return driver.run_cell(cell, seed, seconds, traced, rehearsal, t_start=_T0, faults=faults)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--rehearsal", action="store_true",
        help="tiny sizes on the CPU; prints REHEARSAL lines, never a result",
    )
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    try:
        import photon_ml_tpu  # noqa: F401
    except ImportError:
        print("chipbench measures the photon_ml_tpu package of this checkout; it is not here",
              file=sys.stderr)
        return 1
    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cell = load_cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), args.rehearsal)
    line = json.dumps(result)
    say(("REHEARSAL platform=cpu " if args.rehearsal else "") + line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
