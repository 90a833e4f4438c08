"""The driver of traffic kind ``fit_units``: whole training jobs of the
cell's configuration, back to back from one process (``window.run_window``),
then the comparison with the plain reference. ``run.py`` finds it by the
``kind`` of the cell's traffic file; another kind of traffic is another module
beside this one with the same ``run_cell``."""

from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time

from chipbench.run import load_reader, memory_peak_bytes, require_devices, say

TRACE_SECONDS = 8.0  # a traced window is short: whole units up to this long


def collect(cfg: dict, dataset, window_results: list) -> tuple:
    """``(units, program_losses)``: every unit's answers as host arrays, and
    the last unit's training and held-out log-loss per model, taken by the
    plain scorer from the scores the coordinates exchanged and from the
    coefficients."""
    from chipbench import entry, reference

    units = [entry.unit_answers(results) for results in window_results]
    program_losses = {"train_loss": [], "heldout_loss": []}
    for result, answer in zip(window_results[-1], units[-1]):
        total = sum(result.descent.training_scores.values())
        program_losses["train_loss"].append(reference.mean_logloss(total, dataset.train.labels))
        del total
        program_losses["heldout_loss"].append(
            reference.mean_logloss(
                reference.score_answer(cfg, dataset.validation, answer), dataset.validation.labels
            )
        )
    return units, program_losses


def run_cell(cell: dict, seed: int, seconds: float, traced: bool, rehearsal: bool,
             t_start: float, faults=()) -> dict:
    """Set-up (counted from ``t_start``, the process's start), window,
    comparison. Returns the result object (not printed)."""
    import jax

    from chipbench import compare, entry, generate, reference
    from chipbench.window import CompileMeter, run_window

    cfg = dict(cell["cfg"])
    if rehearsal:
        cfg.update(cfg["rehearsal"])
        device = {"platform": jax.devices()[0].platform, "kind": "REHEARSAL", "count": 1}
    else:
        device = require_devices(int(cell["chips"]))
    tag = "REHEARSAL platform=cpu " if rehearsal else ""
    say(f"{tag}traffic {cell['traffic']}: {cell['traffic_spec']['what']}")

    # ------------------------------------------------------------- set-up
    t_init = time.perf_counter() - t_start
    say(f"{tag}compile cache: {entry.configure_compilation_cache()}")
    meter = CompileMeter()
    dataset = generate.generate(cfg, seed)
    t_data = time.perf_counter() - t_start - t_init
    spans = entry.Spans() if traced else None
    system = entry.System(cfg, dataset, spans=spans)
    t0 = time.perf_counter()
    system.prepare()
    prepare_s = time.perf_counter() - t0
    for fault in faults:  # tests only: the timed path broken underneath
        fault(system)
    for _ in range(int(cell["traffic_spec"]["warm_up_units"])):
        system.fit_unit()
    warm = meter.snapshot()
    gc.collect()
    gc.disable()
    setup_s = time.perf_counter() - t_start
    say(
        f"{tag}set-up {setup_s:.1f} s: start {t_init:.1f} s, data {t_data:.1f} s, "
        f"prepare {prepare_s:.1f} s, warm-up {setup_s - t_init - t_data - prepare_s:.1f} s; "
        f"warm-up compiled {warm['compiles']} programs in {meter.compile_seconds:.1f} s "
        f"({warm['cache_misses']} cache misses)"
    )

    # ------------------------------------------------------------- window
    unit_counts, previous = [], []

    def keep(results):
        unit_counts.append(meter.snapshot())
        # only the last unit keeps its [N] score arrays (the others' models
        # and trackers are small): a long window must not fill the device
        for r in previous:
            r.descent.training_scores = None
        previous[:] = results
        return results

    def fit_unit():
        if spans is None:
            return system.fit_unit()
        with spans.span("fit"):
            return system.fit_unit()

    trace_dir = None
    if traced:
        seconds = min(seconds, TRACE_SECONDS)
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        window = run_window(fit_unit, seconds, keep=keep)
    finally:
        if traced:
            jax.profiler.stop_trace()
        gc.enable()
    in_window = meter.since(warm)
    failed_units = sum(
        1
        for before, after in zip([warm] + unit_counts[:-1], unit_counts)
        if after["compiles"] != before["compiles"] or after["traces"] != before["traces"]
    )
    rows_per_unit = int(cfg["rows_per_unit_factor"]) * int(cfg["n_train_rows"])
    say(
        f"{tag}window {window.seconds:.3f} s: {window.units} units of {rows_per_unit} rows, "
        f"unit seconds {[round(s, 3) for s in window.unit_seconds()]}; inside the window: "
        f"{in_window['compiles']} compiles, {in_window['traces']} traces, "
        f"{in_window['cache_misses']} cache misses"
    )
    peak = memory_peak_bytes()

    # ------------------------------------ what the units produced, compared
    units, program_losses = collect(cfg, dataset, window.results)
    window.results.clear()
    system.release()
    gc.collect()
    t0 = time.perf_counter()
    ref = reference.fit(cfg, dataset, dtype=cfg["precision"])
    values = compare.numbers(units[-1], ref, program_losses)
    values["unit_repeat"] = compare.unit_repeat(units)
    correct, compared = compare.judge(values, cell["limits"])
    correct = correct and failed_units == 0
    say(f"{tag}reference and comparison {time.perf_counter() - t0:.1f} s")
    say(f"{tag}solver iterations of the last unit: {[a['iterations'] for a in units[-1]]}")

    # ------------------------------------------------------------ metrics
    metrics, breakdown, reduced = {}, None, None
    device["memory_peak_bytes"] = peak
    if traced:
        from chipbench import trace as trace_mod
        from chipbench.peaks import peaks_for

        reduced = trace_mod.reduce_trace(trace_mod.load_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is None and not rehearsal:
            raise SystemExit("the trace holds no device operation inside a fit span")
        if reduced is None:
            say(f"{tag}the CPU's trace has no device plane: per-layer metrics not read")
    if reduced is not None:
        run = {
            "cfg": cfg,
            "units": window.units,
            "iterations": [a["iterations"] for a in units[-1]],
            "prepare_s": prepare_s,
            "trace": reduced,
            # a rehearsal gets here only where a test hands it a recorded chip trace
            "peaks": peaks_for("TPU v5 lite" if rehearsal else device["kind"]),
        }
        for m in cell["per_layer"]:
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    if not traced:
        measured = {
            "train_rows_per_s": window.units * rows_per_unit / window.seconds,
            "setup_s": setup_s,
        }
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": float(measured[m["name"]]), "unit": m["unit"]}
    if rehearsal:  # a CPU number is never written under a device metric's name
        metrics = {n: {"value": "not measured", "unit": m["unit"]} for n, m in metrics.items()}
        breakdown = None
    result = {
        "correct": bool(correct), "attempted": window.units, "failed": failed_units,
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared  # the numbers beside their limits come last
    for name, c in compared.items():
        print(f"{tag}compared {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"{tag}correct={result['correct']} failed_units={failed_units}", file=sys.stderr, flush=True)
    return result
