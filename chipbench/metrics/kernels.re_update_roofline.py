def read(run):
    from chipbench import program_records, trace

    window = program_records.window(run)
    updates = window.inside("descent.update", kind="re") if window else []
    evaluations = window.inside("solver.evaluations", kind="re") if window else []
    if not updates or len(evaluations) != len(updates):
        return None
    spans = {"re": window.to_trace(updates)}
    busy = trace.busy_inside({"busy": run["trace"]["busy"], "spans": spans}, "re")
    if busy <= 0:
        return None
    n, k = int(run["cfg"]["n_train_rows"]), int(run["cfg"]["random_effect_dim"])
    least = sum(r.value * n * k * 4 + n * (2 * k * 4 + 8) for r in evaluations)
    return 100.0 * least / run["peaks"]["hbm_bytes_per_s"] / busy
