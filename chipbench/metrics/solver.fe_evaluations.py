def read(run):
    from chipbench import program_records

    window = program_records.window(run)
    counters = window.inside("solver.evaluations", kind="fe") if window else []
    return sum(r.value for r in counters) / run["units"] if counters else None
