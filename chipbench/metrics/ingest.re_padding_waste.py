def read(run):
    from chipbench import program_records

    window = program_records.window(run)
    counters = window.before("ingest.padding_waste") if window else []
    waste = program_records.weighted_mean(counters, "rows")
    return None if waste is None else 100.0 * waste
