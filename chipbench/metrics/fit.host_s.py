def read(run):
    from chipbench import program_records

    inner = [
        program_records.seconds_inside(run, name)
        for name in ("descent.init", "descent.update", "descent.validate")
    ]
    if inner[1] is None:  # no update span: not a program that records
        return None
    fit = program_records.seconds(program_records.window(run).fits)
    return (fit - sum(s or 0.0 for s in inner)) / run["units"]
