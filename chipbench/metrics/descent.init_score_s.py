def read(run):
    from chipbench import program_records

    seconds = program_records.seconds_inside(run, "descent.init")
    return None if seconds is None else seconds / run["units"]
