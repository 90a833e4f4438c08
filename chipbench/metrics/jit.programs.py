def read(run):
    from chipbench import program_records

    window = program_records.window(run)
    compiles = window.before("jit.compile") if window else []
    return len(compiles) or None
