def read(run):
    return run.get("prepare_s")
