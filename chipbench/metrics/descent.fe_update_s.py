def read(run):
    from chipbench import trace

    seconds = trace.span_seconds(run["trace"], "fe_update")
    return None if seconds is None else seconds / run["units"]
