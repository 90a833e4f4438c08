def read(run):
    from chipbench import costs, trace

    busy = trace.busy_inside(run["trace"], "fe_update")
    if busy <= 0:
        return None
    least = costs.unit_fe_bytes(run["cfg"], run["iterations"]) * run["units"]
    return 100.0 * least / run["peaks"]["hbm_bytes_per_s"] / busy
