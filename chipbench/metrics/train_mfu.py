def read(run):
    from chipbench import costs

    flops = costs.unit_flops(run["cfg"], run["iterations"]) * run["units"]
    return 100.0 * flops / (run["trace"]["window_s"] * run["peaks"]["flops_per_s"])
