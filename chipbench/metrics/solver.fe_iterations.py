def read(run):
    counts = [
        it
        for model in run["iterations"]
        for c in run["cfg"]["coordinates"]
        if c["kind"] == "fixed"
        for it in model[c["id"]]
    ]
    return float(sum(counts)) if counts else None
