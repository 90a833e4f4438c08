def read(run):
    from chipbench import program_records

    return program_records.seconds_before(run, "ingest.re_index", "ingest.re_buckets")
