def read(run):
    from chipbench import compile_pipeline

    return compile_pipeline.seconds(run, "jit.lower")
