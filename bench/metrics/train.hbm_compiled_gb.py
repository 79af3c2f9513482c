"""train.hbm_compiled_gb: what the compiled update holds on the device by
its own ``memory_analysis()``: arguments, outputs and temporaries, less the
outputs that alias donated arguments, in GB (1e9 bytes)."""


def read(d):
    v = d.get("compiled_bytes")
    return v / 1e9 if v else None
