"""Device time per call of the chunked-prefill executables (trace)."""


def read(ctx):
    sec, calls = ctx.trace.module_time("chunk_fn")
    return sec / calls * 1e3 if calls else None
