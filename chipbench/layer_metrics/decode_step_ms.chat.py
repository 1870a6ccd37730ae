"""Device time per call of the group decode executable (trace)."""


def read(ctx):
    sec, calls = ctx.trace.module_time("decode_fn")
    return sec / calls * 1e3 if calls else None
