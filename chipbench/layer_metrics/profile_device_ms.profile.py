"""Device busy time per profile pass (trace)."""


def read(ctx):
    passes = ctx.window.get("passes", 0)
    return ctx.trace.busy_s / passes * 1e3 if passes else None
