"""p90 of due time to admission into a slot (`RequestStats.t_admitted`)."""

import math

from chipbench.window import percentile


def read(ctx):
    reqs = ctx.window.get("requests", [])
    waits = [math.inf if r["admitted"] is None else r["admitted"] - r["due"]
             for r in reqs]
    return percentile(waits, 90) * 1e3 if waits else None
