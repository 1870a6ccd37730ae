"""p90 over requests due in the window of (last token - first token) /
(tokens - 1), for requests with at least two tokens."""

from chipbench.window import percentile, tpot_s


def read(ctx):
    vals = tpot_s(ctx.window.get("requests", []))
    if not vals:
        return None
    return percentile(vals, 90) * 1e3
