"""p90 of due time to first token over every request due in the window.

A per-layer reading and not an end-to-end metric: at 100 due requests its
run-to-run spread (10-19% of the median) is wider than any bound a
benchmark may set."""

from chipbench.window import percentile, ttft_s


def read(ctx):
    reqs = ctx.window.get("requests")
    if not reqs:
        return None
    return percentile(ttft_s(reqs), 90) * 1e3
