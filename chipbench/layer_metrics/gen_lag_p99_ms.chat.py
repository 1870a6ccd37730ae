"""How late the load generator submitted: p99 of submit - due (host clock)."""

from chipbench.window import percentile


def read(ctx):
    lags = [r["submit"] - r["due"] for r in ctx.window.get("requests", [])]
    return percentile(lags, 99) * 1e3 if lags else None
