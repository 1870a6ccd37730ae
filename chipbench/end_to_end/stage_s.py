"""Seconds per whole stage pass: the window over the passes completed in it."""

from chipbench.window import per_pass


def read(ctx):
    return per_pass(ctx.window["window_s"], ctx.window.get("passes", 0))
