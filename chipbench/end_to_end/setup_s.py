"""Set-up: process start to the window's opening, compiles included."""


def read(ctx):
    return ctx.setup_s
