"""Output tokens produced inside the window over the window, counting the
tokens of requests still in flight when it closed."""


def read(ctx):
    w = ctx.window
    if "tokens" not in w:
        return None
    return w["tokens"] / w["window_s"]
