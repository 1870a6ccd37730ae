"""Share of decode rows that held a request: tokens produced by decode in
the window (tokens minus first tokens, which come from prefill) over the
rows of the group decode calls the trace counts."""


def read(ctx):
    _, calls = ctx.trace.module_time("decode_fn")
    c = ctx.window["counters"]
    if not calls:
        return None
    return (ctx.window["tokens"] - c["first_tokens"]) \
        / (calls * c["max_batch"]) * 100.0
