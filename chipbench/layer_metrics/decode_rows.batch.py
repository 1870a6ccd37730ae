"""Share of decode rows that held a request, counted where the work
happens: summed ``active`` over summed ``rows`` of the program's
``repro.engine.decode`` spans in the window."""

from chipbench import program_spans


def read(ctx):
    s = program_spans.of_run()
    rows = 0 if s is None else s.stat("repro.engine.decode", "rows")
    if not rows:
        return None
    return s.stat("repro.engine.decode", "active") / rows * 100.0
