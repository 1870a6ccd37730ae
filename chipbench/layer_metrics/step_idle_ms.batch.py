"""Device idle time inside the program's ``repro.engine.step`` spans, per
engine step: what the host does between dispatches within a step."""

from chipbench import program_spans


def read(ctx):
    s = program_spans.of_run()
    if s is None or not s.count("repro.engine.step"):
        return None
    return s.idle_under("repro.engine.step") / s.count("repro.engine.step") \
        * 1e3
