"""Device idle time inside the program's ``repro.profile.*`` spans (tap
capture and each layer's inputs and statistics), per whole profile pass."""

from chipbench import program_spans


def read(ctx):
    s = program_spans.of_run()
    passes = ctx.window.get("passes", 0)
    if s is None or not passes:
        return None
    return s.idle_under("repro.profile.") / passes * 1e3
