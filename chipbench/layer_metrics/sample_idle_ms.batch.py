"""Device idle time whose innermost span is ``repro.engine.fetch`` or
``repro.engine.sample``, per group decode call: the read of each decode's
logits and the host sampling after it, which sampling on the device would
remove. The launch gap of the next decode can fall in ``engine.decode``
instead, so this is a lower bound of that round trip."""

from chipbench import program_spans


def read(ctx):
    s = program_spans.of_run()
    if s is None or not s.count("repro.engine.decode"):
        return None
    return s.idle_in("repro.engine.fetch", "repro.engine.sample") \
        / s.count("repro.engine.decode") * 1e3
