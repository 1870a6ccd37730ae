"""Named host spans on the JAX profiler's own trace.

A span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``. Under
``jax.profiler.trace`` it lands on the host plane, on the same clock as the
device's operations; with no trace running it costs a microsecond or two.
Stats are plain ints or short strings the host already holds: given when
the span opens, or set before it closes with ``set_metadata``. A span never
reads a device value.

    with span("engine.sample") as sp:
        ...
        sp.set_metadata(tokens=n)
"""

from __future__ import annotations

import jax


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation("repro." + name, **stats)
