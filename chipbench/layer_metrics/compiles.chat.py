"""Compiles that start inside the window (host events named
``backend_compile_and_load``); read only from a program that opens spans."""

from chipbench import program_spans


def read(ctx):
    s = program_spans.of_run()
    return None if s is None else float(s.compiles)
