"""CPU tests of `chipbench.program_spans`: the device's idle time put down
to the program's spans, on synthetic events and on traces recorded here.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_program_spans.py
"""

from __future__ import annotations

from pathlib import Path

import pytest

from chipbench import manifest as mf
from chipbench import program_spans as ps

FIXTURE = Path(__file__).with_name("data") / "lut_step.xplane.pb"
NEW = ("step_idle_ms.chat", "step_idle_ms.batch", "sample_idle_ms.batch",
       "decode_rows.batch", "compiles.chat", "layer_idle_ms.profile")


def _span(name, start, dur, **stats):
    return ps.Span(name, float(start), float(dur), stats)


WINDOW = _span("chipbench.window", 0, 1000)
# busy 0..100 and 400..500: idle 100..400 and 500..1000
OPS = [(0.0, 60.0), (50.0, 100.0), (400.0, 500.0)]


def _split(host, ops=OPS):
    s = ps.reduce(ops, [WINDOW, *host], [])
    return {k: round(v * 1e9) for k, v in s.split().items()}


@pytest.mark.parametrize("host,expected", [
    # a gap cut at program span boundaries, each piece to the innermost
    ([_span("repro.engine.step", 50, 450),
      _span("repro.engine.fetch", 150, 100),
      _span("repro.engine.sample", 250, 50)],
     {"repro.engine.step": 150, "repro.engine.fetch": 100,
      "repro.engine.sample": 50, "none": 500}),
    # a repro span wins over a shorter harness span inside it
    ([_span("repro.engine.step", 0, 1000),
      _span("chipbench.engine.step", 120, 10)],
     {"repro.engine.step": 800}),
    # no repro span over a piece: the innermost harness span, then none
    ([_span("repro.engine.decode", 100, 50),
      _span("chipbench.window_outer", 0, 1000),
      _span("chipbench.engine.step", 0, 300)],
     {"repro.engine.decode": 50, "chipbench.engine.step": 150,
      "chipbench.window_outer": 600}),
    ([_span("repro.engine.decode", 100, 50),
      _span("chipbench.engine.step", 0, 300)],
     {"repro.engine.decode": 50, "chipbench.engine.step": 150,
      "none": 600}),
])
def test_idle_split_by_innermost_span(host, expected):
    assert _split(host) == expected


def test_idle_under_counts_every_depth_once():
    s = ps.reduce(OPS, [WINDOW, _span("repro.engine.step", 50, 450),
                        _span("repro.engine.fetch", 150, 100),
                        _span("repro.profile.layer", 600, 100),
                        _span("repro.profile.stats", 620, 50)], [])
    assert s.idle_under("repro.engine.step") == pytest.approx(300e-9)
    assert s.idle_in("repro.engine.fetch") == pytest.approx(100e-9)
    assert s.idle_under("repro.profile.") == pytest.approx(100e-9)
    assert sum(s.split().values()) == pytest.approx(800e-9)


def test_totals_count_spans_that_start_in_the_window():
    host = [WINDOW,
            _span("repro.engine.decode", -50, 100, rows=8, active=5),
            _span("repro.engine.decode", 200, 100, rows=8, active=7),
            _span("repro.engine.decode", 950, 100, rows=8, active=6),
            _span("repro.engine.decode", 1200, 100, rows=8, active=1)]
    s = ps.reduce(OPS, host, [])
    assert s.count("repro.engine.decode") == 2
    assert s.stat("repro.engine.decode", "active") == 13
    assert s.stat("repro.engine.decode", "rows") == 16
    assert s.spans["repro.engine.decode"].seconds == pytest.approx(200e-9)
    assert s.count("repro.engine.step") == 0
    assert s.window_s == pytest.approx(1000e-9)


def test_compiles_counted_only_inside_the_window():
    s = ps.reduce(OPS, [WINDOW, _span("repro.engine.step", 0, 10)],
                  [-5.0, 0.0, 500.0, 999.0, 1000.0, 4000.0])
    assert s.compiles == 3


def test_none_without_a_program_span():
    assert ps.reduce(OPS, [WINDOW, _span("chipbench.engine.step", 0, 10)],
                     [1.0]) is None


@pytest.mark.skipif(not FIXTURE.is_file(), reason="no recorded trace")
def test_a_chip_trace_without_program_spans_reads_nothing():
    ops, host, _ = ps.load(str(FIXTURE))
    assert ops and ps.reduce(ops, host, []) is None


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """A host trace recorded here with a window, program spans and two
    compiles; `of_run` reads it from the run's trace directory."""
    import jax
    import jax.numpy as jnp

    from chipbench import run

    monkeypatch.setattr(run, "TRACE_DIR", tmp_path)
    x = jax.block_until_ready(jnp.ones(3))
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("chipbench.window"):
            for active in (8, 6):
                with jax.profiler.TraceAnnotation("repro.engine.step",
                                                  queued=2):
                    with jax.profiler.TraceAnnotation(
                            "repro.engine.decode", rows=8,
                            active=active) as sp:
                        sp.set_metadata(layer="a string, not summed")
                    jax.jit(lambda y: y * active)(x)
    return tmp_path


def test_of_run_reads_the_runs_trace(traced):
    s = ps.of_run()
    assert s is not None and s is ps.of_run()       # read once
    assert s.count("repro.engine.step") == 2
    assert s.stat("repro.engine.decode", "rows") == 16
    assert s.stat("repro.engine.decode", "active") == 14
    assert "layer" not in s.spans["repro.engine.decode"].stats
    assert s.compiles == 2


def test_new_readers_on_a_recorded_trace(traced):
    ctx = type("Ctx", (), {"window": {"passes": 2}})()
    got = {n: mf.reader("per_layer", n).read(ctx) for n in NEW}
    assert got["decode_rows.batch"] == pytest.approx(14 / 16 * 100)
    assert got["compiles.chat"] == 2.0
    # no device plane here, so the idle time is the whole window
    assert got["step_idle_ms.batch"] > 0
    assert got["layer_idle_ms.profile"] == 0.0


def test_new_readers_read_nothing_without_a_trace(tmp_path, monkeypatch):
    from chipbench import run

    monkeypatch.setattr(run, "TRACE_DIR", tmp_path)
    ctx = type("Ctx", (), {"window": {"passes": 2}})()
    assert all(mf.reader("per_layer", n).read(ctx) is None for n in NEW)
