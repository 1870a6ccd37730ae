"""The program's own spans in a traced run, and the device's idle time put
down to them.

The program opens host spans named ``repro.<name>`` (`repro.obs.span`) on
the profiler's host plane, on the same clock as the device's operations.
From the run's ``.xplane.pb``, read once, this takes the ``repro.*`` spans,
the harness's ``chipbench.*`` spans (``chipbench.window`` among them), the
first chip's operation intervals and the compiles (host events named
``backend_compile_and_load``), and reduces them inside the window to:

- per program span name: host seconds, count and summed integer stats;
- the first chip's idle time, split at span boundaries, each piece put
  down to the innermost ``repro.*`` span over it, else to the innermost
  ``chipbench.*`` span, else to ``none``;
- the number of compiles that start inside the window.

A span counts in the window when it starts there. `of_run` returns None
when the trace holds no ``repro.*`` span (a program that opens none), so a
reader reads nothing rather than a wrong number.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from chipbench import trace

PREFIX = "repro."
COMPILE = "backend_compile_and_load"
NONE = "none"


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Totals:
    """One span name inside the window."""

    seconds: float = 0.0            # host time, clipped to the window
    count: int = 0
    stats: Dict[str, int] = dataclasses.field(
        default_factory=lambda: defaultdict(int))


@dataclasses.dataclass
class ProgramSpans:
    window_s: float
    spans: Dict[str, Totals]
    # (innermost span, every span name over it) -> idle seconds
    idle: Dict[Tuple[str, Tuple[str, ...]], float]
    compiles: int

    def count(self, name: str) -> int:
        return self.spans[name].count if name in self.spans else 0

    def stat(self, name: str, key: str) -> int:
        return self.spans[name].stats.get(key, 0) if name in self.spans else 0

    def idle_in(self, *names: str) -> float:
        """Idle seconds whose innermost span is one of ``names``."""
        return sum(s for (inner, _), s in self.idle.items() if inner in names)

    def idle_under(self, prefix: str) -> float:
        """Idle seconds with a span whose name starts with ``prefix`` over
        them, at any depth."""
        return sum(s for (_, over), s in self.idle.items()
                   if any(n.startswith(prefix) for n in over))

    def split(self) -> Dict[str, float]:
        """Idle seconds by innermost span, largest first."""
        out: Dict[str, float] = defaultdict(float)
        for (inner, _), s in self.idle.items():
            out[inner] += s
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def _innermost(active: List[Span]) -> str:
    for prefix in (PREFIX, trace.SPAN_PREFIX):
        inner = [s for s in active if s.name.startswith(prefix)]
        if inner:
            return min(inner, key=lambda s: s.dur_ns).name
    return NONE


def _segments(spans: List[Span], lo: float, hi: float):
    """[(a, b, innermost, names over it)] covering lo..hi, cut at every span
    boundary."""
    edges = sorted([(max(s.start_ns, lo), 1, i) for i, s in enumerate(spans)]
                   + [(min(s.end_ns, hi), 0, i) for i, s in enumerate(spans)])
    out = []
    active: Dict[int, Span] = {}
    t, k = lo, 0
    while k <= len(edges):
        nxt = edges[k][0] if k < len(edges) else hi
        if nxt > t:
            act = list(active.values())
            out.append((t, nxt, _innermost(act),
                        tuple(sorted({s.name for s in act}))))
            t = nxt
        if k == len(edges):
            break
        # every span that ends or starts at this time, ends first
        while k < len(edges) and edges[k][0] == nxt:
            _, opens, i = edges[k]
            if opens:
                active[i] = spans[i]
            else:
                active.pop(i, None)
            k += 1
    return out


def reduce(ops: List[Tuple[float, float]], host: List[Span],
           compiles: List[float]) -> Optional[ProgramSpans]:
    """``ops``: the first chip's (start, end) operation intervals in ns;
    ``host``: the ``repro.*`` and ``chipbench.*`` spans; ``compiles``: the
    start of each compile. None when no span is a ``repro.*`` one."""
    if not any(s.name.startswith(PREFIX) for s in host):
        return None
    win = [s for s in host if s.name == trace.WINDOW_SPAN]
    if win:
        lo, hi = win[0].start_ns, win[0].end_ns
    elif ops:
        lo, hi = min(a for a, _ in ops), max(b for _, b in ops)
    else:
        return None
    inside = [s for s in host if s.name != trace.WINDOW_SPAN
              and s.end_ns > lo and s.start_ns < hi]
    totals: Dict[str, Totals] = defaultdict(Totals)
    for s in inside:
        if not s.name.startswith(PREFIX):
            continue
        t = totals[s.name]
        t.seconds += (min(s.end_ns, hi) - max(s.start_ns, lo)) * 1e-9
        if s.start_ns >= lo:
            t.count += 1
            for key, value in s.stats.items():
                t.stats[key] += value
    busy = trace.clip(trace.union(ops), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    idle: Dict[Tuple[str, Tuple[str, ...]], float] = defaultdict(float)
    segs = _segments(inside, lo, hi)
    j = 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            sa, sb, inner, over = segs[k]
            idle[(inner, over)] += (min(b, sb) - max(a, sa)) * 1e-9
            k += 1
    return ProgramSpans(
        window_s=(hi - lo) * 1e-9, spans=dict(totals), idle=dict(idle),
        compiles=sum(1 for c in compiles if lo <= c < hi))


def _int_stats(event) -> Dict[str, int]:
    return {k: v for k, v in event.stats
            if isinstance(v, int) and not isinstance(v, bool)}


def load(path: str):
    """(first chip's op intervals, host spans, compile starts) of a trace."""
    from jax.profiler import ProfileData

    chips = {}
    host: List[Span] = []
    compiles: List[float] = []
    for plane in ProfileData.from_file(path).planes:
        m = trace._DEVICE.match(plane.name)
        if m:
            chips[int(m.group(1))] = plane
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith((PREFIX, trace.SPAN_PREFIX)):
                        host.append(Span(name, float(e.start_ns),
                                         float(e.duration_ns), _int_stats(e)))
                    elif name == COMPILE:
                        compiles.append(float(e.start_ns))
    ops: List[Tuple[float, float]] = []
    if chips:
        for line in chips[min(chips)].lines:
            if line.name == trace.OPS_LINE:
                ops += [(float(e.start_ns), float(e.start_ns + e.duration_ns))
                        for e in line.events]
    return ops, host, compiles


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime: float) -> Optional[ProgramSpans]:
    return reduce(*load(path))


def of_run() -> Optional[ProgramSpans]:
    """The current run's program spans, read from its trace once."""
    from chipbench import run

    try:
        path = trace.find_xplane(run.TRACE_DIR)
    except FileNotFoundError:
        return None
    return _read(path, os.path.getmtime(path))
