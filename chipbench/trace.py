"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Read with `jax.profiler.ProfileData`, nothing else. On a TPU each chip is a
plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
operation run, and ``XLA Modules`` one per executable run. The host plane
holds the spans the benchmark opened around its calls into the program
(``chipbench.<name>``). From these:

- busy time: the union of a chip's operation intervals, averaged over the
  chips the cell uses; the window is the host span ``chipbench.window``
  when there is one, else first to last device event;
- per-executable and per-operation device time and call counts (a TPU
  operation's event name is its HLO text, e.g.
  ``%fusion.3 = f32[8,2048]{1,0} fusion(...)``; a ``long_name`` stat wins
  where a trace has one);
- collective time: operations whose name says all-reduce, all-gather,
  reduce-scatter, all-to-all or collective-permute;
- idle gaps on the first chip, each named by the innermost benchmark span
  that covers its middle.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|psum", re.IGNORECASE)
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    long_name: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                                   # mean over chips
    ops: Dict[str, Tuple[float, int]]               # name -> (s, calls), all chips
    modules: Dict[str, Tuple[float, int]]           # name -> (s, calls), mean
    collective_s: float                             # mean over chips
    idle_gaps: List[Tuple[str, float]]              # (span, s), longest first
    op_events: List[Event]                          # first chip, in window
    n_devices: int

    def module_time(self, pattern: str) -> Tuple[float, int]:
        """(seconds, calls) of executables whose name contains ``pattern``."""
        s = n = 0
        for name, (sec, calls) in self.modules.items():
            if pattern in name:
                s, n = s + sec, n + calls
        return s, n

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][0])[:top]
        return {"device_ops": [[short(name), sec] for name, (sec, _) in ops],
                "idle_gaps": [[name, sec] for name, sec in
                              self.idle_gaps[:top]]}


def find_xplane(directory) -> str:
    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(files, key=os.path.getmtime)


def _events(line, prefix: str = "") -> List[Event]:
    """Events of one line whose name starts with ``prefix``."""
    out = []
    for e in line.events:
        name = e.name
        if not name.startswith(prefix):
            continue
        # a TPU op's event name is its HLO text; a long_name stat wins
        long_name = next((v for k, v in e.stats
                          if k == "long_name" and isinstance(v, str)), name) \
            if not prefix and not name.startswith("%") else name
        out.append(Event(name, float(e.start_ns), float(e.duration_ns),
                         long_name))
    return out


def load(path: str):
    """(devices {index: {line: [Event]}}, host spans [Event])."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            devices[int(m.group(1))] = {
                line.name: _events(line) for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans += _events(line, SPAN_PREFIX)
    return devices, spans


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _innermost(spans: List[Event], t: float) -> str:
    best: Optional[Event] = None
    for s in spans:
        if s.name == WINDOW_SPAN or not (s.start_ns <= t <= s.end_ns):
            continue
        if best is None or s.dur_ns < best.dur_ns:
            best = s
    return best.name[len(SPAN_PREFIX):] if best else "outside any span"


def summarize(devices: Dict[int, Dict[str, List[Event]]], spans: List[Event],
              n_devices: int) -> Summary:
    chips = sorted(devices)[:n_devices]
    if not chips:
        raise ValueError("the trace holds no TPU plane")
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if win:
        lo, hi = win[0].start_ns, win[0].end_ns
    else:
        evs = [e for c in chips for e in devices[c].get(OPS_LINE, [])]
        lo = min(e.start_ns for e in evs)
        hi = max(e.end_ns for e in evs)
    busy, coll = [], []
    ops: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    modules: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    for c in chips:
        evs = [e for e in devices[c].get(OPS_LINE, [])
               if e.end_ns > lo and e.start_ns < hi]
        busy_iv = clip(union([(e.start_ns, e.end_ns) for e in evs]), lo, hi)
        busy.append(sum(b - a for a, b in busy_iv))
        coll.append(sum(e.dur_ns for e in evs
                        if COLLECTIVE.search(e.long_name or e.name)))
        for e in evs:
            key = e.long_name or e.name
            ops[key][0] += e.dur_ns * 1e-9
            ops[key][1] += 1
        for e in devices[c].get(MODULES_LINE, []):
            if e.end_ns > lo and e.start_ns < hi:
                modules[e.name][0] += e.dur_ns * 1e-9 / len(chips)
                modules[e.name][1] += 1
    first = sorted([e for e in devices[chips[0]].get(OPS_LINE, [])
                    if e.end_ns > lo and e.start_ns < hi],
                   key=lambda e: e.start_ns)
    busy_iv = clip(union([(e.start_ns, e.end_ns) for e in first]), lo, hi)
    edges = [lo] + [x for iv in busy_iv for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    idle = sorted(((_innermost(spans, (a + b) / 2), (b - a) * 1e-9)
                   for a, b in gaps), key=lambda g: -g[1])
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / len(chips) * 1e-9,
        ops={k: (v[0], int(v[1])) for k, v in ops.items()},
        modules={k: (v[0], int(v[1]) // len(chips))
                 for k, v in modules.items()},
        collective_s=sum(coll) / len(chips) * 1e-9,
        idle_gaps=idle, op_events=first, n_devices=len(chips))


def reduce_dir(directory, n_devices: int) -> Summary:
    devices, spans = load(find_xplane(directory))
    return summarize(devices, spans, n_devices)


def short(op: str, limit: int = 120) -> str:
    """An HLO op's text cut to its name, output type and op kind."""
    head = op.split("{", 1)[0] if " = (" not in op else op.split(" = ", 1)[0]
    kind = re.search(r"\}\s*([a-z-]+)\(", op)
    text = head + (" " + kind.group(1) if kind else "")
    return text[:limit]


_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def shapes(long_name: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of every array type in an HLO text, output first."""
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(long_name)]
