"""Window arithmetic shared by the metric readers.

A configuration module's ``window`` returns a plain dict:

- ``window_s``: the measured span in seconds;
- ``requests``: one dict per request that was due inside the window (for
  a backlog, per request that finished inside it), with
  ``due``, ``submit``, ``admitted``, ``first``, ``finish`` (seconds after
  the window opened; None where it never happened) and ``tokens``;
- ``tokens``: output tokens produced inside the window, counting requests
  still in flight when it closed;
- ``passes``: whole stage passes completed inside the window;
- ``counters``: what the configuration module read from the program, by name.

Tails are taken over every request due in the window, timed from its due
time; a request that never produced a token counts as infinitely late.
"""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (q in [0, 100]); NaN on no values.

    Same arithmetic as `repro.serving.metrics.percentile`, kept here so the
    yardstick cannot move with the program."""
    if not values:
        return math.nan
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (q / 100.0) * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    frac = pos - lo
    if frac == 0.0:
        return float(xs[lo])
    return float(xs[lo] * (1.0 - frac) + xs[hi] * frac)


def ttft_s(requests: Iterable[dict]) -> List[float]:
    """Due time to first token, for every request due in the window."""
    return [math.inf if r.get("first") is None else r["first"] - r["due"]
            for r in requests]


def tpot_s(requests: Iterable[dict]) -> List[float]:
    """(last token - first token) / (tokens - 1), for requests with >= 2."""
    out = []
    for r in requests:
        if r.get("first") is None or r.get("finish") is None:
            continue
        if r["tokens"] >= 2:
            out.append((r["finish"] - r["first"]) / (r["tokens"] - 1))
    return out


def interpolate(samples: Sequence[Tuple[float, float]], t: float) -> float:
    """Value at time ``t`` of a counter sampled as (time, value) pairs in
    time order; held flat outside the samples."""
    if not samples:
        raise ValueError("no samples")
    if t <= samples[0][0]:
        return float(samples[0][1])
    for (t0, v0), (t1, v1) in zip(samples, samples[1:]):
        if t0 <= t <= t1:
            if t1 == t0:
                return float(v1)
            return float(v0 + (v1 - v0) * (t - t0) / (t1 - t0))
    return float(samples[-1][1])


def count_between(samples: Sequence[Tuple[float, float]], start: float,
                  end: float) -> float:
    """Increase of a sampled counter between two times."""
    return interpolate(samples, end) - interpolate(samples, start)


def per_pass(window_s: float, passes: int) -> Optional[float]:
    """Seconds per whole pass; None when no pass completed."""
    if passes <= 0:
        return None
    return window_s / passes
