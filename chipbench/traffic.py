"""The one traffic generator. A mix is a data file, ``traffic/<mix>.json``.

A configuration module asks for the requests of three segments, each found
from the mix's keys alone:

- ``"queued"``: ``backlog`` requests, all queued before the window opens
  (none without the key);
- ``"ramp"``: open-loop arrivals over ``ramp_s`` untimed seconds before the
  window (none without ``rate_per_s``);
- ``"window"``: open-loop arrivals due inside the window (none without
  ``rate_per_s``).

Keys a mix may hold:

- ``rate_per_s``, ``ramp_s``: the offered open-loop rate and the ramp;
- ``gaps``: the distribution of the gaps between arrivals, scaled to the
  rate: ``{"dist": "exponential"}`` (Poisson, the default) or
  ``{"dist": "gamma", "cv": c}``; a coefficient of variation above 1 gives
  bursts at the same mean rate (a Gamma renewal process, as BurstGPT
  models bursty LLM traffic);
- ``backlog``, ``block``: the queued requests, their lengths in blocks of
  ``block``, each block the whole stratified set in its own order, so any
  stretch of the queue that a window drains holds nearly the same work;
- ``prompt`` and ``output``: a length distribution each,
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
  ``{"dist": "uniform", "min", "max"}`` (both ends included);
- ``greedy``: decoding is greedy (the only mode the check can compare);
- ``generator``: the name of a file ``traffic/<generator>.py`` whose
  ``requests(mix, seed, segment, duration, vocab)`` replaces this module's,
  for an arrival process that no data here can state.

Every seed gets the same set of lengths and gaps: each is the distribution's
quantile at the points ``(i + 0.5) / n``, and the seed only chooses their
order and the prompt tokens. Two seeds therefore offer the same amount of
work, which keeps the spread between runs down to what the system adds.
"""

from __future__ import annotations

import dataclasses
import json
import statistics
from pathlib import Path
from typing import List

import numpy as np

from chipbench.manifest import load_module

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
SEGMENTS = {"ramp": 1, "window": 2, "queued": 3}
GAPS = ("exponential", "gamma")


@dataclasses.dataclass
class Request:
    due: float                 # seconds after the segment's start
    prompt: np.ndarray         # (prompt_len,) int32
    max_new_tokens: int


def load(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    """The mix ``name``; a named generator file must be beside it."""
    mix = json.loads((directory / f"{name}.json").read_text())
    gen = mix.get("generator")
    if gen is not None and not (directory / f"{gen}.py").is_file():
        raise ValueError(f"traffic {name}: no generator file {gen}.py")
    dist = mix.get("gaps", {}).get("dist", "exponential")
    if dist not in GAPS:
        raise ValueError(f"traffic {name}: gaps must be one of {GAPS}, "
                         f"got {dist!r}")
    return mix


def rng_for(seed: int, segment: str) -> np.random.Generator:
    """An independent stream per (seed, segment); any whole seed."""
    return np.random.default_rng([int(seed) % 2**64, SEGMENTS[segment]])


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths: the distribution's stratified quantiles, clipped to
    [min, max], in an order drawn from ``rng``."""
    u = quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(p) for p in u])
        vals = np.round(float(spec["median"]) * np.exp(float(spec["sigma"]) * z))
    elif spec["dist"] == "uniform":
        vals = np.floor(lo + u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return rng.permutation(np.clip(vals, lo, hi).astype(np.int64))


def gap_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of the gap distribution, mean about 1."""
    u = quantiles(n)
    if spec.get("dist", "exponential") == "exponential":
        return -np.log1p(-u)
    from scipy.stats import gamma

    shape = 1.0 / float(spec["cv"]) ** 2
    return gamma.ppf(u, shape, scale=1.0 / shape)


def due_times(mix: dict, duration: float, rng: np.random.Generator
              ) -> np.ndarray:
    """Open-loop arrivals over ``duration``: round(rate * duration) gaps
    (stratified quantiles, shuffled), scaled to fill the duration exactly,
    so the offered rate is the stated one."""
    n = max(1, int(round(float(mix["rate_per_s"]) * duration)))
    gaps = rng.permutation(gap_quantiles(mix.get("gaps", {}), n))
    gaps *= duration / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def requests(mix: dict, seed: int, segment: str, duration: float,
             vocab: int, directory: Path = TRAFFIC_DIR) -> List[Request]:
    """The requests of one segment (``"queued"``, ``"ramp"`` or
    ``"window"``)."""
    if "generator" in mix:
        gen = load_module(directory / f"{mix['generator']}.py")
        return gen.requests(mix, seed, segment, duration, vocab)
    rng = rng_for(seed, segment)
    if segment == "queued":
        due = np.zeros(int(mix.get("backlog", 0)))
    elif mix.get("rate_per_s") and duration > 0:
        due = due_times(mix, duration, rng)
    else:
        due = np.zeros(0)
    n = len(due)
    if n == 0:
        return []
    block = int(mix.get("block", n))
    blocks = -(-n // block)
    plen = np.concatenate([lengths(mix["prompt"], block, rng)
                           for _ in range(blocks)])
    olen = np.concatenate([lengths(mix["output"], block, rng)
                           for _ in range(blocks)])
    return [Request(due=float(due[i]),
                    prompt=rng.integers(1, vocab, size=int(plen[i]),
                                        dtype=np.int32),
                    max_new_tokens=int(olen[i]))
            for i in range(n)]


def ramp_seconds(mix: dict) -> float:
    return float(mix.get("ramp_s", 0.0))
