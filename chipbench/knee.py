"""Find an open-loop cell's knee once, by a sweep on the chip.

    python3 -m chipbench.knee --workload olmo-1b-k4.chat --seed 7 \
        --rates 1.5 2 2.5 3 3.5 --seconds 40

One process: the cell's set-up once, then for each offered rate a window
of that cell's traffic at that rate, drained before the next. For each rate
it prints one JSON line: offered and completed rate, tokens/s, the p90s of
TTFT and queue wait, and the queue-wait trend (least-squares slope of
due -> admission wait against due time, seconds per second). The knee is
the highest rate whose queue wait shows no trend over the window (slope
under 0.002 s/s); the cell runs at 4/5 of it, a number written into its
traffic file. A looser rule (0.02 s/s) let through rates at which the
slots were 96% busy and TTFT swung by seconds between runs.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import numpy as np

from chipbench.run import start
from chipbench.window import percentile, ttft_s


def trend(records) -> float:
    pts = [(r["due"], r["admitted"] - r["due"]) for r in records
           if r["admitted"] is not None]
    if len(pts) < 3:
        return float("nan")
    x, y = np.array(pts).T
    return float(np.polyfit(x, y, 1)[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    h = start(args.workload, prog="chipbench.knee")
    if h is None:
        return 1
    if not h.mix.get("rate_per_s"):
        print("chipbench.knee: the cell's traffic is not open-loop",
              file=sys.stderr)
        return 2
    rates = sorted(args.rates)
    run = h.run_info(args.seed, args.seconds, False)
    s = h.module.setup(h.config, dict(h.mix, rate_per_s=rates[0]), run)
    for i, rate in enumerate(rates):
        # the configuration's own window: submit at due times, then drain
        s.mix = dict(h.mix, rate_per_s=rate)
        s.run = dataclasses.replace(run, seed=args.seed + i + 1)
        recs = h.module.window(s, args.seconds)["requests"]
        done = [r for r in recs if r["finish"] is not None]
        in_window = [r for r in done if r["finish"] <= args.seconds]
        wait = [r["admitted"] - r["due"] for r in done]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(recs),
            "completed_in_window": len(in_window),
            "tokens_per_s_completed":
                sum(r["tokens"] for r in in_window) / args.seconds,
            "ttft_p90_ms": percentile(ttft_s(done), 90) * 1e3,
            "queue_wait_p90_ms": percentile(wait, 90) * 1e3,
            "queue_wait_trend_s_per_s": trend(recs),
            "unfinished": len(recs) - len(done)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
