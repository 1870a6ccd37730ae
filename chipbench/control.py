"""Read a cell's compared numbers for the program and for its control.

    python3 -m chipbench.control --workload olmo-1b-k4.chat \
        --seeds 11 12 13 --seconds 20

Not part of any benchmark run. For each seed, one process runs the cell's
set-up and a window at the cell's own load and size, then holds two sets of
numbers against the limits, through the same checks a benchmark run makes:
what the program produced, and the control, the plain reference in the
program's place computed one precision step lower than the configuration
states (float8 for olmo's bfloat16 compute, bfloat16 for the profiler's
float32 energy sums). It prints one JSON line a seed; the program has to
come out correct and the control not. The limits in the configuration
files are set from these readings: above the program's, below the
control's.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

from chipbench.run import all_ok, start


def main(argv=None, *, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    h = start(args.workload, require_tpu=require_tpu,
              prog="chipbench.control")
    if h is None:
        return 1
    for seed in args.seeds:
        session = h.module.setup(h.config, h.mix,
                                 h.run_info(seed, args.seconds, False))
        win = h.module.window(session, args.seconds)
        line = {"workload": h.cell["name"], "seed": seed}
        for side, control in (("program", False), ("control", True)):
            checks = h.checks(h.module.compared(session, win,
                                                control=control))
            line[side] = {c.name: {"value": c.value, "limit": c.limit}
                          for c in checks}
            line[f"{side}_correct"] = all_ok(checks)
        print(json.dumps(line), flush=True)
        del session, win
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
