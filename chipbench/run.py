"""One benchmark run: set up a cell, measure one window, check, report.

    python -m chipbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run is one process on the machine that holds the chips. It fails, and
prints no result, when JAX finds no TPU or fewer chips than the cell asks
for, or when the checkout holds no ``src/repro``. Set-up (``setup_s``) is
everything from process start to the window's opening: weights, plan,
compiles (from the persistent cache after the first run), warm-up and any
ramp the traffic asks for. After the window the configuration module frees the program's
state and compares what the window produced with the plain reference; each
compared number is printed beside its limit, last on stderr and last in the
result line.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import shutil
import sys
import time
from typing import Callable, Optional

from chipbench import manifest as mf

TRACE_DIR = mf.ROOT / "chipbench_out" / "trace"


@dataclasses.dataclass
class RunInfo:
    """What a configuration module is told about the run."""

    cell: dict
    seed: int
    seconds: float
    chips: int
    trace: bool
    span: Callable            # span(name) -> context manager


@dataclasses.dataclass
class Check:
    """One compared number: correct while ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


def all_ok(checks: list) -> bool:
    """A run is correct when it compared something and every number held."""
    return bool(checks) and all(c.ok for c in checks)


@dataclasses.dataclass
class Context:
    """What a metric reader gets."""

    cell: dict
    config: dict
    mix: dict
    window: dict
    setup_s: float
    device_kind: str
    n_devices: int
    trace: Optional[object] = None      # chipbench.trace.Summary


def process_start() -> float:
    """Wall-clock time this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        ticks = int(fields[19])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="python -m chipbench",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def span_factory(enabled: bool):
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax

    return lambda name: jax.profiler.TraceAnnotation(f"chipbench.{name}")


def check_devices(chips: int) -> Optional[str]:
    """None when JAX sees a TPU with at least ``chips`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"needs a TPU, JAX found {devs[0].platform}"
    if len(devs) < chips:
        return f"the cell needs {chips} chips, JAX found {len(devs)}"
    return None


def memory_peak(n: int) -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:n]]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def read_metrics(manifest: dict, section: str, ctx: Context) -> dict:
    out = {}
    for m in mf.metrics_for(manifest, section, ctx.cell["name"]):
        value = mf.reader(section, m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _num(x: float):
    return x if math.isfinite(x) else str(x)


@dataclasses.dataclass
class Harness:
    """A cell as its files state it, with the configuration's module."""

    manifest: dict
    cell: dict
    config: dict
    mix: dict
    module: object

    def run_info(self, seed: int, seconds: float, trace: bool) -> RunInfo:
        return RunInfo(cell=self.cell, seed=seed, seconds=seconds,
                       chips=int(self.cell["chips"]), trace=trace,
                       span=span_factory(trace))

    def checks(self, numbers: dict) -> list:
        """Each compared number beside its limit in the configuration's
        ``check`` group."""
        return [Check(name, float(value), float(self.config["check"][name]))
                for name, value in numbers.items()]


def start(workload: str, *, require_tpu: bool = True,
          prog: str = "chipbench") -> Optional[Harness]:
    """The start-up every entry shares: the cell's files, the device check
    (None after printing why, when the chips are missing) and the
    persistent compile cache."""
    manifest = mf.load()
    cell = mf.cell(manifest, workload)
    if not (mf.ROOT / "src" / "repro").is_dir():
        print(f"{prog}: no src/repro under {mf.ROOT}", file=sys.stderr)
        return None
    sys.path.insert(0, str(mf.ROOT / "src"))
    import jax

    if require_tpu:
        why = check_devices(int(cell["chips"]))
        if why:
            print(f"{prog}: {why}", file=sys.stderr)
            return None
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program, however quick to compile, is kept: the second run of a
    # cell must find all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from chipbench import traffic

    return Harness(manifest=manifest, cell=cell,
                   config=mf.config_file(manifest, cell["config"]),
                   mix=traffic.load(cell["traffic"]),
                   module=mf.config_module(cell["config"]))


def main(argv=None, *, require_tpu: bool = True) -> int:
    t_process = process_start()
    args = parse(argv)
    h = start(args.workload, require_tpu=require_tpu)
    if h is None:
        return 1
    import jax

    manifest, cell, config, module = h.manifest, h.cell, h.config, h.module
    run = h.run_info(args.seed, args.seconds, bool(args.trace))
    session = module.setup(config, h.mix, run)

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    setup_s = time.time() - t_process
    win = module.window(session, args.seconds)
    if args.trace:
        jax.profiler.stop_trace()
    peak = memory_peak(run.chips)

    dev = jax.devices()[0]
    ctx = Context(cell=cell, config=config, mix=h.mix, window=win,
                  setup_s=setup_s, device_kind=dev.device_kind,
                  n_devices=jax.device_count())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": peak}
    result = {}
    if args.trace:
        from chipbench import trace

        ctx.trace = trace.reduce_dir(TRACE_DIR, n_devices=run.chips)
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        result["metrics"] = read_metrics(manifest, "per_layer", ctx)
        result["breakdown"] = ctx.trace.breakdown()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        result["metrics"] = read_metrics(manifest, "end_to_end", ctx)

    t_check = time.perf_counter()
    checks = h.checks(module.compared(session, win))
    print(f"reference check took {time.perf_counter() - t_check:.3f} s",
          file=sys.stderr)
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    line = {"correct": all_ok(checks),
            "attempted": int(win.get("attempted", 0)),
            "failed": int(win.get("failed", 0)),
            **result,
            "device": device,
            "checks": {c.name: {"value": _num(c.value), "limit": c.limit}
                       for c in checks}}
    sys.stdout.flush()
    print(json.dumps(line))
    return 0
