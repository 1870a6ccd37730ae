"""`repro` command-line entry point: drive the Pipeline from a shell.

    repro profile  [--config cfg.json | presets] [--plan-out BASE]
    repro compress [--config cfg.json | presets] [--plan-out BASE]
    repro export   [--plan-in BASE | presets]    [--plan-out BASE]
    repro serve    [--plan-in BASE | presets]    [--mode engine|oneshot]

Each subcommand runs the same `repro.pipeline.Pipeline` up to a stage:
``profile`` stops after ``energy_model`` (per-layer stats + energy shares —
a profiling report), ``compress`` after ``schedule``, ``export`` after
``export``, and ``serve`` runs everything. ``--plan-in`` resumes a saved
`CompressionPlan` (completed stages are skipped); ``--plan-out`` saves the
resulting plan as ``BASE.json`` + ``BASE.npz``.

This module imports **no stage code at parse time** — ``repro --help`` (and
the argparse error paths) never touch jax. Stage modules load lazily inside
`_execute` once a subcommand actually runs.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Optional

# subcommand -> last pipeline stage it runs (see repro.pipeline.schema.STAGES)
COMMAND_STAGE = {
    "profile": "energy_model",
    "compress": "schedule",
    "export": "export",
    "serve": "serve",
}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="repro",
        description="Energy-aware layer-wise compression pipeline "
                    "(profile -> energy_model -> schedule -> export -> "
                    "serve) over one CompressionPlan artifact.")
    sub = ap.add_subparsers(dest="command", required=True)
    for command, stage in COMMAND_STAGE.items():
        p = sub.add_parser(
            command,
            help=f"run the pipeline through its '{stage}' stage")
        p.add_argument("--config", default=None, metavar="JSON",
                       help="PipelineConfig JSON file (see docs/pipeline.md)")
        p.add_argument("--target", choices=("cnn", "lm", "moe", "scan"),
                       default=None,
                       help="target kind when building a config from flags "
                            "(moe/scan: routing-aware LM targets)")
        p.add_argument("--arch", default=None,
                       help="cnn: lenet5|resnet8|resnet20|resnet50; "
                            "lm/moe/scan: repro.configs arch id "
                            "(e.g. olmo-1b, phi3.5-moe-42b-a6.6b, "
                            "mamba2-1.3b)")
        p.add_argument("--reduced", action="store_true",
                       help="CPU-smoke preset (tiny budgets; lm: scaled-down "
                            "config)")
        p.add_argument("--steps", type=int, default=None,
                       help="override train.qat_steps")
        p.add_argument("--search-mode", choices=("batched", "serial"),
                       default=None, help="override schedule.search_mode")
        p.add_argument("--compress-k", type=int, default=None,
                       help="lm: restrict every eligible matmul to a "
                            "k-value codebook")
        p.add_argument("--seed", type=int, default=None,
                       help="override target.seed")
        p.add_argument("--plan-in", default=None, metavar="BASE",
                       help="resume from a saved plan (BASE.json + BASE.npz)")
        p.add_argument("--plan-out", default=None, metavar="BASE",
                       help="save the resulting plan to BASE.json + BASE.npz")
        p.add_argument("--verify-cosim", action="store_true",
                       help="gate the profiler's transition histograms "
                            "against the bit-accurate systolic cosim "
                            "(repro.cosim) on the sampled tiles")
        p.add_argument("--quiet", action="store_true",
                       help="suppress per-stage progress output")
        if command == "serve":
            p.add_argument("--mode", choices=("engine", "oneshot"),
                           default=None, help="override serve.mode")
            p.add_argument("--requests", type=int, default=None)
            p.add_argument("--prompt-len", type=int, default=None)
            p.add_argument("--new-tokens", type=int, default=None)
            p.add_argument("--mixed", action=argparse.BooleanOptionalAction,
                           default=None,
                           help="vary request lengths across buckets")
            p.add_argument("--max-batch", type=int, default=None,
                           help="engine wave width")
            p.add_argument("--temperature", type=float, default=None)
            p.add_argument("--verify-oneshot", action="store_true",
                           default=None,
                           help="cross-check engine tokens vs the oneshot "
                                "fallback")
            p.add_argument("--plans", nargs="+", default=None,
                           metavar="SPEC",
                           help="fleet serving: resident plan variants "
                                "routed across by load/budget. Each SPEC is "
                                "'base', 'k<N>[m<M>]' (k-value codebook + "
                                "MSR bits), or a saved CompressionPlan "
                                "base path")
            p.add_argument("--plans-dir", default=None, metavar="DIR",
                           help="fleet serving: load every saved "
                                "CompressionPlan under DIR as a resident "
                                "variant")
    return ap


def _serve_overrides(args) -> dict:
    fields = {
        "mode": getattr(args, "mode", None),
        "compress_k": args.compress_k,
        "requests": getattr(args, "requests", None),
        "prompt_len": getattr(args, "prompt_len", None),
        "new_tokens": getattr(args, "new_tokens", None),
        "mixed": getattr(args, "mixed", None),
        "max_batch": getattr(args, "max_batch", None),
        "temperature": getattr(args, "temperature", None),
        "verify_oneshot": getattr(args, "verify_oneshot", None),
        "plans": (tuple(args.plans)
                  if getattr(args, "plans", None) else None),
        "plans_dir": getattr(args, "plans_dir", None),
    }
    return {k: v for k, v in fields.items() if v is not None}


def _build_config(args):
    """Resolve the PipelineConfig from --config / presets / flag overrides.

    Imported lazily: this is the first point that touches jax."""
    from repro.pipeline.config import (
        PipelineConfig,
        reduced_cnn_config,
        reduced_lm_config,
        reduced_moe_config,
        reduced_scan_config,
    )

    kind = args.target
    if kind is None and (args.compress_k or getattr(args, "plans", None)
                         or getattr(args, "plans_dir", None)):
        kind = "lm"  # codebook restriction / fleet serving are LM schedules
    if args.config:
        cfg = PipelineConfig.load(args.config)
    elif args.reduced:
        if kind == "moe":
            cfg = reduced_moe_config(args.arch or "phi3.5-moe-42b-a6.6b")
        elif kind == "scan":
            cfg = reduced_scan_config(args.arch or "mamba2-1.3b")
        elif kind == "lm":
            cfg = reduced_lm_config(args.arch or "olmo-1b")
        else:
            cfg = reduced_cnn_config()
    else:
        cfg = PipelineConfig()

    overrides: dict = {}
    target_over = {}
    if kind:
        target_over["kind"] = kind
    if args.arch:
        target_over["arch"] = args.arch
    if args.seed is not None:
        target_over["seed"] = args.seed
    if target_over:
        overrides["target"] = target_over
    if args.steps is not None:
        overrides["train"] = {"qat_steps": args.steps}
    if args.search_mode is not None:
        overrides["schedule"] = {"search_mode": args.search_mode}
    if getattr(args, "verify_cosim", False):
        overrides["profile"] = {"verify_cosim": True}
    serve_over = _serve_overrides(args)
    if serve_over:
        overrides["serve"] = serve_over
    return cfg.with_overrides(overrides)


def _execute(args) -> int:
    from repro.compile_cache import enable_compile_cache
    from repro.pipeline.pipeline import Pipeline
    from repro.pipeline.plan import CompressionPlan

    enable_compile_cache()

    verbose = not args.quiet
    if args.plan_in:
        plan = CompressionPlan.load(args.plan_in)
        pipe = Pipeline.from_plan(plan)
        # CLI flags still override the embedded config for the stages that
        # remain to run (e.g. `repro serve --plan-in p --mode oneshot`);
        # target identity is fixed by the plan and cannot be overridden.
        over: dict = {}
        if args.steps is not None:
            over["train"] = {"qat_steps": args.steps}
        if args.search_mode is not None:
            over["schedule"] = {"search_mode": args.search_mode}
        if getattr(args, "verify_cosim", False):
            over["profile"] = {"verify_cosim": True}
        serve_over = _serve_overrides(args)
        if serve_over:
            over["serve"] = serve_over
        if over:
            pipe.cfg = pipe.cfg.with_overrides(over)
    else:
        pipe = Pipeline(_build_config(args))

    plan = pipe.run_until(COMMAND_STAGE[args.command], verbose=verbose)
    print(json.dumps(plan.summary(), indent=2))
    if args.plan_out:
        json_path, npz_path = plan.save(args.plan_out)
        print(f"plan saved: {json_path} + {npz_path}")
    return 0


def main(argv: Optional[list] = None) -> int:
    args = build_parser().parse_args(argv)
    return _execute(args)


if __name__ == "__main__":
    sys.exit(main())
