import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# a CPU dry run, on any host: this process and the --all children it starts
# (which inherit the environment) never claim an accelerator
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces a JSON manifest under benchmarks/out/dryrun/ with:
  * memory_analysis()  (bytes per device as XLA sees them)
  * cost_analysis()    (HLO flops / bytes accessed)
  * collective_bytes   (per collective kind, parsed from the optimized HLO)
  * sharding guard report (which logical axes fell back to replication)
These manifests are the input to benchmarks/roofline.py (EXPERIMENTS.md
§Dry-run / §Roofline).

Usage:
  python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  python -m repro.launch.dryrun --arch olmo-1b --shape decode_32k --multi-pod
  python -m repro.launch.dryrun --all --jobs 4      # everything, subprocesses
"""

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

OUT_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "out" / "dryrun"

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the optimized HLO.

    Counts `-start` variants once and skips `-done`. Returns
    {kind: {"bytes": int, "count": int}} plus a "total" entry. Result bytes
    approximate per-device transferred volume (ring all-gather moves
    ~result_bytes x (n-1)/n; all-reduce ~2x operand; the roofline term applies
    kind-specific multipliers).
    """
    out = {k: {"bytes": 0, "count": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "-done" in stripped:
            continue
        for kind in _COLLECTIVES:
            # match "= TYPE[SHAPE]{...} kind(" or " kind-start("
            if f" {kind}(" not in stripped and f" {kind}-start(" not in stripped:
                continue
            m = _SHAPE_RE.search(stripped)
            if not m:
                continue
            dtype, dims = m.group(1), m.group(2)
            if dtype == "tuple" or dtype not in _DTYPE_BYTES:
                # tuple-shaped (variadic) collectives: sum every element shape
                total = 0
                for m2 in _SHAPE_RE.finditer(stripped.split("=", 1)[-1]):
                    d2, dd = m2.group(1), m2.group(2)
                    if d2 in _DTYPE_BYTES:
                        n = 1
                        for x in dd.split(","):
                            if x:
                                n *= int(x)
                        total += n * _DTYPE_BYTES[d2]
                out[kind]["bytes"] += total
                out[kind]["count"] += 1
                break
            n = 1
            for x in dims.split(","):
                if x:
                    n *= int(x)
            out[kind]["bytes"] += n * _DTYPE_BYTES[dtype]
            out[kind]["count"] += 1
            break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             qat: bool = True, with_comp: bool = True,
             remat: bool = True, q_block: int = 512, kv_block: int = 512,
             rules_override: dict | None = None, flash: bool = False,
             grad_accum: int = 1, kv_seq_shard: bool = False,
             moe_local_dispatch: bool = False, remat_save_qat: bool = False,
             tag: str = "") -> dict:
    from repro.configs import SHAPES, cell_is_runnable, get_config, skip_reason
    from repro.distributed.sharding import DEFAULT_RULES
    from repro.launch import train as TR
    from repro.launch.mesh import make_production_mesh
    from repro.models.lm import build_lm

    t0 = time.time()
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "seq": shape.seq, "batch": shape.batch,
        "qat": qat, "with_comp": with_comp, "flash": flash,
        "grad_accum": grad_accum, "q_block": q_block, "kv_block": kv_block,
        "kv_seq_shard": kv_seq_shard, "tag": tag,
    }
    if not cell_is_runnable(arch, shape_name):
        result["status"] = "skipped"
        result["skip_reason"] = skip_reason(arch, shape_name)
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = DEFAULT_RULES
    if rules_override:
        rules = rules.replace(**rules_override)
    model = build_lm(cfg)
    guard: list = []
    step_cfg = TR.StepConfig(qat=qat, with_comp=with_comp, remat=remat,
                             q_block=q_block, kv_block=kv_block, flash=flash,
                             grad_accum=grad_accum,
                             remat_save_qat=remat_save_qat)

    if shape.kind == "train":
        state = TR.abstract_train_state(model)
        state_sh = TR.train_state_shardings(model, mesh, rules, guard)
        specs = TR.batch_specs(cfg, shape)
        specs_sh = TR.batch_shardings(specs, mesh, rules)
        step = TR.make_train_step(model, step_cfg, mesh, rules,
                                  moe_local_dispatch=moe_local_dispatch)
        if with_comp:
            comp = TR.comp_abstract(model)
            comp_sh = TR.comp_shardings(model, mesh, rules, guard)
            jitted = jax.jit(step, in_shardings=(state_sh, specs_sh, comp_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,))
            with mesh:
                lowered = jitted.lower(state, specs, comp)
        else:
            jitted = jax.jit(step, in_shardings=(state_sh, specs_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,))
            with mesh:
                lowered = jitted.lower(state, specs)
    elif shape.kind == "prefill":
        params = TR.abstract_serve_params(model)
        params_sh = TR.make_param_shardings(model.spec, mesh, rules,
                                            guard_report=guard)
        specs = TR.batch_specs(cfg, shape)
        specs_sh = TR.batch_shardings(specs, mesh, rules)
        step = TR.make_prefill_step(model, step_cfg, mesh, rules)
        jitted = jax.jit(step, in_shardings=(params_sh, specs_sh))
        with mesh:
            lowered = jitted.lower(params, specs)
    else:  # decode
        params = TR.abstract_serve_params(model)
        params_sh = TR.make_param_shardings(model.spec, mesh, rules,
                                            guard_report=guard)
        cache = TR.decode_cache_specs(model, shape)
        cache_sh = TR.cache_shardings(model, shape, mesh, rules,
                                      guard_report=guard,
                                      kv_seq_shard=kv_seq_shard)
        tokens = jax.ShapeDtypeStruct((shape.batch, 1), jnp.int32)
        tokens_sh = TR.batch_shardings({"tokens": tokens}, mesh, rules)["tokens"]
        step = TR.make_serve_step(model, step_cfg, mesh, rules)
        jitted = jax.jit(step, in_shardings=(params_sh, cache_sh, tokens_sh),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(1,))
        with mesh:
            lowered = jitted.lower(params, cache, tokens)

    t_lower = time.time()
    compiled = lowered.compile()
    t_compile = time.time()

    mem = compiled.memory_analysis()
    mem_dict = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            mem_dict[attr] = int(getattr(mem, attr, 0) or 0)
    cost = compiled.cost_analysis() or {}
    cost_dict = {k: float(v) for k, v in cost.items()
                 if isinstance(v, (int, float)) and (
                     k in ("flops", "bytes accessed", "transcendentals",
                           "optimal_seconds")
                     or k.startswith("bytes accessed"))}
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)

    # loop-corrected costs: XLA counts while bodies once; scan-over-layers
    # models under-report by ~n_layers without this (see launch/hlo_cost.py)
    from repro.launch.hlo_cost import loop_corrected_cost

    try:
        corrected = loop_corrected_cost(hlo)
        corrected_out = {
            "flops": corrected["flops"],
            "bytes": corrected["bytes"],
            "collectives": corrected["collectives"],
            "collective_total_bytes": corrected["collective_total_bytes"],
        }
    except Exception as e:  # parsing must never fail the cell
        corrected_out = {"error": repr(e)}

    result.update({
        "status": "ok",
        "corrected_cost": corrected_out,
        "lower_s": round(t_lower - t0, 1),
        "compile_s": round(t_compile - t_lower, 1),
        "memory_analysis": mem_dict,
        "cost_analysis": cost_dict,
        "collectives": coll,
        "guard_report": guard,
        "hlo_bytes": len(hlo),
        "n_devices": mesh.devices.size,
    })
    return result


def cell_path(arch: str, shape: str, multi_pod: bool, tag: str = "") -> Path:
    mesh = "2x16x16" if multi_pod else "16x16"
    suffix = f"__{tag}" if tag else ""
    return OUT_DIR / f"{arch}__{shape}__{mesh}{suffix}.json"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--no-qat", action="store_true")
    ap.add_argument("--no-comp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--q-block", type=int, default=512)
    ap.add_argument("--kv-block", type=int, default=512)
    ap.add_argument("--flash", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--kv-seq", action="store_true")
    ap.add_argument("--moe-local", action="store_true")
    ap.add_argument("--remat-save-qat", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--rules", default="",
                    help="logical=mesh overrides, e.g. embed=model,heads=None")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)
    OUT_DIR.mkdir(parents=True, exist_ok=True)

    if args.all:
        from repro.configs import ALL_ARCHS, SHAPES
        jobs = []
        for arch in ALL_ARCHS:
            for shape in SHAPES:
                for mp in (False, True):
                    path = cell_path(arch, shape, mp, args.tag)
                    if path.exists() and not args.force:
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape]
                    if mp:
                        cmd.append("--multi-pod")
                    if args.tag:
                        cmd += ["--tag", args.tag]
                    jobs.append((path, cmd))
        print(f"{len(jobs)} cells to run")
        running: list = []
        while jobs or running:
            while jobs and len(running) < args.jobs:
                path, cmd = jobs.pop(0)
                print("start", path.name, flush=True)
                running.append((path, subprocess.Popen(
                    cmd, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
                    cwd=str(OUT_DIR.parents[2]),
                    env={**os.environ, "PYTHONPATH": "src"})))
            still = []
            for path, proc in running:
                if proc.poll() is None:
                    still.append((path, proc))
                else:
                    ok = proc.returncode == 0 and path.exists()
                    print(("done " if ok else "FAIL ") + path.name, flush=True)
                    if not ok:
                        err = proc.stderr.read().decode()[-2000:]
                        path.with_suffix(".err").write_text(err)
            running = still
            time.sleep(3)
        return

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    rules_override = {}
    if args.rules:
        for kv in args.rules.split(","):
            k, v = kv.split("=")
            if v in ("None", "none", ""):
                rules_override[k] = None
            elif "+" in v:
                rules_override[k] = tuple(v.split("+"))
            else:
                rules_override[k] = v
    result = run_cell(
        args.arch, args.shape, args.multi_pod,
        qat=not args.no_qat, with_comp=not args.no_comp,
        remat=not args.no_remat, q_block=args.q_block,
        kv_block=args.kv_block, flash=args.flash,
        grad_accum=args.grad_accum, kv_seq_shard=args.kv_seq,
        moe_local_dispatch=args.moe_local,
        remat_save_qat=args.remat_save_qat,
        rules_override=rules_override or None, tag=args.tag)
    path = cell_path(args.arch, args.shape, args.multi_pod, args.tag)
    path.write_text(json.dumps(result, indent=2))
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("guard_report",)}, indent=2))


if __name__ == "__main__":
    main()
