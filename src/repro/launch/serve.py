"""Serving launcher: thin CLI over the unified compression pipeline.

Production shape: `repro.pipeline.Pipeline` with an LM target — restore
params from a checkpoint (mesh-elastic), optionally restrict every eligible
matmul to a k-value codebook + export the packed 4-bit artifacts, and drain
a request trace through `repro.serving.ServingEngine`. On this CPU host it
drives reduced configs (examples/serve_lm.py shows the same flow scripted);
on a pod the identical code runs the engine's optional sharded decode over
`repro.distributed.sharding.request_mesh()`.

    python -m repro.launch.serve --arch gemma3-4b --reduced --batch 4

Equivalent pipeline CLI: ``repro serve --target lm --arch gemma3-4b
--reduced`` (same stages, same plan; see docs/pipeline.md).

``--mode wave`` swaps the slot-level engine for the legacy wave-lockstep
scheduler and ``--mode oneshot`` for the single-shot fallback (batch-1
waves, one request at a time, same buckets and compile cache) — all three
modes are output-identical, and `benchmarks/bench_serving.py` gates the
engine's throughput edge over both baselines.

``--compress-k N`` restricts every eligible matmul to an N-value codebook,
serves the compressed fake-quant forward, exports the packed 4-bit artifacts
(`repro.core.lm_compress.export_lm_matmuls`), and verifies the LUT GEMM
against the fake-quant matmul before serving (see docs/serving.md).

``--plans SPEC [SPEC ...]`` (or ``--plans-dir DIR``) serves a **fleet**
instead of one pinned variant: every SPEC becomes a resident
`repro.serving.fleet.PlanHandle` (``base``, ``k4``, ``k8m2``, or a saved
CompressionPlan base path) and a `FleetRouter` picks the variant per request
from queue pressure and per-request budgets — degrading to aggressive
compression under load, recovering to high fidelity when idle:

    python -m repro.launch.serve --arch olmo-1b --reduced --plans k4 base
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp


def compress_report(model, params, k: int, *, block_k: int = 128,
                    check_units: int = 4, seed: int = 2):
    """Export eligible LM matmuls at codebook size ``k`` and verify parity.

    Standalone form of the pipeline's export stage
    (`repro.pipeline.targets.LMTarget.stage_export`) for callers holding a
    bare (model, params): restricts every eligible matmul to a symmetric
    k-value codebook, exports the packed 4-bit artifacts, and checks the LUT
    GEMM against the QAT fake-quant matmul on random activations for
    ``check_units`` units. Returns (artifacts, summary dict).
    """
    from repro.core import lm_compress
    from repro.core.export import export_summary

    values = lm_compress.symmetric_codebook_values(k)
    comp = lm_compress.init_lm_comp(model)
    comp = lm_compress.restrict_all_codebooks(model, comp, values)
    arts, skips = lm_compress.export_lm_matmuls(model, params, comp,
                                                block_k=block_k)
    summary = export_summary(arts)
    summary["skipped_units"] = skips
    checked = lm_compress.lut_parity_report(model, params, comp, arts,
                                            check_units=check_units,
                                            seed=seed)
    summary["parity_checked"] = checked
    summary["parity_max_rel_err"] = max(checked.values()) if checked else 0.0
    return arts, summary


def generate(model, params, prompts: jax.Array, *, new_tokens: int,
             temperature: float = 0.0, seed: int = 0, q_block: int = 8,
             kv_block: int = 8):
    """Reference single-dispatch generation: prefill once, loop decode.

    Kept as the pre-engine serving path; the engine reproduces it exactly
    when a prompt fills its bucket (tested in tests/test_serving_engine.py).
    """
    b, s = prompts.shape
    max_len = s + new_tokens
    logits, cache = model.prefill(params, prompts, max_len=max_len,
                                  cache_dtype=jnp.float32, q_block=q_block,
                                  kv_block=kv_block)

    def sample(lg, key):
        lg = lg[:, -1, :model.cfg.vocab] if lg.ndim == 3 else lg[:, :model.cfg.vocab]
        if temperature <= 0:
            return jnp.argmax(lg, axis=-1)
        return jax.random.categorical(key, lg / temperature, axis=-1)

    key = jax.random.PRNGKey(seed)
    tok = sample(logits, key)[:, None]
    decode = jax.jit(model.decode_step)

    outs = [tok]
    for i in range(new_tokens - 1):
        logits, cache = decode(params, cache, tok)
        key = jax.random.fold_in(key, i)
        tok = sample(logits[:, 0], key)[:, None]
        outs.append(tok)
    return jnp.concatenate(outs, axis=1)


def trace_shapes(n_requests: int, prompt_len: int, new_tokens: int,
                 mixed: bool) -> list:
    """(prompt_len, new_tokens) per request; ``mixed`` varies lengths
    deterministically to exercise several buckets. Delegates to the
    pipeline's trace generator so the CLI and the serve stage agree."""
    from repro.pipeline.targets import lm_trace_shapes

    return lm_trace_shapes(n_requests, prompt_len, new_tokens, mixed)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config of the same family")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from a CheckpointManager directory")
    ap.add_argument("--mode", choices=("engine", "wave", "oneshot"),
                    default="engine",
                    help="continuous-batching engine or single-shot fallback")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests in the trace")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--mixed", action="store_true",
                    help="vary request lengths across buckets")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--max-batch", type=int, default=8,
                    help="engine wave width")
    ap.add_argument("--compress-k", type=int, default=0,
                    help="restrict eligible matmuls to a k-value codebook, "
                         "export packed 4-bit artifacts, verify LUT parity, "
                         "and serve the compressed forward")
    ap.add_argument("--plans", nargs="+", default=None, metavar="SPEC",
                    help="fleet serving: resident variants ('base', "
                         "'k<N>[m<M>]', or saved CompressionPlan base "
                         "paths) routed across by load and budget")
    ap.add_argument("--plans-dir", default=None, metavar="DIR",
                    help="fleet serving: load every saved CompressionPlan "
                         "under DIR as a resident variant")
    ap.add_argument("--plan-out", default=None, metavar="BASE",
                    help="save the CompressionPlan to BASE.json + BASE.npz")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    from repro.pipeline import (
        Pipeline,
        PipelineConfig,
        ServeStageConfig,
        TargetConfig,
        TrainStageConfig,
    )

    cfg = PipelineConfig(
        target=TargetConfig(kind="lm", arch=args.arch, reduced=args.reduced,
                            ckpt_dir=args.ckpt_dir),
        train=TrainStageConfig(qat_steps=0, final_finetune_steps=0),
        serve=ServeStageConfig(mode=args.mode, compress_k=args.compress_k,
                               plans=tuple(args.plans or ()),
                               plans_dir=args.plans_dir,
                               requests=args.batch,
                               prompt_len=args.prompt_len,
                               new_tokens=args.new_tokens, mixed=args.mixed,
                               max_batch=args.max_batch,
                               temperature=args.temperature),
    )
    enable_compile_cache()
    pipe = Pipeline(cfg)
    plan = pipe.run_until("serve", verbose=True)
    m = plan.metrics

    print(f"serving {pipe.target.name}: {m['n_params']/1e6:.1f}M params")
    if args.compress_k:
        print(f"compressed export: {m['export_layers']} matmuls, "
              f"{m['export_weight_bytes_packed'] / 1e6:.2f} MB packed "
              f"({m['export_compression_vs_int8']:.2f}x vs int8), "
              f"LUT parity max rel err "
              f"{m['export_parity_max_rel_err']:.2e}")

    if m.get("serve_mode") == "fleet":
        rep = pipe.target.last_fleet_report
        print(f"fleet [{m['serve_plans']}]: {m['serve_requests']} requests "
              f"({m['serve_tokens_per_s']:.1f} tok/s), "
              f"{m['serve_level_degrades']} degrades / "
              f"{m['serve_level_recovers']} recovers, "
              f"{m['serve_recompiles_after_warmup']} recompiles after warmup")
        for pid, p in rep["plans"].items():
            print(f"  plan {pid}: {p['requests']} requests, "
                  f"{p['new_tokens']} tokens, {p['energy_eu']:.3g} eu")
        for tid, t in sorted(rep["tenants"].items()):
            print(f"  tenant {tid}: {t['requests']} requests, "
                  f"{t['new_tokens']} tokens, {t['energy_eu']:.3g} eu, "
                  f"SLO {t['slo_hits']}/{t['slo_total']}")
        results = pipe.target.last_serve_results
        for rid in sorted(results)[:2]:
            print(f"  req{rid}: {results[rid].tokens[:10]}...")
        if args.plan_out:
            json_path, npz_path = plan.save(args.plan_out)
            print(f"plan saved: {json_path} + {npz_path}")
        return

    print(f"{args.mode}: {m['serve_requests']} requests, "
          f"{m['serve_new_tokens']} tokens in {m['serve_wall_s']:.2f}s "
          f"({m['serve_tokens_per_s']:.1f} tok/s), "
          f"latency p50/p99 {m['serve_latency_p50_s']*1e3:.0f}/"
          f"{m['serve_latency_p99_s']*1e3:.0f} ms, "
          f"ttft p50 {m['serve_ttft_p50_s']*1e3:.0f} ms, "
          f"energy {m['serve_energy_eu_total']:.3g} eu "
          f"({m['serve_energy_eu_per_token']:.3g} eu/token), "
          f"{m['serve_cache_buckets_compiled']} buckets / "
          f"{m['serve_cache_compile_count']} compiles")
    results = pipe.target.last_serve_results
    for rid in sorted(results)[:2]:
        print(f"  req{rid}: {results[rid].tokens[:10]}...")
    if args.plan_out:
        json_path, npz_path = plan.save(args.plan_out)
        print(f"plan saved: {json_path} + {npz_path}")


if __name__ == "__main__":
    main()
