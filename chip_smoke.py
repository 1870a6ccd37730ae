"""Chip smoke test: both jobs of the system, once each, on a TPU.

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --four-chips  # four chips: the multi-chip paths only

Phase A, the offline compression job. The `Pipeline` on resnet20 (the
paper's network, at its own widths) takes a few QAT steps, then runs up to
``energy_model`` with ``profile.verify_cosim=True``, the route of
``repro profile --verify-cosim``. The compiled transition-energy kernel must
match the bit-accurate cosim exactly on every sampled tile.

Phase B, serving. olmo-1b at its published widths (weights drawn from a
seed, ``qat_steps=0``) goes schedule -> export -> serve with a k=4 plan and
a mixed-length trace, through the fake-quant engine. The same plan and
prompts are then served through the packed 4-bit LUT GEMM
(``EngineConfig(lut_serve=True)``). Neither engine may recompile after
warmup, and the packed path must agree with the fake-quant path within
``LOGIT_REL_TOL`` and ``GREEDY_AGREE_TOL``.

``--four-chips`` runs only the multi-chip paths a user reaches without
asking, each against its one-device result: `profile_layer` on the 4-device
tile mesh with the compiled kernel, and the schedule's candidate sweep on
`sweep_mesh()`.

The script exits non-zero before any phase when JAX finds no TPU. It never
interprets a kernel, never falls back to the jnp oracle and never catches a
phase's failure. The last line of stdout is one JSON object naming the
device; everything else is printed before it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
SEED = 0

# packed LUT path vs fake-quant path, on the prefill logits of the trace's
# prompts. Both quantize weights to the same codebook; the fake-quant path
# rounds its dequantized weights to bf16 and the kernel keeps them in f32, so
# the logits differ at bf16 resolution. Random weights give nearly flat
# logits, where such a difference flips a near-tie argmax now and then; a
# wrong codebook or packing instead agrees on about 1 position in the vocab.
LOGIT_REL_TOL = 0.05       # max |logit_lut - logit_fq| / max |logit_fq|
GREEDY_AGREE_TOL = 0.8     # share of prompt positions whose argmax agrees


class CompileClock:
    """Seconds and count of XLA backend compiles, from JAX's own events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def since(self, mark):
        return self.seconds - mark[0], self.count - mark[1]

    def mark(self):
        return self.seconds, self.count


def check(cond, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip smoke check failed: {what}")


# --------------------------------------------------------------- phase A


def phase_a(clock: CompileClock, *, arch: str = "resnet20",
            qat_steps: int = 5, max_tiles: int = 16) -> None:
    from repro.kernels import resolve_interpret
    from repro.pipeline import (Pipeline, PipelineConfig, ProfileStageConfig,
                                TargetConfig, TrainStageConfig)

    check(not resolve_interpret(), "the transition kernel would interpret")
    cfg = PipelineConfig(
        target=TargetConfig(kind="cnn", arch=arch, seed=SEED),
        train=TrainStageConfig(qat_steps=qat_steps, final_finetune_steps=0,
                               eval_batches=1),
        profile=ProfileStageConfig(batches=1, max_tiles=max_tiles,
                                   verify_cosim=True),
    )
    mark, t0 = clock.mark(), time.perf_counter()
    plan = Pipeline(cfg).run_until("energy_model")
    wall = time.perf_counter() - t0
    comp_s, comp_n = clock.since(mark)
    m = plan.metrics
    print(f"phase A [{arch}]: {qat_steps} QAT steps, cosim "
          f"match={m['cosim_match']} on {m['cosim_tiles']} tiles "
          f"(max_abs_diff={m['cosim_max_abs_diff']}, "
          f"toggles={m['cosim_toggles']})")
    print(f"phase A stage wall s: profile={m['wall_s_profile']} "
          f"energy_model={m['wall_s_energy_model']}; total {wall:.1f} s, "
          f"of which compile {comp_s:.1f} s over {comp_n} compiles")
    check(m["cosim_match"], "compiled kernel disagrees with the cosim")
    check(m["cosim_tiles"] > 0, "no tile was gated against the cosim")


# --------------------------------------------------------------- phase B


def _prefill_logits(model, params, comp, qcfg, tokens, ecfg):
    """Logits (B, C, vocab) of one prefill chunk from position 0."""
    import jax
    import jax.numpy as jnp

    b, c = tokens.shape
    cache = model.init_cache(b, c, jnp.dtype(ecfg.cache_dtype))

    def run(p, comp_, cache_, toks):
        logits, _ = model.prefill_chunk(
            p, cache_, toks, start=jnp.zeros((b,), jnp.int32), qcfg=qcfg,
            comp=comp_, q_block=ecfg.q_block, kv_block=ecfg.kv_block)
        return logits[..., :model.cfg.vocab]

    return jax.jit(run)(params, comp, cache, tokens)


def packed_vs_fake_quant(model, params, fq_comp, lut_engine, requests,
                         ecfg) -> tuple:
    """(max relative logit error, share of agreeing argmaxes) over the real
    positions of the trace's prompts, prefilled by both paths."""
    import jax.numpy as jnp
    import numpy as np

    from repro.nn.layers import QuantConfig

    lens = [len(r.tokens) for r in requests]
    toks = np.zeros((len(requests), max(lens)), np.int32)
    for i, r in enumerate(requests):
        toks[i, :lens[i]] = np.asarray(r.tokens)
    toks = jnp.asarray(toks)
    fq = _prefill_logits(model, params, fq_comp, QuantConfig.on(), toks, ecfg)
    lut = _prefill_logits(model, params, lut_engine.comp, lut_engine.qcfg,
                          toks, ecfg)
    fq, lut = np.asarray(fq, np.float64), np.asarray(lut, np.float64)
    real = np.arange(toks.shape[1])[None, :] < np.asarray(lens)[:, None]
    err = np.abs(lut - fq).max(axis=-1)[real].max() / np.abs(fq[real]).max()
    agree = (lut.argmax(-1) == fq.argmax(-1))[real].mean()
    return float(err), float(agree)


def phase_b(clock: CompileClock, *, arch: str = "olmo-1b",
            reduced: bool = False, compress_k: int = 4, requests: int = 6,
            prompt_len: int = 32, new_tokens: int = 16) -> None:
    import jax

    from repro.pipeline import (Pipeline, PipelineConfig, ServeStageConfig,
                                TargetConfig, TrainStageConfig)
    from repro.serving import ServingEngine

    cfg = PipelineConfig(
        target=TargetConfig(kind="lm", arch=arch, reduced=reduced, seed=SEED),
        train=TrainStageConfig(qat_steps=0, final_finetune_steps=0),
        serve=ServeStageConfig(compress_k=compress_k, requests=requests,
                               prompt_len=prompt_len, new_tokens=new_tokens,
                               mixed=True, max_batch=8),
    )
    pipe = Pipeline(cfg)
    mark, t0 = clock.mark(), time.perf_counter()
    plan = pipe.run_until("serve")
    comp_s, comp_n = clock.since(mark)
    m = plan.metrics
    acfg = pipe.target.acfg
    print(f"phase B [{acfg.name}] d_model={acfg.d_model} "
          f"layers={acfg.n_layers} vocab={acfg.vocab} "
          f"compute={acfg.compute_dtype}: {m['n_params'] / 1e6:.1f}M params, "
          f"{m['export_layers']} packed matmuls, export LUT parity max rel "
          f"err {m['export_parity_max_rel_err']:.2e}")
    print("phase B stage wall s: " + " ".join(
        f"{s}={m[f'wall_s_{s}']}" for s in
        ("profile", "energy_model", "schedule", "export", "serve")))
    print(f"phase B fake-quant engine: {m['serve_requests']} requests, "
          f"{m['serve_new_tokens']} tokens, "
          f"{m['serve_tokens_per_s']:.1f} tok/s, "
          f"{m['serve_cache_compile_count']} compiles, "
          f"{m['serve_recompiles_after_warmup']} recompiles after warmup; "
          f"pipeline compile {comp_s:.1f} s over {comp_n} compiles")
    check(m["serve_recompiles_after_warmup"] == 0,
          "fake-quant engine recompiled after warmup")

    target = pipe.target
    shapes, ecfg, reqs = target.serve_trace(cfg)
    lut_cfg = dataclasses.replace(ecfg, lut_serve=True)
    mark = clock.mark()
    engine = ServingEngine(target.model, plan.params, config=lut_cfg,
                           plan=target.serve_handle(plan, compress_k))
    check(not engine.qcfg.use_ref_kernel,
          "lut_use_ref resolved to the jnp oracle")
    engine.warmup(shapes)
    warm = engine.cache.compile_count
    lut_results = engine.serve(reqs)
    rep = engine.report()
    recompiles = engine.cache.compile_count - warm
    comp_s, comp_n = clock.since(mark)
    print(f"phase B packed LUT engine: {rep['requests']} requests, "
          f"{rep['new_tokens']} tokens, {rep['tokens_per_s']:.1f} tok/s, "
          f"{engine.serve_units} packed units, {warm} compiles, "
          f"{recompiles} recompiles after warmup; engine compile "
          f"{comp_s:.1f} s over {comp_n} compiles")
    check(recompiles == 0, "packed LUT engine recompiled after warmup")
    for req, res in zip(reqs, lut_results):
        check(len(res.tokens) == req.max_new_tokens,
              f"request {res.rid} got {len(res.tokens)} tokens")

    fq_results = target.last_serve_results
    same = sum(a == b for res in lut_results
               for a, b in zip(res.tokens, fq_results[res.rid].tokens))
    total = sum(len(res.tokens) for res in lut_results)
    err, agree = packed_vs_fake_quant(target.model, plan.params, plan.comp,
                                      engine, reqs, ecfg)
    print(f"phase B packed vs fake-quant: prefill max logit err "
          f"{err:.3e} (tol {LOGIT_REL_TOL}), greedy argmax agree "
          f"{agree:.4f} (tol {GREEDY_AGREE_TOL}); served tokens equal "
          f"{same}/{total}")
    check(err <= LOGIT_REL_TOL, "packed logits too far from fake-quant")
    check(agree >= GREEDY_AGREE_TOL, "packed argmax disagrees too often")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"phase B peak device memory: "
          f"{stats.get('peak_bytes_in_use', 'not reported')} bytes")


# ------------------------------------------------------------- four chips


def four_chips(*, n_devices: int = 4) -> None:
    """Sharded profiling and the sharded candidate sweep vs one device."""
    import jax
    import numpy as np

    from repro.core.profiler import profile_layer
    from repro.core.runner import CnnRunner
    from repro.core.schedule import ScheduleConfig, \
        energy_prioritized_compression
    from repro.core.weight_selection import SelectionConfig
    from repro.data.synthetic import SyntheticImages
    from repro.distributed.sharding import sweep_mesh, tile_mesh
    from repro.nn import cnn

    check(jax.device_count() == n_devices,
          f"need {n_devices} devices, have {jax.device_count()}")

    # a resnet20 stage-3 conv: 64 output channels, 3x3x64 inputs
    key = jax.random.PRNGKey(SEED)
    w = jax.random.randint(key, (64, 576), -128, 128)
    x = jax.random.randint(jax.random.fold_in(key, 1), (576, 4096), -128, 128)
    kw = dict(max_tiles=48, key=jax.random.fold_in(key, 2), use_kernel=True)
    t0 = time.perf_counter()
    sharded = profile_layer(w, x, **kw)       # >1 device: the tile mesh
    one = profile_layer(w, x, mesh=tile_mesh(jax.devices()[:1]), **kw)
    for name in ("group_hist", "act_hist", "count"):
        check(np.array_equal(np.asarray(getattr(sharded, name)),
                             np.asarray(getattr(one, name))),
              f"sharded profile {name} differs from one device")
    es_s, es_1 = np.asarray(sharded.energy_sum), np.asarray(one.energy_sum)
    # energy sums are f32 partial sums added in another order
    check(np.allclose(es_s, es_1, rtol=1e-5, atol=1e-3),
          "sharded profile energy_sum differs from one device")
    print(f"four chips: profile_layer on the {n_devices}-device tile mesh "
          f"== one device (48 tiles; energy_sum max rel diff "
          f"{float(np.max(np.abs(es_s - es_1) / np.maximum(es_1, 1))):.2e}) "
          f"in {time.perf_counter() - t0:.1f} s")

    def build(mesh):
        return CnnRunner(cnn.lenet5(), SyntheticImages(seed=5), batch_size=64,
                         lr=2e-3, seed=SEED, sweep_mesh=mesh)

    sched = ScheduleConfig(prune_ratios=(0.75, 0.5, 0.25), k_targets=(16, 24),
                           delta_acc=0.08, finetune_steps=4,
                           trial_finetune_steps=4, eval_batches=1,
                           max_layers=1)
    sel = SelectionConfig(k_init=20, k_target=16, delta_acc=0.08,
                          score_batches=1, accept_batches=1,
                          max_score_candidates=3)
    t0 = time.perf_counter()
    base = build(None)
    params, state, opt_state, comp = base.init()
    params, state, opt_state, _ = base.train(params, state, opt_state, comp,
                                             10)
    stats = base.profile(params, state, comp, max_tiles=4)
    decisions = []
    for runner in (base, build(sweep_mesh())):
        *_, res = energy_prioritized_compression(
            runner, params, state, opt_state, comp, stats, sched, sel)
        decisions.append([(d.layer, d.prune_ratio, d.k, d.accepted)
                          for d in res.decisions])
    print(f"four chips: candidate sweep on sweep_mesh() decisions "
          f"{decisions[1]} == unsharded {decisions[0]}: "
          f"{decisions[0] == decisions[1]} "
          f"in {time.perf_counter() - t0:.1f} s")
    check(decisions[0] == decisions[1],
          "sharded sweep decisions differ from the unsharded sweep")


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths, on four chips")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    # block shapes come from the roofline model alone, never from a tuning
    # file some earlier run left in the checkout
    os.environ.pop("REPRO_LUT_AUTOTUNE_CACHE", None)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache

    print(f"device: {dev.device_kind} x {jax.device_count()}, compile "
          f"cache {enable_compile_cache()}")
    clock = CompileClock()
    if args.four_chips:
        four_chips()
    else:
        phase_a(clock)
        phase_b(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
