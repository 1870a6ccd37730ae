"""`Target` protocol: CNN and LM models behind one pipeline stage interface.

A target owns the model runtime (a `CnnRunner`, or an `LMModel` + serving
engine) and implements one method per pipeline stage. Every method takes the
shared `CompressionPlan` and the `PipelineConfig` and mutates only the plan —
the plan is the *only* object that travels between stages, which is what
makes `run_until` + save + `Pipeline.from_plan` resume exact.

  stage          CnnTarget                        LMTarget
  ------------   ------------------------------   ---------------------------
  profile        QAT base train + systolic trace  param init/restore
                 stats per layer                  (+ optional LM QAT steps)
  energy_model   blended per-layer LUTs + shares  uniform-trace LUT per-unit
                                                  energies + shares
  schedule       energy-prioritized layer sweep   uniform k-value codebook
                 (prune x k, accuracy floor)      restriction per unit
  export         packed 4-bit ServeArtifacts      packed 4-bit ServeArtifacts
                 (repro.core.export)              (repro.core.lm_compress)
  serve          full-model LUT-GEMM forward,     continuous-batching engine
                 parity + accuracy vs fake-quant  over a deterministic trace

The CNN stages reproduce the pre-refactor `CompressionPipeline.run()` wiring
operation for operation (same seeds, same batch streams, same eval order),
so schedule decisions and exported artifacts are bit-identical to the old
path — gated by tests/test_pipeline.py.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from repro.pipeline.config import PipelineConfig
from repro.pipeline.plan import CompressionPlan, decision_dict


def resolve_target(cfg: PipelineConfig):
    if cfg.target.kind == "cnn":
        return CnnTarget(cfg)
    if cfg.target.kind == "lm":
        return LMTarget(cfg)
    if cfg.target.kind == "moe":
        return MoETarget(cfg)
    if cfg.target.kind == "scan":
        return ScanTarget(cfg)
    raise ValueError(f"unknown target kind {cfg.target.kind!r}")


def lm_trace_shapes(n_requests: int, prompt_len: int, new_tokens: int,
                    mixed: bool, *, stride: int = 7) -> List[Tuple[int, int]]:
    """Deterministic (prompt_len, new_tokens) trace; ``mixed`` varies lengths
    so several buckets are exercised."""
    if not mixed:
        return [(prompt_len, new_tokens)] * n_requests
    lens = [max(2, prompt_len - stride * (i % 3)) for i in range(n_requests)]
    news = [max(2, new_tokens - 3 * (i % 2)) for i in range(n_requests)]
    return list(zip(lens, news))


# ===================================================================== CNN


class CnnTarget:
    """CNN compression through a `repro.core.runner.CnnRunner`."""

    kind = "cnn"

    def __init__(self, cfg: PipelineConfig, runner=None):
        if runner is None:
            from repro.core.runner import CnnRunner
            from repro.data.synthetic import SyntheticImages
            from repro.nn import cnn

            factories = {"lenet5": cnn.lenet5, "resnet8": cnn.resnet8,
                         "resnet20": cnn.resnet20, "resnet50": cnn.resnet50}
            t = cfg.target
            runner = CnnRunner(factories[t.arch](),
                               SyntheticImages(seed=t.data_seed),
                               batch_size=t.batch_size, lr=t.lr, seed=t.seed)
        self.runner = runner
        # an injected runner's model name wins over the config arch so the
        # plan's target identity stays truthful for custom models
        self.name = getattr(runner.model, "name", cfg.target.arch)
        self.last_schedule_result = None  # transient, for the legacy shim

    # ------------------------------------------------------------- stages

    def stage_profile(self, plan: CompressionPlan, cfg: PipelineConfig,
                      verbose: bool = False) -> None:
        runner = self.runner
        params, state, opt_state, comp = runner.init()
        loss = float("nan")
        if cfg.train.qat_steps:
            params, state, opt_state, loss = runner.train(
                params, state, opt_state, comp, cfg.train.qat_steps)
        acc_base = runner.accuracy(params, state, comp,
                                   n_batches=cfg.train.eval_batches)
        if verbose:
            print(f"[pipeline] QAT base: loss={loss:.4f} acc={acc_base:.3f}")
        stats = runner.profile(params, state, comp,
                               n_batches=cfg.profile.batches,
                               max_tiles=cfg.profile.max_tiles)
        plan.params, plan.state = params, state
        plan.opt_state, plan.comp = opt_state, comp
        plan.stats = stats
        plan.metrics["acc_base"] = float(acc_base)
        plan.metrics["qat_loss"] = float(loss)
        if cfg.profile.verify_cosim:
            from repro.cosim import verify_runner_profile

            res = verify_runner_profile(
                runner, params, state, comp,
                n_batches=cfg.profile.batches,
                max_tiles=cfg.profile.max_tiles)
            plan.metrics["cosim_match"] = bool(res["match"])
            plan.metrics["cosim_tiles"] = int(res["n_tiles"])
            plan.metrics["cosim_max_abs_diff"] = float(res["max_abs_diff"])
            plan.metrics["cosim_toggles"] = int(res["toggles"])
            if verbose:
                print(f"[pipeline] cosim verify: match={res['match']} "
                      f"tiles={res['n_tiles']} "
                      f"max_abs_diff={res['max_abs_diff']}")
            if not res["match"]:
                bad = {n: r["max_abs_diff"] for n, r in res["layers"].items()
                       if not r["match"]}
                raise RuntimeError(
                    "transition-energy kernel disagrees with the "
                    f"bit-accurate cosim on layers {bad} — see docs/cosim.md")

    def stage_energy_model(self, plan: CompressionPlan, cfg: PipelineConfig,
                           verbose: bool = False) -> None:
        runner = self.runner
        models = runner.energy_models(plan.params, plan.comp, plan.stats)
        e_total = sum(m.energy for m in models.values())
        plan.shares = {n: m.energy / max(e_total, 1e-12)
                       for n, m in models.items()}
        plan.luts = {n: m.lut for n, m in models.items()}
        plan.metrics["energy_profile_total"] = float(e_total)
        if verbose:
            for n, s in sorted(plan.shares.items(), key=lambda kv: -kv[1]):
                print(f"[pipeline] energy share {n}: {s:.3f}")

    def stage_schedule(self, plan: CompressionPlan, cfg: PipelineConfig,
                       verbose: bool = False) -> None:
        from repro.core.schedule import energy_prioritized_compression

        runner = self.runner
        params, state, opt_state, comp, sched = energy_prioritized_compression(
            runner, plan.params, plan.state, plan.opt_state, plan.comp,
            plan.stats, cfg.schedule, cfg.selection, verbose=verbose)
        if cfg.train.final_finetune_steps:
            params, state, opt_state, _ = runner.train(
                params, state, opt_state, comp,
                cfg.train.final_finetune_steps)
        acc_final = runner.accuracy(params, state, comp,
                                    n_batches=cfg.train.eval_batches)
        models = runner.refresh_counts(
            params, comp, runner.energy_models(params, comp, plan.stats))
        e_after = sum(m.energy for m in models.values())

        plan.params, plan.state = params, state
        plan.opt_state, plan.comp = opt_state, comp
        plan.decisions = [decision_dict(d) for d in sched.decisions]
        ks = [int(d.k) for d in sched.decisions if d.k is not None]
        plan.metrics.update({
            "acc0": float(sched.acc0),
            "acc_final": float(acc_final),
            "accuracy_drop": float(plan.metrics.get("acc_base", sched.acc0)
                                   - acc_final),
            "energy_before": float(sched.energy_before),
            "energy_after": float(e_after),
            "energy_saving": 1.0 - float(e_after)
            / max(float(sched.energy_before), 1e-12),
            "max_codebook": max(ks) if ks else 256,
        })
        self.last_schedule_result = sched

    def stage_export(self, plan: CompressionPlan, cfg: PipelineConfig,
                     verbose: bool = False) -> None:
        from repro.core.export import export_model, export_summary

        arts = export_model(self.runner.model, plan.params, plan.comp,
                            block_k=cfg.export.block_k)
        plan.artifacts = arts
        plan.metrics.update(
            {f"export_{k}": v for k, v in export_summary(arts).items()})
        if verbose:
            print(f"[pipeline] exported {len(arts)} compressed layers")

    def stage_serve(self, plan: CompressionPlan, cfg: PipelineConfig,
                    verbose: bool = False) -> None:
        """Full-model forward through the packed LUT GEMM: logit parity vs
        the QAT fake-quant reference + served accuracy."""
        import jax.numpy as jnp

        from repro.nn.layers import QuantConfig

        runner = self.runner
        arts = plan.artifacts or {}
        plan.metrics["serve_layers"] = len(arts)
        if not arts:
            if verbose:
                print("[pipeline] no layer is servable; nothing to serve")
            return
        qserve = QuantConfig.serve(use_ref_kernel=cfg.serve.use_ref_kernel)
        x, _ = runner.dataset.batch(0, runner.batch_size, "val")
        l_fake, _, _ = runner.model.apply(
            plan.params, plan.state, x, train=False, qcfg=QuantConfig.on(),
            comp=plan.comp)
        l_serve, _, _ = runner.model.apply(
            plan.params, plan.state, x, train=False, qcfg=qserve,
            comp=plan.comp, serve=arts)
        rel = float(jnp.linalg.norm(l_serve - l_fake)
                    / jnp.maximum(jnp.linalg.norm(l_fake), 1e-9))
        correct = 0
        n_batches = max(cfg.train.eval_batches, 1)
        for i in range(n_batches):
            xb, yb = runner.dataset.batch(i, runner.batch_size, "val")
            logits, _, _ = runner.model.apply(
                plan.params, plan.state, xb, train=False, qcfg=qserve,
                comp=plan.comp, serve=arts)
            correct += int(jnp.sum(jnp.argmax(logits, -1) == yb))
        plan.metrics["serve_logit_rel_err"] = rel
        plan.metrics["serve_accuracy"] = correct / (n_batches
                                                    * runner.batch_size)
        if verbose:
            print(f"[pipeline] serve: {len(arts)} layers on the LUT GEMM, "
                  f"rel_err={rel:.2e}, "
                  f"acc={plan.metrics['serve_accuracy']:.3f}")


# ====================================================================== LM


class LMTarget:
    """LM compression + serving through `repro.serving.ServingEngine`."""

    kind = "lm"

    def __init__(self, cfg: PipelineConfig):
        from repro.configs import get_config
        from repro.models.lm import build_lm

        acfg = get_config(cfg.target.arch)
        if cfg.target.reduced:
            acfg = acfg.scaled_down(compute_dtype="float32")
        self.acfg = acfg
        self.model = build_lm(acfg)
        self.name = acfg.name
        self.last_schedule_result = None

    # ----------------------------------------------------------- helpers

    def _unit_energies(self, params, comp) -> Dict[str, float]:
        """Per-unit one-token MAC energy on the 64x64 array (uniform-trace
        LUT — no profiled activations exist at LM scale); the summed total
        is `repro.serving.metrics.per_token_energy`."""
        from repro.core import qat
        from repro.core.energy_lut import uniform_trace_lut
        from repro.core.layer_energy import (
            dense_matmul_dims,
            layer_energy_from_counts,
            weight_value_counts,
        )
        from repro.core.lm_compress import iter_eligible_units

        lut = uniform_trace_lut()
        out: Dict[str, float] = {}
        for name, w, c, layout in iter_eligible_units(self.model, params,
                                                      comp):
            w_int = qat.quantize_weight_int(w, c)
            mat = (w_int.reshape(w_int.shape[0], -1) if layout == "in_first"
                   else w_int.reshape(-1, w_int.shape[-1]))
            dims = dense_matmul_dims(fan_in=mat.shape[0], fan_out=mat.shape[1],
                                     n_tokens=1)
            counts = weight_value_counts(mat.T, dims)
            out[name] = float(layer_energy_from_counts(counts, lut, dims))
        return out

    # ------------------------------------------------------------- stages

    def stage_profile(self, plan: CompressionPlan, cfg: PipelineConfig,
                      verbose: bool = False) -> None:
        import jax

        from repro.core.lm_compress import init_lm_comp, lm_comp_layers
        from repro.nn.spec import init_params, spec_count

        if cfg.target.ckpt_dir:
            from repro.checkpoint.manager import CheckpointManager

            step, state = CheckpointManager(cfg.target.ckpt_dir).restore()
            params = state["params"] if "params" in state else state
            if verbose:
                print(f"[pipeline] restored checkpoint step {step}")
        else:
            params = init_params(jax.random.PRNGKey(cfg.target.seed),
                                 self.model.spec)
        comp = init_lm_comp(self.model)
        if cfg.train.qat_steps:
            params = self._qat_train(params, comp, cfg, verbose)
        plan.params, plan.comp = params, comp
        plan.metrics["n_params"] = int(spec_count(self.model.spec))
        plan.metrics["n_units"] = len(lm_comp_layers(self.model))
        if verbose:
            print(f"[pipeline] {self.name}: "
                  f"{plan.metrics['n_params'] / 1e6:.1f}M params, "
                  f"{plan.metrics['n_units']} compressible units")

    def _qat_train(self, params, comp, cfg: PipelineConfig, verbose: bool):
        """Optional LM QAT through the `repro.launch.train` step factories."""
        import jax

        from repro.data.synthetic import SyntheticTokens
        from repro.launch.train import StepConfig, make_optimizer, make_train_step

        step_cfg = StepConfig(qat=True, with_comp=True, remat=False,
                              q_block=128, kv_block=128, lr=cfg.target.lr)
        train_step = jax.jit(make_train_step(self.model, step_cfg))
        state = {"params": params,
                 "opt": make_optimizer(step_cfg).init(params)}
        data = SyntheticTokens(vocab=self.acfg.vocab, seed=cfg.target.data_seed)
        loss = float("nan")
        for i in range(cfg.train.qat_steps):
            x, y = data.batch(i, cfg.target.batch_size, 64)
            state, metrics = train_step(state, {"tokens": x, "labels": y},
                                        comp)
            loss = float(metrics["loss"])
        if verbose:
            print(f"[pipeline] LM QAT: {cfg.train.qat_steps} steps, "
                  f"final loss={loss:.3f}")
        return state["params"]

    def stage_energy_model(self, plan: CompressionPlan, cfg: PipelineConfig,
                           verbose: bool = False) -> None:
        from repro.core.energy_lut import uniform_trace_lut

        energies = self._unit_energies(plan.params, plan.comp)
        total = sum(energies.values())
        plan.shares = {n: e / max(total, 1e-12) for n, e in energies.items()}
        plan.luts = {"uniform": uniform_trace_lut()}
        plan.metrics["energy_per_token"] = float(total)
        self._unit_energy_cache = energies

    def stage_schedule(self, plan: CompressionPlan, cfg: PipelineConfig,
                       verbose: bool = False) -> None:
        from repro.core.lm_compress import (
            restrict_all_codebooks,
            symmetric_codebook_values,
        )

        k = cfg.serve.compress_k
        e_before = getattr(self, "_unit_energy_cache", None)
        if e_before is None:
            e_before = self._unit_energies(plan.params, plan.comp)
        total_before = sum(e_before.values())
        if not k:
            plan.metrics["energy_before"] = float(total_before)
            plan.metrics["energy_after"] = float(total_before)
            return
        values = symmetric_codebook_values(k)
        plan.comp = restrict_all_codebooks(self.model, plan.comp, values)
        e_after = self._unit_energies(plan.params, plan.comp)
        plan.decisions = [
            {"layer": name, "share": e_before[name] / max(total_before, 1e-12),
             "prune_ratio": None, "k": k,
             "energy_before": e_before[name], "energy_after": e_after[name],
             "accuracy": None, "accepted": True, "tried": [[0.0, k]]}
            for name in e_before
        ]
        plan.metrics["energy_before"] = float(total_before)
        plan.metrics["energy_after"] = float(sum(e_after.values()))
        plan.metrics["compress_k"] = k
        if verbose:
            print(f"[pipeline] restricted {len(e_before)} units to "
                  f"{k}-value codebooks "
                  f"(per-token energy {total_before:.3g} -> "
                  f"{plan.metrics['energy_after']:.3g} eu)")

    def stage_export(self, plan: CompressionPlan, cfg: PipelineConfig,
                     verbose: bool = False) -> None:
        from repro.core.export import export_summary
        from repro.core.lm_compress import export_lm_matmuls, lut_parity_report

        arts, skips = export_lm_matmuls(self.model, plan.params, plan.comp,
                                        block_k=cfg.export.block_k)
        plan.artifacts = arts
        summary = export_summary(arts)
        checked = lut_parity_report(self.model, plan.params, plan.comp, arts)
        summary["parity_max_rel_err"] = max(checked.values()) if checked else 0.0
        summary["skipped"] = len(skips)
        plan.metrics.update({f"export_{k}": v for k, v in summary.items()
                             if k != "skipped_units"})
        if plan.stats is None:
            plan.stats = {}
        plan.stats.setdefault("export", {})["skip_report"] = skips
        if verbose and arts:
            print(f"[pipeline] exported {summary['layers']} matmuls, "
                  f"{summary['weight_bytes_packed'] / 1e6:.2f} MB packed "
                  f"({summary['compression_vs_int8']:.2f}x vs int8), "
                  f"LUT parity max rel err "
                  f"{summary['parity_max_rel_err']:.2e}")
        if verbose and skips:
            print(f"[pipeline] export skipped {len(skips)} units:")
            for s in skips:
                print(f"  - {s['unit']}: {s['reason']} ({s['detail']})")

    def serve_handle(self, plan: CompressionPlan, k: int):
        """The single-variant `PlanHandle` the pinned serve stage uses."""
        from repro.serving import PlanHandle

        if k and plan.comp is not None:
            return PlanHandle.from_comp(plan.comp, compress_k=k,
                                        plan_id=f"k{k}")
        if k:
            return PlanHandle.from_compress_k(self.model, k)
        return PlanHandle.uncompressed()

    def _fleet_handles(self, plan: CompressionPlan, cfg: PipelineConfig):
        """Resolve `serve.plans` specs + `serve.plans_dir` into handles."""
        from repro.pipeline.config import parse_plan_spec
        from repro.serving import PlanHandle, PlanRegistry

        registry = PlanRegistry()
        if cfg.serve.plans_dir:
            for h in PlanRegistry.from_dir(cfg.serve.plans_dir):
                registry.register(h)
        for spec in cfg.serve.plans:
            k, msr = parse_plan_spec(spec)
            if k is None:
                loaded = CompressionPlan.load(spec)
                registry.register(PlanHandle.from_compression_plan(loaded))
            elif k == 0:
                registry.register(PlanHandle.uncompressed())
            else:
                registry.register(PlanHandle.from_compress_k(
                    self.model, k, msr_bits=msr))
        return registry

    def serve_trace(self, cfg: PipelineConfig):
        """(shapes, EngineConfig, requests) of the serve stage's trace.

        Deterministic in ``cfg.serve``: the same config gives the same
        buckets and the same seeded prompts."""
        import jax

        from repro.serving import EngineConfig, ServeRequest

        s = cfg.serve
        shapes = lm_trace_shapes(s.requests, s.prompt_len, s.new_tokens,
                                 s.mixed, stride=s.mixed_stride)
        p_bucket = max(sh[0] for sh in shapes)
        n_bucket = max(sh[1] for sh in shapes)
        # dedupe and sort: EngineConfig rejects duplicate buckets, and a
        # tiny p_bucket makes the half-size bucket collide with it
        p_buckets = tuple(sorted({max(p_bucket // 2, 2), p_bucket}))
        ecfg = EngineConfig(max_batch=s.max_batch,
                            prompt_buckets=p_buckets,
                            new_token_buckets=(n_bucket,))
        prompts = [
            jax.random.randint(jax.random.PRNGKey(s.prompt_seed + i),
                               (plen,), 0, self.acfg.vocab)
            for i, (plen, _) in enumerate(shapes)
        ]
        requests = [
            ServeRequest(tokens=prompt, max_new_tokens=ntok,
                         temperature=s.temperature,
                         tenant=f"tenant{i % 2}")
            for i, (prompt, (_, ntok)) in enumerate(zip(prompts, shapes))
        ]
        return shapes, ecfg, requests

    def stage_serve(self, plan: CompressionPlan, cfg: PipelineConfig,
                    verbose: bool = False) -> None:
        from repro.serving import ServingEngine

        s = cfg.serve
        k = s.compress_k
        shapes, ecfg, requests = self.serve_trace(cfg)

        if s.plans or s.plans_dir:
            self._serve_fleet(plan, cfg, ecfg, shapes, requests, verbose)
            return

        handle = self.serve_handle(plan, k)

        def drain(mode):
            engine = ServingEngine(self.model, plan.params, mode=mode,
                                   config=ecfg, plan=handle)
            engine.warmup(shapes)
            warm_compiles = engine.cache.compile_count
            results = engine.serve(requests)
            rep = engine.report()
            rep["recompiles_after_warmup"] = (engine.cache.compile_count
                                              - warm_compiles)
            return {r.rid: r for r in results}, rep

        results, rep = drain(s.mode)
        plan.metrics.update({f"serve_{key}": val for key, val in rep.items()
                             if isinstance(val, (int, float, bool))})
        plan.metrics["serve_mode"] = s.mode
        parity: Optional[bool] = None
        if s.verify_oneshot and s.mode == "engine":
            ref, _ = drain("oneshot")
            parity = all(results[r].tokens == ref[r].tokens for r in results)
            plan.metrics["serve_parity_engine_vs_oneshot"] = bool(parity)
        self.last_serve_results = results
        if verbose:
            line = (f"[pipeline] {s.mode}: {rep['requests']} requests, "
                    f"{rep['new_tokens']} tokens "
                    f"({rep['tokens_per_s']:.1f} tok/s), "
                    f"{rep['recompiles_after_warmup']} recompiles after "
                    f"warmup")
            if parity is not None:
                line += f", engine==oneshot: {parity}"
            print(line)

    def _serve_fleet(self, plan: CompressionPlan, cfg: PipelineConfig, ecfg,
                     shapes, requests, verbose: bool) -> None:
        """Fleet path: route the trace across every resident plan."""
        from repro.serving import FleetRouter

        s = cfg.serve
        registry = self._fleet_handles(plan, cfg)
        fleet = FleetRouter(self.model, plan.params, registry,
                            mode=s.mode if s.mode != "oneshot" else "engine",
                            config=ecfg)
        fleet.warmup(shapes)
        results = fleet.serve(requests)
        rep = fleet.report()
        plan.metrics.update({f"serve_{key}": val for key, val in rep.items()
                             if isinstance(val, (int, float, bool))})
        plan.metrics["serve_mode"] = "fleet"
        plan.metrics["serve_plans"] = ",".join(h.plan_id
                                               for h in fleet.levels)
        # engine-local rids repeat across the fleet; key on trace order
        self.last_serve_results = dict(enumerate(results))
        self.last_fleet_report = rep
        if verbose:
            routed = {pid: p["requests"] for pid, p in rep["plans"].items()}
            print(f"[pipeline] fleet: {rep['requests']} requests over "
                  f"{rep['plans_resident']} plans {routed}, "
                  f"{rep['new_tokens']} tokens "
                  f"({rep['tokens_per_s']:.1f} tok/s), "
                  f"{rep['recompiles_after_warmup']} recompiles after "
                  f"warmup")


# ==================================================== routing-aware targets


# per-(layer, expert) slice names from LMTarget._unit_energies /
# iter_eligible_units: "blocks/g0/moe/w_gate[1][e2]", "tail/t0/moe/w_up[e0]",
# "blocks/g0/ssm/in_proj[1]", "tail/t0/mlp/w_down"
_EXPERT_SLICE_RE = re.compile(
    r"^(?P<base>.+)/(?P<key>[^/\[]+)(?:\[(?P<li>\d+)\])?\[e(?P<ei>\d+)\]$")
_LAYER_SLICE_RE = re.compile(
    r"^(?P<base>.+)/(?P<key>[^/\[]+)(?:\[(?P<li>\d+)\])?$")


def _slice_key(name: str) -> Tuple[str, int, Optional[int]]:
    """(unit path, layer index, expert index|None) of one energy-slice name."""
    m = _EXPERT_SLICE_RE.match(name)
    if m:
        return (f"{m.group('base')}/{m.group('key')}",
                int(m.group("li") or 0), int(m.group("ei")))
    m = _LAYER_SLICE_RE.match(name)
    if m:
        return (f"{m.group('base')}/{m.group('key')}",
                int(m.group("li") or 0), None)
    return (name, 0, None)


def traffic_weighted_unit_energies(energies: Dict[str, float],
                                   stats) -> Dict[str, float]:
    """Scale per-slice tile energies by measured routing traffic.

    ``stats`` is a `repro.core.routing_stats.RoutingStats`. Expert slices
    are charged ``energy * share * E`` (uniform traffic changes nothing,
    hot experts weigh more); scan-layer slices likewise against the
    activity share. Slices without routing statistics pass through.
    """
    from repro.core import routing_stats as rs

    moe = {u: rs.traffic_shares(c) for u, c in stats.moe_counts.items()}
    scan = {u: rs.activity_shares(a) for u, a in stats.scan_activity.items()}
    out: Dict[str, float] = {}
    for name, e in energies.items():
        path, li, ei = _slice_key(name)
        base = path.rsplit("/", 1)[0]
        if ei is not None and base in moe:
            shares = moe[base]
            out[name] = float(e * shares[li, ei] * shares.shape[-1])
        elif ei is None and base in scan:
            shares = scan[base]
            out[name] = float(e * shares[li] * shares.size)
        else:
            out[name] = float(e)
    return out


class _RoutedTarget(LMTarget):
    """LM target with traffic-weighted per-unit compression.

    Extends the uniform LM schedule with a calibration pass
    (`repro.core.routing_stats.collect_lm_routing_stats`): the profile
    stage measures how traffic distributes over routed units, the energy
    model scales each unit's tile energy by its measured share, and the
    schedule stage assigns per-unit codebook sizes from the config's k
    ladder by traffic rank — hot units keep gentler (larger-k) codebooks,
    cold units compress aggressively. Subclasses define which units are
    routed and how assignments map onto comp entries."""

    def _collect_routing(self, plan: CompressionPlan, cfg: PipelineConfig,
                         verbose: bool = False):
        from repro.core import routing_stats as rs

        r = cfg.routing
        stats = rs.collect_lm_routing_stats(
            self.model, plan.params, comp=plan.comp,
            batches=r.calib_batches, batch_size=r.calib_batch_size,
            seq_len=r.calib_seq_len, seed=r.calib_seed)
        if plan.stats is None:
            plan.stats = {}
        plan.stats["routing"] = stats.as_arrays()
        self._routing_cache = stats
        if verbose:
            units = len(stats.moe_counts) + len(stats.scan_activity)
            print(f"[pipeline] routing calibration: {stats.tokens} tokens "
                  f"over {units} routed units")
        return stats

    def _routing_stats(self, plan: CompressionPlan, cfg: PipelineConfig):
        """Cached -> plan-recorded -> freshly collected, in that order."""
        stats = getattr(self, "_routing_cache", None)
        if stats is not None:
            return stats
        arrays = (plan.stats or {}).get("routing")
        if arrays:
            from repro.core.routing_stats import RoutingStats

            self._routing_cache = RoutingStats.from_arrays(
                {k: v for k, v in arrays.items()})
            return self._routing_cache
        return self._collect_routing(plan, cfg)

    def _unit_energies(self, params, comp) -> Dict[str, float]:
        energies = super()._unit_energies(params, comp)
        stats = getattr(self, "_routing_cache", None)
        if stats is None:
            return energies
        return traffic_weighted_unit_energies(energies, stats)

    def _routed_assignments(self, stats, cfg: PipelineConfig) -> List[Tuple]:
        """(path, layer, expert|None, k, traffic_share) per routed slice."""
        raise NotImplementedError

    # ------------------------------------------------------------- stages

    def stage_profile(self, plan: CompressionPlan, cfg: PipelineConfig,
                      verbose: bool = False) -> None:
        super().stage_profile(plan, cfg, verbose)
        self._collect_routing(plan, cfg, verbose)

    def stage_energy_model(self, plan: CompressionPlan, cfg: PipelineConfig,
                           verbose: bool = False) -> None:
        self._routing_stats(plan, cfg)   # ensure the traffic prior is live
        super().stage_energy_model(plan, cfg, verbose)

    def stage_schedule(self, plan: CompressionPlan, cfg: PipelineConfig,
                       verbose: bool = False) -> None:
        from repro.core.lm_compress import (
            restrict_all_codebooks,
            set_codebook,
            symmetric_codebook_values,
        )

        k = cfg.serve.compress_k
        e_before = getattr(self, "_unit_energy_cache", None)
        if e_before is None:
            e_before = self._unit_energies(plan.params, plan.comp)
        total_before = sum(e_before.values())
        plan.metrics["energy_before"] = float(total_before)
        if not k:
            plan.metrics["energy_after"] = float(total_before)
            return

        # uniform floor first (every eligible unit gets the serve codebook),
        # then traffic-ranked per-unit overrides from the k ladder
        plan.comp = restrict_all_codebooks(self.model, plan.comp,
                                           symmetric_codebook_values(k))
        stats = self._routing_stats(plan, cfg)
        routed = self._routed_assignments(stats, cfg)
        for path, li, ei, kk, _share in routed:
            plan.comp = set_codebook(plan.comp, path,
                                     symmetric_codebook_values(int(kk)),
                                     layer=li, expert=ei)
        e_after = self._unit_energies(plan.params, plan.comp)

        assign = {(p, li, ei): (kk, share)
                  for p, li, ei, kk, share in routed}
        plan.decisions = []
        for name in e_before:
            kk, tshare = assign.get(_slice_key(name), (k, None))
            d = {"layer": name,
                 "share": e_before[name] / max(total_before, 1e-12),
                 "prune_ratio": None, "k": int(kk),
                 "energy_before": e_before[name],
                 "energy_after": e_after[name],
                 "accuracy": None, "accepted": True,
                 "tried": [[0.0, int(kk)]]}
            if tshare is not None:
                d["traffic_share"] = float(tshare)
            plan.decisions.append(d)

        plan.metrics["energy_after"] = float(sum(e_after.values()))
        plan.metrics["compress_k"] = k
        plan.metrics["routed_units"] = len(routed)
        plan.metrics["routing_tokens"] = int(stats.tokens)
        if verbose:
            ks = sorted({int(kk) for _, _, _, kk, _ in routed})
            print(f"[pipeline] routed {len(routed)} unit slices onto "
                  f"k ladder {ks} (uniform floor k={k}; per-token energy "
                  f"{total_before:.3g} -> "
                  f"{plan.metrics['energy_after']:.3g} eu)")


class MoETarget(_RoutedTarget):
    """MoE LM: per-expert codebooks sized by measured dispatch frequency."""

    kind = "moe"

    def _routed_assignments(self, stats, cfg: PipelineConfig) -> List[Tuple]:
        from repro.core import routing_stats as rs
        from repro.core.lm_compress import MOE_EXPERT_KEYS

        ladder = tuple(cfg.routing.k_ladder)
        out: List[Tuple] = []
        for base, counts in sorted(stats.moe_counts.items()):
            shares = rs.traffic_shares(counts)
            for li in range(shares.shape[0]):
                ks = rs.assign_rank_k(shares[li], ladder)
                for key in MOE_EXPERT_KEYS:
                    for ei in range(shares.shape[1]):
                        out.append((f"{base}/{key}", li, ei, int(ks[ei]),
                                    float(shares[li, ei])))
        return out


class ScanTarget(_RoutedTarget):
    """SSM/RG-LRU LM: per-scan-unit codebooks sized by measured activity."""

    kind = "scan"

    def _routed_assignments(self, stats, cfg: PipelineConfig) -> List[Tuple]:
        from repro.core import routing_stats as rs
        from repro.core.lm_compress import lm_comp_layers

        ladder = tuple(cfg.routing.k_ladder)
        by_base: Dict[str, List[str]] = {}
        for path in lm_comp_layers(self.model):
            by_base.setdefault(path.rsplit("/", 1)[0], []).append(path)
        out: List[Tuple] = []
        for base, act in sorted(stats.scan_activity.items()):
            shares = rs.activity_shares(act)
            ks = rs.assign_rank_k(shares, ladder)
            for li in range(shares.size):
                for path in by_base.get(base, ()):
                    out.append((path, li, None, int(ks[li]),
                                float(shares[li])))
        return out
