"""olmo-1b served with a uniform k=4 plan on the packed 4-bit LUT GEMM.

Set-up makes the weights from the seed on the device in one jitted call,
restricts every eligible matmul to the file's codebook with the functions
the LM target's schedule and export use (`restrict_all_codebooks`, then
`attach_serve_artifacts` inside the engine), builds
``ServingEngine(mode="engine")`` with ``lut_serve=True``, compiles every
executable the config fixes and runs each once with every row inactive.
The profile, energy-model and schedule stages are skipped: a uniform plan
reads none of their outputs.

The window drives ``ServingEngine.submit`` and ``step`` from one thread.
Before it, set-up serves the mix's untimed ramp and queues its backlog;
with a backlog the window opens once every slot decodes. In the window the
requests due there are submitted at their due times and drained after it
closes. Which of these a mix has, the traffic generator says by the
requests it returns.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic
from chipbench.manifest import load_module

REF = load_module(Path(__file__).with_name("olmo-1b-k4_reference.py"))
DRAIN_LIMIT_S = 120.0


# ----------------------------------------------------------------- weights


def weight_layout(model: dict) -> dict:
    """{path: (shape, std)} of every parameter, in the program's tree."""
    L, d = model["n_layers"], model["d_model"]
    H, D, F = model["n_heads"], model["head_dim"], model["d_ff"]
    rows = -(-model["vocab"] // 256) * 256
    return {
        "blocks/g0/attn/wq": ((L, d, H, D), d ** -0.5),
        "blocks/g0/attn/wk": ((L, d, H, D), d ** -0.5),
        "blocks/g0/attn/wv": ((L, d, H, D), d ** -0.5),
        "blocks/g0/attn/wo": ((L, H, D, d), (H * D) ** -0.5),
        "blocks/g0/mlp/w_gate": ((L, d, F), d ** -0.5),
        "blocks/g0/mlp/w_up": ((L, d, F), d ** -0.5),
        "blocks/g0/mlp/w_down": ((L, F, d), F ** -0.5),
        "embed/table": ((rows, d), 0.02),
    }


def seed_key(seed: int):
    seed = int(seed) % 2**63
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)


def make_weights(model: dict, seed: int) -> dict:
    """Every weight from the seed, float32, in one jitted call."""
    layout = weight_layout(model)

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, (shape, std)) in enumerate(sorted(layout.items())):
            k = jax.random.fold_in(key, i)
            flat[path] = jax.random.normal(k, shape, jnp.float32) * std
        return flat

    flat = make(seed_key(seed))
    tree = {"blocks": {"g0": {"attn": {}, "ln1": {}, "ln2": {}, "mlp": {}}},
            "embed": {}, "final_norm": {}}
    for path, value in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node[p]
        node[leaf] = value
    return tree


def _same_layout(params, program_shapes) -> None:
    ours = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    theirs = jax.tree.map(lambda x: (x.shape, x.dtype), program_shapes)
    if ours != theirs:
        raise ValueError(f"the benchmark's olmo weights no longer match the "
                         f"program's parameter tree: {theirs}")


# ------------------------------------------------------------------ engine


def engine_config(engine: dict):
    from repro.serving import EngineConfig

    return EngineConfig(
        max_batch=engine["max_batch"], max_waves=engine["max_waves"],
        prompt_buckets=tuple(engine["prompt_buckets"]),
        new_token_buckets=tuple(engine["new_token_buckets"]),
        chunk_buckets=tuple(engine["chunk_buckets"]),
        chunk_rows=engine["chunk_rows"], q_block=engine["q_block"],
        kv_block=engine["kv_block"], cache_dtype=engine["cache_dtype"],
        pad_token=engine["pad_token"], lut_serve=engine["lut_serve"])


def exercise(engine) -> None:
    """Run the group decode and every chunk executable once, every row
    inactive, on a scratch cache, and read their logits back as the engine
    does (an eager slice off the padded vocabulary, one small program per
    shape): nothing compiles or runs for the first time inside the ramp or
    the window."""
    from repro.serving.bucketing import chunk_plan

    ecfg = engine.config
    group = engine.cache.group_fns(engine.params)
    cache = group.make_cache()
    zeros = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    vocab = engine.model.cfg.vocab
    logits, cache = group.decode(engine.params, cache, zeros(group.batch, 1),
                                 jnp.zeros((group.batch,), bool))
    np.asarray(logits[:, 0, :vocab])
    sizes = sorted({c for p in ecfg.prompt_buckets
                    for c in chunk_plan(p, ecfg.resolved_chunk_buckets)})
    for size in sizes:
        for rows in ecfg.chunk_row_buckets:
            step = engine.cache.chunk_fns(size, rows, engine.params)
            logits, cache = step.fn(engine.params, cache, zeros(rows, size),
                                    zeros(rows), zeros(rows),
                                    jnp.zeros((rows,), bool))
            np.asarray(logits[:, :vocab])
    jax.block_until_ready(cache)


def emitted(engine) -> int:
    """Output tokens the engine has produced so far."""
    done = sum(len(r.tokens) for r in engine._completed.values())
    return done + sum(len(s.tokens) for g in engine._groups
                      for s in g.slots if s is not None)


def in_flight(engine) -> dict:
    """{rid: (tokens so far, padded prompt, first-token time)} of the
    requests that hold a slot."""
    out = {}
    for g in engine._groups:
        for s in g.slots:
            if s is not None:
                out[s.req.rid] = (len(s.tokens), s.stats.bucket[1],
                                  s.stats.t_first_token)
    return out


class Clock:
    """Prints the split of set-up to stderr."""

    def __init__(self):
        self.t = time.perf_counter()

    def lap(self, what: str) -> None:
        now = time.perf_counter()
        print(f"setup: {what} {now - self.t:.3f} s", file=sys.stderr)
        self.t = now


@dataclasses.dataclass
class Session:
    config: dict
    mix: dict
    run: object
    model: object
    params: dict
    engine: object
    prompts: dict = dataclasses.field(default_factory=dict)   # rid -> prompt
    window_rids: list = dataclasses.field(default_factory=list)
    queued_rids: set = dataclasses.field(default_factory=set)
    sample: Optional[list] = None     # (prompt, served) pairs to compare


def setup(config: dict, mix: dict, run) -> Session:
    from repro.configs import get_config
    from repro.core.lm_compress import init_lm_comp, restrict_all_codebooks
    from repro.models.lm import build_lm
    from repro.nn.spec import init_params
    from repro.serving import PlanHandle, ServingEngine

    clock = Clock()
    acfg = dataclasses.replace(get_config("olmo-1b"), **config["model"])
    model = build_lm(acfg)
    params = jax.block_until_ready(make_weights(config["model"], run.seed))
    clock.lap("weights")
    _same_layout(params, jax.eval_shape(
        lambda k: init_params(k, model.spec), jax.random.PRNGKey(0)))
    plan = config["plan"]
    comp = restrict_all_codebooks(model, init_lm_comp(model), plan["codebook"])
    k = int(plan["compress_k"])
    engine = ServingEngine(
        model, params, mode=config["engine"]["mode"],
        config=engine_config(config["engine"]),
        plan=PlanHandle.from_comp(comp, compress_k=k, plan_id=f"k{k}"))
    del comp
    clock.lap("plan and engine (packed export)")
    if engine.qcfg.use_ref_kernel and jax.default_backend() == "tpu":
        raise RuntimeError("the LUT GEMM resolved to the jnp oracle on a TPU")
    ecfg = engine.config
    engine.warmup([(max(ecfg.prompt_buckets), max(ecfg.new_token_buckets))])
    clock.lap("warmup (compile or cache load, energy model)")
    exercise(engine)
    clock.lap("first execution of every executable")
    s = Session(config=config, mix=mix, run=run, model=model, params=params,
                engine=engine)
    ramp_s = traffic.ramp_seconds(mix)
    ramp = traffic.requests(mix, run.seed, "ramp", ramp_s, acfg.vocab)
    serve_until(s, ramp, ramp_s if ramp else 0.0, drain=False)
    queued = traffic.requests(mix, run.seed, "queued", 0.0, acfg.vocab)
    s.queued_rids = {_submit(s, r) for r in queued}
    slots = min(len(queued), ecfg.max_batch * ecfg.max_waves)
    while sum(1 for v in in_flight(engine).values() if v[0] > 0) < slots:
        with run.span("engine.step"):
            engine.step()
    clock.lap("ramp and queue")
    return s


def _submit(s: Session, r) -> int:
    with s.run.span("submit"):
        rid = s.engine.submit(r.prompt, r.max_new_tokens)
    s.prompts[rid] = r.prompt
    return rid


def serve_until(s: Session, reqs, seconds: float, *, drain: bool,
                label: str = "ramp") -> dict:
    """Submit ``reqs`` at their due times and step the engine for
    ``seconds`` (inside the span ``label``); with ``drain``, keep stepping
    until every one of them has finished (at most `DRAIN_LIMIT_S` more).
    Times are after ``t0``."""
    engine, span = s.engine, s.run.span
    with span(label):
        t0 = time.perf_counter()
        out = _serve_loop(s, reqs, seconds, t0)
    rids = out["rids"]
    if drain:
        limit = time.perf_counter() + DRAIN_LIMIT_S
        while (any(engine.result(r) is None for r in rids)
               and time.perf_counter() < limit):
            with span("engine.step"):
                engine.step()
    return out


def _serve_loop(s: Session, reqs, seconds: float, t0: float) -> dict:
    engine, span = s.engine, s.run.span
    samples = [(0.0, emitted(engine))]
    rids, submits = [], []
    i = 0
    while True:
        now = time.perf_counter() - t0
        while i < len(reqs) and reqs[i].due <= now:
            rids.append(_submit(s, reqs[i]))
            submits.append(time.perf_counter() - t0)
            i += 1
        if now >= seconds and i == len(reqs):
            break
        with span("engine.step"):
            busy = engine.step()
        samples.append((time.perf_counter() - t0, emitted(engine)))
        if not busy:
            nxt = reqs[i].due if i < len(reqs) else seconds
            wait = nxt - (time.perf_counter() - t0)
            if wait > 0:
                with span("wait_for_arrival"):
                    time.sleep(wait)
    return {"t0": t0, "rids": rids, "submits": submits, "samples": samples,
            "at_close": (in_flight(engine), set(engine._completed))}


def _record(engine, rid, due, submit, t0) -> dict:
    res = engine.result(rid)
    if res is None:
        return {"due": due, "submit": submit, "admitted": None,
                "first": None, "finish": None, "tokens": 0}
    st = res.stats
    rel = lambda t: None if t is None else t - t0  # noqa: E731
    return {"due": due, "submit": submit, "admitted": rel(st.t_admitted),
            "first": rel(st.t_first_token), "finish": rel(st.t_finish),
            "tokens": len(res.tokens)}


def window(s: Session, seconds: float) -> dict:
    from chipbench.window import count_between

    engine = s.engine
    reqs = traffic.requests(s.mix, s.run.seed, "window", seconds,
                            s.model.cfg.vocab)
    before, done0 = in_flight(engine), set(engine._completed)
    out = serve_until(s, reqs, seconds, drain=True, label="window")
    t0 = out["t0"]
    after, done1 = out["at_close"]
    # the requests due in the window, timed from their due times, and the
    # queued ones that finished inside it (their due time is its opening)
    records = [_record(engine, rid, r.due, sub, t0) for rid, r, sub in
               zip(out["rids"], reqs, out["submits"])]
    finished = sorted((done1 - done0) & s.queued_rids)
    records += [_record(engine, rid, 0.0, 0.0, t0) for rid in finished]
    s.window_rids = out["rids"] + finished
    spans = emitted_spans(engine, before, after, done1 - done0)
    firsts = [engine.result(r).stats.t_first_token for r in done1 - done0]
    firsts += [tf for _, _, tf in after.values()]
    first_tokens = sum(1 for tf in firsts
                       if tf is not None and 0.0 <= tf - t0 < seconds)
    return {"window_s": float(seconds), "requests": records,
            "tokens": count_between(out["samples"], 0.0, seconds),
            "attempted": len(records),
            "failed": sum(1 for r in records if r["finish"] is None),
            "counters": {"emitted_spans": spans,
                         "first_tokens": first_tokens,
                         "max_batch": engine.config.max_batch}}


def emitted_spans(engine, before: dict, after: dict, finished) -> list:
    """[(padded prompt, tokens before, tokens after)] of every request that
    produced tokens between two in-flight snapshots; ``finished`` are the
    requests that completed in between."""
    out = []
    for rid in finished:
        res = engine.result(rid)
        out.append((res.stats.bucket[1], before.get(rid, (0,))[0],
                    len(res.tokens)))
    for rid, (n1, bucket, _) in after.items():
        n0 = before.get(rid, (0,))[0]
        if n1 > n0:
            out.append((bucket, n0, n1))
    return out


# ------------------------------------------------------------------- check


def _sample(s: Session, seed: int) -> list:
    """Finished window requests to compare: the longest, then others in an
    order drawn from the seed, up to the file's token and request counts."""
    engine, chk = s.engine, s.config["check"]
    done = [rid for rid in s.window_rids if engine.result(rid) is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: len(engine.result(r).tokens))
    rng = np.random.default_rng([int(seed) % 2**64, 3])
    rest = [r for r in rng.permutation(done).tolist() if r != longest]
    picked, total = [], 0
    for rid in [longest] + rest:
        if total >= chk["sample_tokens"] or len(picked) >= chk["max_requests"]:
            break
        picked.append(rid)
        total += len(engine.result(rid).tokens)
    return [(s.prompts[rid], list(engine.result(rid).tokens)) for rid in picked]


def _free_engine(s: Session) -> None:
    s.engine = None
    gc.collect()


def reference_gaps(s: Session, sample: list, *, control: bool = False):
    """Per sampled request, the gaps of the served tokens below the
    reference's best (``control``: of the tokens the float8 reference puts
    first, at the same positions)."""
    cfg = s.config
    eng = cfg["engine"]
    total = max(eng["prompt_buckets"]) + max(eng["new_token_buckets"])
    blocks = s.params["blocks"]["g0"]
    layers = {**blocks["attn"], **blocks["mlp"]}
    embed = s.params["embed"]["table"]
    kw = dict(vocab=cfg["model"]["vocab"], theta=cfg["model"]["rope_theta"])
    cb = jnp.asarray(cfg["plan"]["codebook"], jnp.float32)
    out = []
    for prompt, served in sample:
        bucket = next(b for b in sorted(eng["prompt_buckets"])
                      if b >= len(prompt))
        seq = jnp.asarray(REF.sequence(prompt, served, bucket,
                                       eng["pad_token"], total))
        ref = REF.logits(layers, embed, cb, seq, **kw)
        if control:
            low = np.asarray(REF.logits(layers, embed, cb, seq, fp8=True,
                                        **kw))
            first = low[bucket - 1:bucket - 1 + len(served)].argmax(-1)
            out.append(REF.served_gaps(ref, first.tolist(), bucket))
        else:
            out.append(REF.served_gaps(ref, served, bucket))
    return out


def mean_gap(gaps) -> float:
    """Each sampled request's mean gap over its served tokens, averaged
    over the requests: a request that loops on one token with a wide
    margin counts once, not once a token."""
    if not gaps:
        return math.inf
    return float(np.mean([x.mean() for x in gaps]))


def compared(s: Session, win: dict, *, control: bool = False) -> dict:
    """The numbers the check compares: ``mean_logit_gap`` of the served
    tokens below the f32 reference (``control``: of the tokens the float8
    reference puts first) and the due requests that never finished. The
    sample is drawn once, then the engine is freed."""
    if s.sample is None:
        s.sample = _sample(s, s.run.seed)
        _free_engine(s)
    return {"mean_logit_gap": mean_gap(reference_gaps(s, s.sample,
                                                      control=control)),
            "unfinished_requests": float(win["failed"])}
