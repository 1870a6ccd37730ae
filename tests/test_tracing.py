"""Host spans of the serving engine and the profile stage, read back from
the profiler's own trace (``repro.*`` events on the host plane): names,
nesting, the token counts their stats carry, and that tracing changes no
served token."""

import glob

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.models.lm import build_lm
from repro.nn.spec import init_params
from repro.serving import EngineConfig, ServeRequest, ServingEngine

CFG = EngineConfig(max_batch=4, prompt_buckets=(8, 16),
                   new_token_buckets=(8,), max_waves=2)
# (prompt_len, new_tokens): both prompt buckets, 16-token prompts in two
# chunks, requests finishing mid-wave
TRACE = [(6, 8), (8, 5), (14, 8), (5, 8), (8, 8), (16, 6), (12, 8)]
SLOT_SPANS = {"engine.step", "engine.refill", "engine.chunk",
              "engine.decode", "engine.fetch", "engine.sample"}
WAVE_SPANS = {"engine.step", "engine.prefill", "engine.decode",
              "engine.fetch", "engine.sample"}


@pytest.fixture(scope="module")
def lm():
    cfg = get_config("olmo-1b").scaled_down(compute_dtype="float32")
    model = build_lm(cfg)
    params = init_params(jax.random.PRNGKey(0), model.spec)
    return model, params


@pytest.fixture(scope="module")
def requests(lm):
    model, _ = lm
    rng = np.random.default_rng(3)
    return [ServeRequest(rng.integers(0, model.cfg.vocab, size=plen)
                         .astype(np.int32), n) for plen, n in TRACE]


def program_spans(directory):
    """[(name, start_ns, end_ns, stats)] of every ``repro.*`` host event."""
    from jax.profiler import ProfileData

    path, = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name[len("repro."):], e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


def _total(spans, name, stat):
    return sum(st[stat] for n, _, _, st in spans if n == name)


@pytest.mark.parametrize("mode,names", [("engine", SLOT_SPANS),
                                        ("wave", WAVE_SPANS),
                                        ("oneshot", WAVE_SPANS)])
def test_engine_spans(lm, requests, tmp_path, mode, names):
    model, params = lm
    eng = ServingEngine(model, params, mode=mode, config=CFG)
    eng.warmup(TRACE)
    untraced = [r.tokens for r in eng.serve(requests)]
    with jax.profiler.trace(str(tmp_path)):
        traced = [r.tokens for r in eng.serve(requests)]
    assert traced == untraced

    spans = program_spans(tmp_path)
    assert {n for n, *_ in spans} == names
    steps = [(a, b) for n, a, b, _ in spans if n == "engine.step"]
    for n, a, b, _ in spans:
        if n in ("engine.decode", "engine.fetch", "engine.sample"):
            assert any(sa <= a and b <= sb for sa, sb in steps), n
    tokens = sum(len(t) for t in traced)
    assert _total(spans, "engine.sample", "tokens") == tokens
    assert _total(spans, "engine.sample", "finished") == len(requests)
    # every request's first token comes from prefill, the rest from decode
    assert _total(spans, "engine.decode", "active") == tokens - len(requests)
    assert all(st["rows"] == (1 if mode == "oneshot" else CFG.max_batch)
               for n, _, _, st in spans if n == "engine.decode")
    if mode == "engine":
        assert _total(spans, "engine.refill", "admitted") == len(requests)


def test_profile_opens_a_span_per_layer(tmp_path):
    from repro.core.runner import CnnRunner
    from repro.data.synthetic import SyntheticImages
    from repro.nn import cnn

    runner = CnnRunner(cnn.lenet5(10), SyntheticImages(num_classes=10, seed=3),
                       batch_size=16)
    params, state, _, comp = runner.init()
    runner.profile(params, state, comp, max_tiles=2)    # compile untraced
    with jax.profiler.trace(str(tmp_path)):
        runner.profile(params, state, comp, max_tiles=2)
    spans = program_spans(tmp_path)
    layers = [(a, b, st["layer"]) for n, a, b, st in spans
              if n == "profile.layer"]
    assert [name for *_, name in layers] == \
        [cl.name for cl in runner.model.comp_layers]
    assert sum(n == "profile.taps" for n, *_ in spans) == 1
    for inner in ("profile.inputs", "profile.stats"):
        starts = [a for n, a, _, _ in spans if n == inner]
        assert len(starts) == len(layers)
        assert all(any(la <= a <= lb for la, lb, _ in layers)
                   for a in starts)
