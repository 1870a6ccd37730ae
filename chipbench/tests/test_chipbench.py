"""CPU tests of the benchmark: manifest, traffic, window arithmetic, trace
reduction, the refusal to run without a TPU, and that a later PR can add a
mix and a cell with files and entries alone.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -n 6
"""

from __future__ import annotations

import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import manifest as mf
from chipbench import traffic, window

ROOT = mf.ROOT
FIXTURE = Path(__file__).with_name("data") / "lut_step.xplane.pb"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    return mf.load()


# ----------------------------------------------------------------- manifest


def test_manifest_keys_names_and_units(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[section]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append(entry["name"])
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    assert len(names) == len(set(names))
    fours = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert fours <= max(1, len(manifest["workloads"]) // 2)
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])
    assert 1 <= manifest["run_seconds"] <= 51


def test_every_named_file_exists(manifest):
    for c in manifest["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert (mf.PACKAGE / "configs" / f"{c['name']}.py").is_file()
    for w in manifest["workloads"]:
        traffic.load(w["traffic"])
    for section in ("end_to_end", "per_layer"):
        for m in manifest[section]:
            assert callable(mf.reader(section, m["name"]).read)


def test_each_per_layer_metric_moves_a_metric_its_cells_report(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    layers = {}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for cell in m["workloads"]:
            reported = {x["name"] for x in
                        mf.metrics_for(manifest, "end_to_end", cell)}
            assert m["moves"] in reported, (m["name"], cell)
        layers.setdefault(m["layer"], []).append(m["name"])
    for w in manifest["workloads"]:
        reported = {x["name"] for x in
                    mf.metrics_for(manifest, "end_to_end", w["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        assert mf.metrics_for(manifest, "per_layer", w["name"])


def test_bounds_within_the_contract(manifest):
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
    cells = len(manifest["workloads"])
    runs = 2 + 14 * 24
    need = runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200
    assert need <= 43200 and cells <= 24


# ------------------------------------------------------------------ traffic


@pytest.mark.parametrize("mix,segment", [("chat", "window"),
                                         ("batch", "queued")])
def test_traffic_is_deterministic_per_seed(mix, segment):
    m = traffic.load(mix)
    a = traffic.requests(m, 2**33 + 7, segment, 40.0, 50304)
    b = traffic.requests(m, 2**33 + 7, segment, 40.0, 50304)
    c = traffic.requests(m, 5, segment, 40.0, 50304)
    assert a
    assert [(r.due, r.max_new_tokens, r.prompt.tolist()) for r in a] == \
        [(r.due, r.max_new_tokens, r.prompt.tolist()) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]
    # another seed: the same work in another order
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in c)


def test_chat_rate_and_lengths():
    m = traffic.load("chat")
    reqs = traffic.requests(m, 3, "window", 50.0, 50304)
    rate = float(m["rate_per_s"])
    assert len(reqs) == round(rate * 50.0)
    due = np.array([r.due for r in reqs])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < 50.0
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new_tokens for r in reqs])
    assert p.min() >= 16 and p.max() <= 768
    assert o.min() >= 8 and o.max() <= 256
    assert abs(np.median(p) - 192) <= 12 and abs(np.median(o) - 48) <= 4
    assert all(0 < t < 50304 for r in reqs for t in r.prompt)


def test_batch_backlog_blocks():
    m = traffic.load("batch")
    reqs = traffic.requests(m, 9, "queued", 0.0, 50304)
    assert len(reqs) == m["backlog"] and all(r.due == 0.0 for r in reqs)
    block = m["block"]
    first = sorted(r.max_new_tokens for r in reqs[:block])
    second = sorted(r.max_new_tokens for r in reqs[block:2 * block])
    assert first == second
    assert min(first) >= 128 and max(first) <= 256


def test_due_times_fill_the_duration():
    rng = np.random.default_rng(0)
    due = traffic.due_times({"rate_per_s": 2.0}, 30.0, rng)
    assert len(due) == 60 and due[0] == 0.0 and due[-1] < 30.0


def test_a_mix_has_only_the_segments_its_keys_state():
    chat, batch = traffic.load("chat"), traffic.load("batch")
    assert traffic.requests(chat, 1, "queued", 0.0, 50304) == []
    assert len(traffic.requests(chat, 1, "ramp", 15.0, 50304)) == 30
    assert traffic.requests(batch, 1, "window", 50.0, 50304) == []
    assert traffic.requests(batch, 1, "ramp", 15.0, 50304) == []


def test_gamma_gaps_burst_at_the_same_mean_rate():
    steady = traffic.load("chat")
    bursty = dict(steady, gaps={"dist": "gamma", "cv": 3.0})
    a = traffic.requests(bursty, 7, "window", 50.0, 50304)
    b = traffic.requests(bursty, 7, "window", 50.0, 50304)
    p = traffic.requests(steady, 7, "window", 50.0, 50304)
    assert [r.due for r in a] == [r.due for r in b]
    assert len(a) == len(p) == 100 and a[-1].due < 50.0

    def cv(reqs):
        gaps = np.diff([r.due for r in reqs])
        return gaps.std() / gaps.mean()

    assert cv(p) < 1.2 < 2.0 < cv(a)
    # same lengths: only the arrivals differ
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in p)


# ------------------------------------------------------- window arithmetic


def test_percentile_matches_linear_interpolation():
    assert window.percentile([1, 2, 3, 4], 50) == 2.5
    assert window.percentile([5.0], 90) == 5.0
    assert math.isnan(window.percentile([], 90))
    assert window.percentile([1.0, math.inf], 0) == 1.0


def test_tails_are_timed_from_due_over_every_due_request():
    reqs = [{"due": 0.0, "submit": 0.5, "admitted": 0.6, "first": 1.0,
             "finish": 2.0, "tokens": 11},
            {"due": 1.0, "submit": 1.0, "admitted": None, "first": None,
             "finish": None, "tokens": 0}]
    ttft = window.ttft_s(reqs)
    assert ttft[0] == 1.0           # from due, not from submit (0.5)
    assert ttft[1] == math.inf      # a request that never answered counts
    assert window.tpot_s(reqs) == [0.1]


def test_in_flight_tokens_count_and_window_interpolation():
    samples = [(0.0, 100), (1.0, 116), (2.0, 132), (2.5, 140)]
    # a window closing between two steps counts the tokens produced so far
    assert window.count_between(samples, 0.0, 2.25) == pytest.approx(36.0)
    assert window.interpolate(samples, 9.0) == 140


def test_stage_s_counts_whole_passes():
    assert window.per_pass(10.4, 13) == pytest.approx(0.8)
    assert window.per_pass(10.0, 0) is None


def _ctx(win, **kw):
    from chipbench.run import Context

    return Context(cell={"name": "c"}, config={}, mix={}, window=win,
                   setup_s=kw.get("setup_s", 1.0), device_kind="TPU v5 lite",
                   n_devices=1)


def test_end_to_end_readers():
    reqs = [{"due": float(i), "submit": float(i), "admitted": float(i),
             "first": i + 0.1 * (i + 1), "finish": i + 2.0, "tokens": 5}
            for i in range(10)]
    win = {"window_s": 10.0, "requests": reqs, "tokens": 321.0, "passes": 4}
    ctx = _ctx(win, setup_s=12.5)
    read = lambda n: mf.reader("end_to_end", n).read(ctx)  # noqa: E731
    assert read("tokens_per_s") == pytest.approx(32.1)
    ttft = mf.reader("per_layer", "ttft_p90_ms.chat").read(ctx)
    assert ttft == pytest.approx(
        window.percentile([0.1 * (i + 1) for i in range(10)], 90) * 1e3)
    assert read("stage_s") == pytest.approx(2.5)
    assert read("setup_s") == 12.5


def test_decode_flops_and_lut_costs():
    from chipbench import costs

    model = {"n_layers": 16, "d_model": 2048, "n_heads": 16, "n_kv_heads": 16,
             "head_dim": 128, "d_ff": 8192, "vocab": 50304}
    params = costs.lm_matmul_params(model)
    assert 1.17e9 < params < 1.19e9
    one = costs.decode_flops(model, [(64, 0, 1)])
    assert one == pytest.approx(2 * params + 4 * 16 * 2048 * 64)
    shapes = [("f32", (8, 8192)), ("bf16", (8, 2048)), ("s8", (1024, 8192)),
              ("s32", (16,)), ("f32", (1, 8192))]
    flops, moved = costs.lut_matmul(shapes)
    assert flops == 2 * 8 * 2048 * 8192
    assert moved == 8 * 8192 * 4 + 8 * 2048 * 2 + 1024 * 8192 + 64 + 8192 * 4
    t, bound = costs.roofline_s(flops, moved, {"bf16_flops": 197e12,
                                               "hbm_bytes_per_s": 819e9})
    assert bound == "hbm" and t == pytest.approx(moved / 819e9)
    assert costs.lut_matmul(shapes[:2]) is None


def test_peaks_refuse_an_unknown_device():
    from chipbench import peaks

    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


# ----------------------------------------------------------- trace reduction


def _ev(name, start, dur, long_name=""):
    from chipbench.trace import Event

    return Event(name, float(start), float(dur), long_name)


def test_trace_summary_busy_idle_and_named_gaps():
    from chipbench import trace

    ops = [_ev("fusion.1", 100, 50), _ev("fusion.2", 140, 40),
           _ev("custom-call.3", 300, 100,
               "%custom-call.3 = f32[8,8192] custom-call(bf16[8,2048] %a)"),
           _ev("all-reduce.4", 500, 100, "%all-reduce.4 = f32[50,50] "
               "all-reduce(f32[50,50] %x)")]
    mods = [_ev("jit_decode_fn", 100, 300), _ev("jit_chunk_fn", 500, 100)]
    devices = {0: {trace.OPS_LINE: ops, trace.MODULES_LINE: mods}}
    spans = [_ev("chipbench.window", 0, 1000), _ev("chipbench.engine.step",
                                                   0, 450),
             _ev("chipbench.submit", 600, 400)]
    s = trace.summarize(devices, spans, n_devices=1)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx((80 + 100 + 100) * 1e-9)
    assert s.collective_s == pytest.approx(100e-9)
    assert s.module_time("decode_fn") == (pytest.approx(300e-9), 1)
    gaps = dict((round(sec * 1e9), name) for name, sec in s.idle_gaps)
    assert gaps[400] == "submit"            # 600..1000, inside submit's span
    assert gaps[100] == "engine.step"       # 0..100
    br = s.breakdown()
    assert br["device_ops"][0][1] == pytest.approx(100e-9)
    assert len(br["idle_gaps"]) <= 10
    assert trace.shapes(ops[2].long_name) == [("f32", (8, 8192)),
                                              ("bf16", (8, 2048))]


@pytest.mark.skipif(not FIXTURE.is_file(), reason="no recorded trace")
def test_trace_reduction_on_a_recorded_chip_trace():
    from chipbench import costs, trace

    devices, spans = trace.load(str(FIXTURE))
    s = trace.summarize(devices, spans, n_devices=1)
    assert 0 < s.busy_s < s.window_s
    assert any(n.startswith("engine.step") or n == "outside any span"
               for n, _ in s.idle_gaps)
    calls = [costs.lut_matmul(trace.shapes(e.long_name))
             for e in s.op_events if "custom-call" in e.long_name]
    assert any(c is not None for c in calls)


# ---------------------------------------------------------- refusing to run


def _run(args, cwd, env_extra=None, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-m", "chipbench", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_exits_non_zero_without_a_tpu():
    r = _run(["--workload", "olmo-1b-k4.chat", "--seed", "1", "--seconds",
              "1", "--trace", "0"], ROOT)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_exits_non_zero_in_a_bare_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(mf.PACKAGE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(["--workload", "resnet20.profile", "--seed", "1", "--seconds",
              "1", "--trace", "0"], tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""


# ------------------------------------------------- adding a mix and a cell


GENERATOR = """
from chipbench.traffic import Request
import numpy as np

def requests(mix, seed, segment, duration, vocab):
    # arrivals on a fixed beat, a process no data file states
    if segment != "window":
        return []
    rng = np.random.default_rng([seed, 9])
    n = int(duration / mix["beat_s"])
    return [Request(due=i * mix["beat_s"],
                    prompt=rng.integers(1, vocab, size=8, dtype=np.int32),
                    max_new_tokens=4) for i in range(n)]
"""


def test_a_new_mix_and_cell_need_only_files_and_entries(tmp_path):
    shutil.copytree(mf.PACKAGE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    mixes = tmp_path / "chipbench" / "traffic"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # a bursty mix, by data alone
    burst = dict(traffic.load("chat"), gaps={"dist": "gamma", "cv": 2.5})
    (mixes / "burst.json").write_text(json.dumps(burst))
    # a new arrival process, by a data file that names a generator file
    (mixes / "beat.json").write_text(json.dumps({"generator": "beat_gen",
                                                 "beat_s": 0.5}))
    (mixes / "beat_gen.py").write_text(GENERATOR)
    for mix in ("burst", "beat"):
        bench["workloads"].append({"name": f"olmo-1b-k4.{mix}",
                                   "config": "olmo-1b-k4", "traffic": mix,
                                   "chips": 1, "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    m = mf.load(tmp_path / "BENCHMARK.json")

    def window_requests(name):
        loaded = traffic.load(mf.cell(m, name)["traffic"], mixes)
        return traffic.requests(loaded, 1, "window", 10.0, 1000, mixes)

    burst_reqs = window_requests("olmo-1b-k4.burst")
    assert len(burst_reqs) == 20
    gaps = np.diff([r.due for r in burst_reqs])
    assert gaps.std() / gaps.mean() > 1.5
    beat = window_requests("olmo-1b-k4.beat")
    assert [r.due for r in beat] == [0.5 * i for i in range(20)]
    assert all(len(r.prompt) == 8 and r.max_new_tokens == 4 for r in beat)
    cell = mf.cell(m, "olmo-1b-k4.beat")
    module = mf.config_module(cell["config"], tmp_path / "chipbench")
    assert callable(module.setup) and callable(module.window)
    assert callable(module.compared)
    assert {x["name"] for x in mf.metrics_for(m, "end_to_end", cell["name"])} \
        == {"setup_s"}


def test_a_mix_naming_a_missing_generator_is_refused(tmp_path):
    (tmp_path / "x.json").write_text(json.dumps({"generator": "nowhere"}))
    with pytest.raises(ValueError):
        traffic.load("x", tmp_path)
