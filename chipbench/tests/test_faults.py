"""Whole benchmark runs at a tiny size on the CPU, with the timed path
broken underneath: ``correct`` must come out false. Plus the controls: the
reference one precision step down reads above what the program reads.

Each run goes through ``chipbench.run.main`` in a fresh process, in a copy
of the checkout whose configuration and traffic files are shrunk; only the
look for a chip is skipped.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -n 6
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import manifest as mf

ROOT = mf.ROOT

TINY_MODEL = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 4,
              "head_dim": 32, "d_ff": 256, "vocab": 512}
TINY_ENGINE = {"max_batch": 4, "prompt_buckets": [16, 32, 64],
               "new_token_buckets": [32], "chunk_buckets": [16, 32],
               "chunk_rows": 2, "q_block": 16, "kv_block": 32}
# limits at this size, between what the program and the control read here
TINY_LIMITS = {"mean_logit_gap": 0.0015}

FAULTS = {
    # a token altered where it is produced: every 5th sampled token is the
    # one the model ranks last
    "token": """
import numpy as np
from repro.serving import engine as E
_orig = E.ServingEngine._sample_row
_n = [0]
def _bad(self, row, slot):
    _n[0] += 1
    if slot is not None and _n[0] % 5 == 0:
        return int(np.argmin(row))
    return _orig(self, row, slot)
E.ServingEngine._sample_row = _bad
""",
    # an answer altered where it is produced: one transition miscounted
    "answer": """
from repro.core import profiler as P
_orig = P.batched_layer_stats
def _bad(*a, **k):
    es, cnt, gh, ah = _orig(*a, **k)
    return es, cnt, gh.at[0, 0].add(1.0), ah
P.batched_layer_stats = _bad
""",
}


def tiny_checkout(tmp_path):
    shutil.copytree(mf.PACKAGE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    (tmp_path / "src").symlink_to(ROOT / "src")
    cfg_path = tmp_path / "chipbench" / "configs" / "olmo-1b-k4.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["model"].update(TINY_MODEL)
    cfg["engine"].update(TINY_ENGINE)
    cfg["check"].update(TINY_LIMITS, sample_tokens=120)
    cfg_path.write_text(json.dumps(cfg))
    for name, prompt, output in (("chat", (20, 4, 64), (8, 2, 32)),
                                 ("batch", (20, 4, 64), (8, 8, 32))):
        p = tmp_path / "chipbench" / "traffic" / f"{name}.json"
        mix = json.loads(p.read_text())
        mix["prompt"].update(min=prompt[1], max=prompt[2])
        if mix["prompt"]["dist"] == "lognormal":
            mix["prompt"]["median"] = prompt[0]
        mix["output"].update(min=output[1], max=output[2])
        if mix["output"]["dist"] == "lognormal":
            mix["output"]["median"] = output[0]
        mix.update({"rate_per_s": 3.0, "ramp_s": 2} if name == "chat"
                   else {"backlog": 48, "block": 16})
        p.write_text(json.dumps(mix))
    return tmp_path


def run_tiny(tmp_path, module: str, args, preamble: str = "",
             timeout: int = 900):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    code = ("import sys\nsys.path.insert(0, 'src')\n" + preamble + "\n"
            f"from {module} import main\n"
            f"sys.exit(main({list(args)!r}, require_tpu=False))\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stderr[-3000:]
    return [json.loads(line) for line in r.stdout.splitlines()
            if line.startswith("{")]


def run_cell(tmp_path, cell: str, fault: str = "",
             seconds: float = 3.0) -> dict:
    lines = run_tiny(tiny_checkout(tmp_path), "chipbench.run",
                     ["--workload", cell, "--seed", str(2**32 + 17),
                      "--seconds", str(seconds), "--trace", "0"],
                     preamble=FAULTS.get(fault, ""))
    return lines[-1]


@pytest.mark.parametrize("cell", ["olmo-1b-k4.chat", "olmo-1b-k4.batch"])
def test_sound_tiny_olmo_run_is_correct(tmp_path, cell):
    line = run_cell(tmp_path, cell)
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("cell", ["olmo-1b-k4.chat", "olmo-1b-k4.batch"])
def test_altered_token_is_not_correct(tmp_path, cell):
    line = run_cell(tmp_path, cell, fault="token")
    assert line["correct"] is False
    assert line["checks"]["mean_logit_gap"]["value"] > \
        line["checks"]["mean_logit_gap"]["limit"]


def test_altered_profile_answer_is_not_correct(tmp_path):
    line = run_cell(tmp_path, "resnet20.profile", fault="answer", seconds=1)
    assert line["correct"] is False
    assert line["checks"]["hist_max_abs_diff"]["value"] >= 1.0


def test_sound_profile_run_is_correct(tmp_path):
    line = run_cell(tmp_path, "resnet20.profile", seconds=1)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1


@pytest.mark.parametrize("cell", ["olmo-1b-k4.chat", "resnet20.profile"])
def test_control_reads_above_the_program(tmp_path, cell):
    lines = run_tiny(tiny_checkout(tmp_path), "chipbench.control",
                     ["--workload", cell, "--seeds", "3", "4",
                      "--seconds", "2" if cell.startswith("olmo") else "1"])
    assert len(lines) == 2
    for line in lines:
        # the same checks a run makes: the program holds, the control fails
        assert line["program_correct"] is True, line
        assert line["control_correct"] is False, line
        assert set(line["program"]) == set(line["control"])
        assert any(c["value"] > c["limit"] for c in line["control"].values())
