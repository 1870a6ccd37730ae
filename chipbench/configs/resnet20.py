"""resnet20 under the compression job's profile stage, pass after pass.

A pass is ``CnnRunner.profile`` as ``repro compress --target cnn`` runs it
(``ProfileStageConfig`` defaults: one batch, 16 tiles per layer, the jnp
oracle since no pipeline option turns the kernel statistics on), on a fresh
batch of seeded images. With more than one device visible, `profile_layer`
shards every layer's tiles over the chips and psums the statistics.

The weights come from the program's initializer under a key from the seed;
the profile needs no trained weights. Set-up runs two passes so that every
eager op and jitted program the window uses is compiled and has run.
"""

from __future__ import annotations

import dataclasses
import math
import sys
import time
from pathlib import Path

import jax
import numpy as np

from chipbench.manifest import load_module

REF = load_module(Path(__file__).with_name("resnet20_reference.py"))
STAT_NAMES = ("energy_sum", "count", "group_hist", "act_hist")


def seed_key(seed: int):
    seed = int(seed) % 2**63
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)


class Images:
    """The runner's dataset: a fresh standard-normal batch per pass."""

    def __init__(self, key, hw: int, channels: int, classes: int):
        self.key, self.shape, self.classes = key, (hw, hw, channels), classes

    def batch(self, step: int, batch_size: int, split: str = "val"):
        k = jax.random.fold_in(self.key, step)
        x = jax.random.normal(k, (batch_size, *self.shape))
        y = jax.random.randint(jax.random.fold_in(k, 1), (batch_size,), 0,
                               self.classes)
        return x, y


@dataclasses.dataclass
class Session:
    config: dict
    run: object
    runner: object
    weights: tuple
    key: object
    kept: dict = dataclasses.field(default_factory=dict)   # pass -> stats


def images(s: Session, index: int) -> Images:
    m = s.config["model"]
    return Images(jax.random.fold_in(s.key, index), m["image_hw"],
                  m["in_channels"], m["num_classes"])


def one_pass(s: Session, index: int) -> dict:
    """One profile stage pass on the images of pass ``index``."""
    st = s.config["stage"]
    s.runner.dataset = images(s, index)
    params, state, comp = s.weights
    with s.run.span("profile"):
        stats = s.runner.profile(params, state, comp, n_batches=st["batches"],
                                 max_tiles=st["max_tiles"])
        jax.block_until_ready({k: [getattr(v, n) for n in STAT_NAMES]
                               for k, v in stats.items()})
    return stats


def setup(config: dict, mix: dict, run) -> Session:
    from repro.core.runner import CnnRunner
    from repro.nn import cnn

    t = time.perf_counter()
    m, st = config["model"], config["stage"]
    model = getattr(cnn, m["arch"])(num_classes=m["num_classes"],
                                    in_channels=m["in_channels"])
    key = seed_key(run.seed)
    runner = CnnRunner(model, None, batch_size=st["batch_size"],
                       seed=int(jax.random.randint(key, (), 0, 2**31 - 1)),
                       use_kernel_stats=st["use_kernel_stats"])
    params, state, _, comp = runner.init()
    s = Session(config=config, run=run, runner=runner,
                weights=(params, state, comp), key=key)
    jax.block_until_ready(params)
    print(f"setup: runner and weights {time.perf_counter() - t:.3f} s",
          file=sys.stderr)
    for i in (2**30, 2**30 + 1):  # warm-up passes, on images of their own
        t = time.perf_counter()
        one_pass(s, i)
        print(f"setup: warm-up pass {time.perf_counter() - t:.3f} s",
              file=sys.stderr)
    return s


def window(s: Session, seconds: float) -> dict:
    """Whole passes until ``seconds`` have passed; keeps the statistics of
    ``check.passes`` of them, drawn from the seed as the window runs."""
    rng = np.random.default_rng([int(s.run.seed) % 2**64, 4])
    k = int(s.config["check"]["passes"])
    with s.run.span("window"):
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < seconds:
            stats = one_pass(s, n)
            # reservoir sample: every pass equally likely to be checked
            if n < k:
                s.kept[n] = stats
            else:
                j = int(rng.integers(0, n + 1))
                if j < k:
                    del s.kept[sorted(s.kept)[j]]
                    s.kept[n] = stats
            n += 1
        elapsed = time.perf_counter() - t0
    return {"window_s": elapsed, "passes": n, "attempted": n, "failed": 0,
            "counters": {"layers": len(s.runner.model.comp_layers)}}


def reference_errors(s: Session, *, control: bool = False) -> tuple:
    """(largest absolute difference of any count or histogram bin, largest
    relative difference of any energy sum) over the kept passes, against
    the reference on the same taps (``control``: the bfloat16 reference in
    the program's place)."""
    st, coeffs = s.config["stage"], s.config["energy_coeffs"]
    runner = s.runner
    params, state, comp = s.weights
    hist_diff, energy_err = 0.0, 0.0
    for index, stats in sorted(s.kept.items()):
        runner.dataset = images(s, index)
        taps = runner.capture_taps(params, state, comp, st["batches"])
        for cl in runner.model.comp_layers:
            wm, x = REF.layer_matrices(taps[cl.name]["a_int"],
                                       taps[cl.name]["w_int"], cl.kind,
                                       cl.kernel, cl.stride)
            w_t, a_t = REF.sample_tiles(wm, x, cl.name, st["max_tiles"])
            ref = REF.layer_stats(w_t, a_t, coeffs)
            got = {n: np.asarray(getattr(stats[cl.name], n), np.float64)
                   for n in STAT_NAMES}
            if control:
                got["energy_sum"] = REF.layer_stats(
                    w_t, a_t, coeffs, bf16=True)["energy_sum"]
            for n in ("count", "group_hist", "act_hist"):
                hist_diff = max(hist_diff,
                                float(np.abs(got[n] - ref[n]).max()))
            e_ref = ref["energy_sum"]
            rel = np.abs(got["energy_sum"] - e_ref) / np.maximum(
                np.abs(e_ref), 1.0)
            energy_err = max(energy_err, float(rel.max()))
    return hist_diff, energy_err


def compared(s: Session, win: dict, *, control: bool = False) -> dict:
    """The numbers the check compares (``control``: the bfloat16 reference
    in the program's place); no kept pass compares as infinitely wrong."""
    names = ("hist_max_abs_diff", "energy_max_rel_err")
    if not s.kept:
        return dict.fromkeys(names, math.inf)
    return dict(zip(names, reference_errors(s, control=control)))
