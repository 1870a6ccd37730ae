"""Plain float32 reference of olmo-1b under a uniform codebook plan.

Written from the published architecture (arXiv:2402.00838) and the plan as
the configuration file states it; it imports nothing of the system under
test. One causal forward over a whole sequence, layer by layer under a
``lax.scan``, every matmul at ``Precision.HIGHEST``:

    x = embed[tokens]
    per layer:  h = LN(x);  x += Attn(h)  (RoPE on q and k, causal softmax)
                h = LN(x);  x += (silu(h Wg) * (h Wu)) Wd
    logits = LN(x) embed[:vocab]^T

LN is the non-parametric LayerNorm (eps 1e-5). Every projection weight is
replaced by its plan value before use: int8 symmetric per output channel
(the last axis, amax over all other axes of one layer's tensor, / 127),
round half to even, clip to 127, the nearest codebook member (ties to the
smaller), times the scale. The embedding is not compressed.

Departures from the system, by design: no activation quantization (the
system quantizes activations to int8 per tensor, per call, so its result
depends on what else shares the batch) and no bfloat16 compute. Both show
up as the gap the check measures.

``fp8=True`` is the control: every matmul operand is rounded to float8
(e4m3) first, the precision one step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5
QMAX = 127


def plan_weight(w, codebook):
    """The plan's value of one layer's weight tensor (last axis = outputs)."""
    axes = tuple(range(w.ndim - 1))
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / QMAX
    q = jnp.clip(jnp.round(w / scale), -QMAX, QMAX)
    cb = jnp.sort(jnp.asarray(codebook, jnp.float32))
    nearest = jnp.argmin(jnp.abs(q[..., None] - cb), axis=-1)
    return cb[nearest] * scale


def _layer_norm(x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS)


def _rope(x, theta):
    """x (S, H, D): rotate the first half against the second half."""
    s, _, d = x.shape
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _rounder(fp8: bool):
    if not fp8:
        return lambda a: a
    return lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("vocab", "theta", "fp8"))
def logits(layers, embed, codebook, tokens, *, vocab: int, theta: float,
           fp8: bool = False):
    """(S, vocab) float32 logits of one sequence ``tokens`` (S,) int32.

    ``layers`` holds the stacked per-layer tensors ``wq wk wv`` (L, d, H, D),
    ``wo`` (L, H, D, d), ``w_gate w_up`` (L, d, F), ``w_down`` (L, F, d);
    ``embed`` is (rows >= vocab, d).
    """
    r = _rounder(fp8)

    def mm(spec, a, b):
        return jnp.einsum(spec, r(a), r(b), precision=HIGHEST)

    s = tokens.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, w):
        wq, wk, wv, wo, wg, wu, wd = (
            plan_weight(w[k], codebook) for k in
            ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"))
        h = _layer_norm(x)
        q = _rope(mm("sd,dhk->shk", h, wq), theta)
        k = _rope(mm("sd,dhk->shk", h, wk), theta)
        v = mm("sd,dhk->shk", h, wv)
        att = mm("shk,thk->hst", q, k) / np.sqrt(q.shape[-1])
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        x = x + mm("shk,hkd->sd", mm("hst,thk->shk", att, v), wo)
        h = _layer_norm(x)
        ff = jax.nn.silu(mm("sd,df->sf", h, wg)) * mm("sd,df->sf", h, wu)
        return x + mm("sf,fd->sd", ff, wd), None

    x = jnp.take(embed, tokens, axis=0).astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, layers)
    return mm("sd,vd->sv", _layer_norm(x), embed[:vocab].astype(jnp.float32))


def sequence(prompt, served, bucket: int, pad: int, total: int) -> np.ndarray:
    """The tokens the served request saw, padded to ``total``: the prompt
    right-padded with ``pad`` to its bucket, then every served token."""
    seq = np.full((total,), pad, np.int32)
    seq[:len(prompt)] = prompt
    seq[bucket:bucket + len(served)] = served
    return seq


def served_gaps(ref_logits, served, bucket: int) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best at the position that produced it."""
    rows = np.asarray(ref_logits[bucket - 1:bucket - 1 + len(served)],
                      np.float64)
    best = rows.max(axis=-1)
    return best - rows[np.arange(len(served)), np.asarray(served)]
