"""jit'd wrappers + weight encode/pack utilities for the LUT GEMM."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.lut_matmul.lut_matmul import N_CODES, lut_matmul_pallas
from repro.kernels.lut_matmul.ref import lut_matmul_fused_ref


def encode_weights(w_int: jax.Array, codebook: jax.Array):
    """Map int8-valued weights to nearest-codebook indices.

    w_int: (K, N) int weights already restricted (or to be snapped) to the
    codebook; codebook: (16,) sorted int values. Returns (K, N) int32 indices.
    Ties (including duplicate/padded codebook entries) resolve to the lowest
    index, so padded codebooks encode stably: every chosen index decodes to
    the same value the projection picked.
    """
    dist = jnp.abs(w_int[..., None].astype(jnp.int32)
                   - codebook[None, None, :].astype(jnp.int32))
    return jnp.argmin(dist, axis=-1).astype(jnp.int32)


def pack_indices(idx: jax.Array, block_k: int = 128) -> jax.Array:
    """(K, N) 4-bit indices -> (K//2, N) int8, block-local pairing.

    Within each K block of ``block_k`` rows, byte row j packs index rows j
    (low nibble) and j + block_k/2 (high nibble) so the kernel's unpack is a
    VMEM-internal concat (no cross-block shuffling).
    """
    k, n = idx.shape
    if block_k % 2 != 0:
        raise ValueError(f"block_k must be even, got {block_k}")
    if k % block_k != 0:
        raise ValueError(
            f"K={k} is not a multiple of block_k={block_k}; pad the index "
            "rows first (packing is block-local, see repro.core.export)")
    blocks = idx.reshape(k // block_k, block_k, n).astype(jnp.int32)
    low = blocks[:, : block_k // 2]
    high = blocks[:, block_k // 2:]
    packed = (low & 0xF) | ((high & 0xF) << 4)
    return packed.reshape(k // 2, n).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("activation", "block_m",
                                             "block_n", "block_k",
                                             "pack_block", "interpret",
                                             "use_ref"))
def _fused_jit(x, packed, codebook, scale, bias, residual, *, activation,
               block_m, block_n, block_k, pack_block, interpret, use_ref):
    """One jitted dispatch: pad M/N, run the fused kernel, slice back."""
    if use_ref:
        return lut_matmul_fused_ref(
            x, packed, codebook, scale, bias=bias, residual=residual,
            activation=activation, block_k=pack_block)
    m, k = x.shape
    _, n = packed.shape
    pm, pn = (-m) % block_m, (-n) % block_n
    xp = jnp.pad(x, ((0, pm), (0, 0))) if pm else x
    pp = jnp.pad(packed, ((0, 0), (0, pn))) if pn else packed
    sp = jnp.pad(scale, (0, pn)) if pn else scale
    bp = None if bias is None else (
        jnp.pad(bias, (0, pn)) if pn else bias)
    rp = None if residual is None else (
        jnp.pad(residual, ((0, pm), (0, pn))) if pm or pn else residual)
    out = lut_matmul_pallas(xp, pp, codebook, sp, bias=bp, residual=rp,
                            activation=activation, block_m=block_m,
                            block_n=block_n, block_k=block_k,
                            pack_block=pack_block, interpret=interpret)
    return out[:m, :n]


def lut_matmul_fused(
    x: jax.Array,            # (M, K)
    packed: jax.Array,       # (K//2, N) int8 packed 4-bit indices
    codebook: jax.Array,     # (16,) int8/int32 codebook values
    scale: jax.Array,        # (N,) per-channel dequant scale
    *,
    bias: Optional[jax.Array] = None,       # (N,)
    residual: Optional[jax.Array] = None,   # (M, N)
    activation: str = "none",               # none|relu|gelu|silu
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    pack_block: int = 128,
    interpret: Optional[bool] = None,
    use_ref: bool = False,
) -> jax.Array:
    """Fused serve matmul: Y = act(X @ dequant(packed) + bias) + residual.

    Pads M/N to block multiples as needed (K must already be a ``pack_block``
    multiple — packing is block-local). Block shapes left as ``None`` resolve
    through the roofline autotuner (`repro.kernels.lut_matmul.autotune`),
    cached per (M, K, N, pack_block, backend) fingerprint. ``interpret=None``
    resolves per backend (`repro.kernels.resolve_interpret`): compiled
    Pallas on TPU, interpreter elsewhere.
    """
    m, k = x.shape
    _, n = packed.shape
    if k % pack_block:
        raise ValueError(
            f"K={k} must already be a multiple of pack_block={pack_block} "
            "(packing is block-local; pad K at export)")
    interpret = resolve_interpret(interpret)
    if use_ref:
        # the ref oracle ignores block shapes — don't touch the autotuner
        block_m = block_n = block_k = pack_block
    if block_m is None or block_n is None or block_k is None:
        from repro.kernels.lut_matmul.autotune import get_default_autotuner

        tm, tn, tk = get_default_autotuner().best(m, k, n,
                                                  pack_block=pack_block)
        block_m = tm if block_m is None else block_m
        block_n = tn if block_n is None else block_n
        block_k = tk if block_k is None else block_k
    if k % block_k:
        raise ValueError(
            f"K={k} must be a multiple of block_k={block_k} "
            "(packing is block-local)")
    return _fused_jit(x, packed, codebook, scale, bias, residual,
                      activation=activation, block_m=block_m, block_n=block_n,
                      block_k=block_k, pack_block=pack_block,
                      interpret=interpret, use_ref=use_ref)


def lut_matmul(
    x: jax.Array,
    packed: jax.Array,
    codebook: jax.Array,
    scale: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
    use_ref: bool = False,
) -> jax.Array:
    """Epilogue-free LUT GEMM (compatibility entry point).

    Equivalent to `lut_matmul_fused` with no bias/activation/residual and
    ``pack_block == block_k`` (the historical contract: kernel block == pack
    block).
    """
    return lut_matmul_fused(x, packed, codebook, scale, block_m=block_m,
                            block_n=block_n, block_k=block_k,
                            pack_block=block_k, interpret=interpret,
                            use_ref=use_ref)


def compress_layer_weights(w: jax.Array, codebook_values, *,
                           mask: Optional[jax.Array] = None,
                           scale: Optional[jax.Array] = None,
                           msr_bits: int = 0,
                           block_k: int = 128,
                           pad_k: bool = False):
    """End-to-end encode of a float (K, N) weight matrix for serving.

    Returns (packed, codebook_arr, scale): per-output-channel symmetric scale,
    int8 snap to the restricted set, 4-bit pack. Mirrors the QAT fake-quant
    semantics: mask -> per-channel scale of the *masked* weight -> round/clip
    -> nearest-codebook projection. ``scale`` overrides the per-column scale
    (used by `repro.core.export` when the training scale reduces over a
    different layout than the matrix columns); ``pad_k=True`` pads K up to a
    ``block_k`` multiple (padded rows encode the 0-nearest entry and pair
    with zero-padded activation rows at serve time).

    A pruning ``mask`` (zeros = pruned) is honored exactly: 0 is
    force-included in the serving codebook when the mask prunes anything, and
    pruned positions encode to the index of 0 — pruned MACs stay zero-gated
    on the array even when the training codebook C_l itself lacks 0.
    """
    from repro.core import qat

    vals = sorted({int(v) for v in codebook_values})
    if not vals:
        raise ValueError("empty codebook")
    prunes = mask is not None and bool(jnp.any(mask == 0))
    serve_vals = sorted(set(vals) | {0}) if prunes else vals
    if len(serve_vals) > N_CODES:
        raise ValueError(
            f"codebook needs {len(serve_vals)} entries (> {N_CODES}); "
            "pruned layers must leave room for the forced 0 entry")

    wm = w * mask.astype(w.dtype) if mask is not None else w
    if scale is None:
        scale = qat.weight_scale(wm)[0]                 # (N,)
    q = jnp.clip(jnp.round(wm / scale[None, :]), -qat.QMAX, qat.QMAX)
    # MSR-truncate then project onto the *training* set (identical order to
    # fake_quant_weight), then force pruned positions to the serving 0 entry
    qi = q.astype(jnp.int32)
    if msr_bits:
        qi = qat.msr_truncate_int(qi, msr_bits)
    cb_train, k_train = qat.make_codebook(vals)
    qp = qat.project_to_codebook(qi, cb_train, k_train)
    if mask is not None:
        qp = jnp.where(mask == 0, 0, qp)

    cb = jnp.asarray(serve_vals, jnp.int32)
    cb = jnp.pad(cb, (0, N_CODES - cb.shape[0]), constant_values=cb[-1])
    idx = encode_weights(qp, cb)
    if pad_k:
        pad = (-idx.shape[0]) % block_k
        if pad:
            zero_idx = int(jnp.argmin(jnp.abs(cb)))
            idx = jnp.pad(idx, ((0, pad), (0, 0)), constant_values=zero_idx)
    packed = pack_indices(idx, block_k)
    return packed, cb.astype(jnp.int8), scale
