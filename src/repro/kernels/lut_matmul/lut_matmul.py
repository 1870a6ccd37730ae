"""Pallas TPU kernel: 4-bit codebook-index GEMM with fused epilogues.

The compressed layer of Section 4 stores, per weight, only a 4-bit index into
the layer's restricted set C_l (|C_l| <= 16 int8 values) plus a per-output-
channel dequant scale. This kernel streams the packed indices HBM->VMEM,
dequantizes in-register via a 16-way select (no gather — MXU-adjacent VPU
work), and feeds the MXU with bf16/f32 tiles:

    Y[m, n] = act(sum_k X[m, k] * (codebook[idx[k, n]] * scale[n]) + bias[n])
              + residual[m, n]

The epilogue (bias add, activation, residual add) runs inside the kernel on
the last K grid step, while the output tile is still in VMEM — one kernel per
matmul instead of gather -> GEMM -> bias -> activation -> residual as
separate dispatches.

Packing layout (TPU-friendly: unpack is a concat along K, no interleave):
packing is block-local over K blocks of ``pack_block`` rows — within each
block, byte row j packs index rows j (low nibble) and j + pack_block/2
(high nibble):
    packed[j, n] = (idx[j, n] & 0xF) | (idx[j + pack_block/2, n] << 4).
The kernel ``block_k`` may be any multiple of ``pack_block`` (the autotuner
sweeps it); each (block_k//2, block_n) byte tile then expands sub-block by
sub-block entirely inside VMEM.

Grid: (M/bm, N/bn, K/bk) with K-innermost accumulation into the output tile
(pl.when(k == 0) zero-init; the output block index ignores k, so the same
VMEM tile is revisited across the K loop and the epilogue fires exactly once,
at k == K/bk - 1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N_CODES = 16

# epilogue activations the kernel can fuse; keys are the public contract
# (serve_dense/serve_conv/apply_dense take the same names)
ACTIVATIONS = {
    "none": lambda v: v,
    "relu": jax.nn.relu,
    "gelu": functools.partial(jax.nn.gelu, approximate=True),
    "silu": jax.nn.silu,
}


def _unpack_tile(packed, pack_block: int):
    """(bk//2, bn) packed bytes -> (bk, bn) int32 indices, per pack block."""
    k2, bn = packed.shape
    p = packed.astype(jnp.int32) & 0xFF
    p = p.reshape(2 * k2 // pack_block, pack_block // 2, bn)
    low = p & 0xF                        # sub-block rows [0, pack_block/2)
    high = (p >> 4) & 0xF                # sub-block rows [pack_block/2, ...)
    return jnp.concatenate([low, high], axis=1).reshape(2 * k2, bn)


def _dequant(packed, cb_ref, scale_ref, pack_block: int):
    idx = _unpack_tile(packed, pack_block)
    # 16-way select instead of gather: w = sum_c (idx == c) * cb[c], with
    # each cb[c] a scalar read from the int32 codebook in scalar memory
    w = jnp.zeros(idx.shape, jnp.float32)
    for c in range(N_CODES):
        w = w + jnp.where(idx == c, cb_ref[c].astype(jnp.float32), 0.0)
    return w * scale_ref[...].astype(jnp.float32)  # (1, bn) per-out-channel


def _kernel(x_ref, packed_ref, cb_ref, scale_ref, *rest,
            pack_block: int, grid_k: int, activation: str,
            has_bias: bool, has_residual: bool):
    o_ref = rest[-1]
    bias_ref = rest[0] if has_bias else None
    res_ref = rest[1 if has_bias else 0] if has_residual else None
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                      # (bm, bk)
    w = _dequant(packed_ref[...], cb_ref, scale_ref, pack_block)
    acc = jnp.dot(x.astype(jnp.float32), w,
                  preferred_element_type=jnp.float32)

    # accumulate in f32 across the K grid; the wrapper casts to out_dtype
    # once after the last K step (accumulating in a narrow out_dtype would
    # re-round the running sum at every K step)
    @pl.when(k_idx < grid_k - 1)
    def _accumulate():
        o_ref[...] += acc

    @pl.when(k_idx == grid_k - 1)
    def _finalize():
        y = o_ref[...] + acc
        if has_bias:
            y = y + bias_ref[...].astype(jnp.float32)
        y = ACTIVATIONS[activation](y)
        if has_residual:
            y = y + res_ref[...].astype(jnp.float32)
        o_ref[...] = y


def _check_blocks(m, k, n, k2, block_m, block_n, block_k, pack_block):
    if k != 2 * k2:
        raise ValueError(
            f"packed shape {(k2, n)} does not pair with x shape {(m, k)}: "
            f"need K == 2 * packed rows, got K={k} vs {2 * k2}")
    if pack_block % 2 != 0 or pack_block < 2:
        raise ValueError(f"pack_block must be a positive even int, "
                         f"got {pack_block}")
    if block_k % pack_block != 0:
        raise ValueError(
            f"block_k={block_k} must be a multiple of pack_block="
            f"{pack_block} (nibble pairing is block-local to pack_block)")
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(
            f"shape (M={m}, K={k}, N={n}) not divisible by blocks "
            f"(block_m={block_m}, block_n={block_n}, block_k={block_k}); "
            "pad via repro.kernels.lut_matmul.ops.lut_matmul")


def lut_matmul_pallas(
    x: jax.Array,            # (M, K) float
    packed: jax.Array,       # (K//2, N) int8 packed 4-bit indices
    codebook: jax.Array,     # (16,) int8/int32 codebook values (SMEM)
    scale: jax.Array,        # (N,) float per-channel dequant scale
    *,
    bias: jax.Array | None = None,       # (N,) fused bias add
    residual: jax.Array | None = None,   # (M, N) fused residual add
    activation: str = "none",            # fused: none|relu|gelu|silu
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    pack_block: int | None = None,       # export-time pack block (default: block_k)
    interpret: bool = False,
) -> jax.Array:
    """Fused LUT GEMM: Y = act(X @ dequant(packed) + bias) + residual."""
    m, k = x.shape
    k2, n = packed.shape
    pack_block = block_k if pack_block is None else pack_block
    _check_blocks(m, k, n, k2, block_m, block_n, block_k, pack_block)
    if activation not in ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}; "
                         f"expected one of {sorted(ACTIVATIONS)}")
    out_dtype = x.dtype if x.dtype != jnp.bfloat16 else jnp.float32

    grid = (m // block_m, n // block_n, k // block_k)
    has_bias = bias is not None
    has_residual = residual is not None
    kernel = functools.partial(
        _kernel, pack_block=pack_block, grid_k=grid[2], activation=activation,
        has_bias=has_bias, has_residual=has_residual)

    # per-channel vectors ride as (1, N) rows: Mosaic tiles 2-D blocks only
    row_spec = pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j))
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((block_k // 2, block_n), lambda i, j, kk: (kk, j)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        row_spec,
    ]
    args = [x, packed, codebook.astype(jnp.int32), scale.reshape(1, n)]
    if has_bias:
        if bias.shape != (n,):
            raise ValueError(f"bias shape {bias.shape} != ({n},)")
        in_specs.append(row_spec)
        args.append(bias.reshape(1, n))
    if has_residual:
        if residual.shape != (m, n):
            raise ValueError(f"residual shape {residual.shape} != {(m, n)}")
        in_specs.append(
            pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)))
        args.append(residual)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(*args)
    return out.astype(out_dtype)
