"""Pallas TPU kernel: systolic-array transition statistics (paper Sec. 3.1).

Profiling a layer means tracing, for every MAC of a 64x64 weight-stationary
tile, the partial-sum transition sequence and accumulating:

  * per-weight-value energy sums / counts        (256 bins)
  * the 50x50 MSB/Hamming group transition hist  (grouping of Sec. 3.1.1)
  * the 256x256 activation transition histogram

This replaces the paper's ModelSim gate-level inner loop and dominates
profiling time, so it gets a kernel. TPU mapping decisions:

  * grid = (n_tiles, T-1): one program per tile and streaming transition
    t -> t+1; the psum prefix over the K axis is recomputed per step (two
    64x64 triangular matmuls) instead of carrying systolic state — grid
    steps stay independent.
  * the pair histograms are ONE-HOT MATMULS on the MXU
    (onehot(prev)^T @ onehot(cur)): no gathers or scatters, which TPUs
    hate. The per-weight-value energy sums are a masked reduction of a
    (64, 64, 256) one-hot on the VPU, which keeps them in f32.
  * all outputs revisit the same VMEM blocks across the grid (accumulation
    pattern with pl.when init at the first step).

Every op is one Mosaic lowers: bit-level ops (population_count / clz) run
on the VPU, and every BlockSpec is tiling-legal. Validated in interpret
mode against the `repro.core.stats` oracle, and bit-exactly against the
cosim (`repro.cosim`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.mac_model import MacEnergyCoeffs

TILE = 64
N_WVALS = 256
N_GROUPS = 50
N_MSB_GROUPS = 10
N_HD_SUBGROUPS = 5
MASK22 = (1 << 22) - 1
MASK16 = (1 << 16) - 1


def _popcount(x):
    return jax.lax.population_count(x)


def _msb22(x):
    # Pinned semantics (tests/test_cosim_differential.py, gated against the
    # bit-accurate cosim): the 22-bit mask applies BEFORE the zero test, so
    # any value that is zero modulo 2^22 (including 1 << 22) returns -1 and
    # lands in msb_val = 0; negatives see their two's-complement 22-bit
    # view (e.g. -1 -> MASK22 -> 21).
    masked = x & MASK22
    msb = 31 - jax.lax.clz(masked)
    return jnp.where(masked == 0, jnp.int32(-1), msb)


def _group_id(p):
    # mg = msb_val * 10 // 23 over msb_val 0..22 never exceeds 9, and
    # hg = hw * 5 // 23 over hw 0..22 never exceeds 4 — the minimums are
    # defensive clamps, exercised exhaustively by the boundary tables in
    # tests/test_cosim_differential.py.
    msb_val = _msb22(p) + 1
    mg = jnp.minimum((msb_val * N_MSB_GROUPS) // 23, N_MSB_GROUPS - 1)
    hw = _popcount(p & MASK22)
    hg = jnp.minimum((hw * N_HD_SUBGROUPS) // 23, N_HD_SUBGROUPS - 1)
    return mg * N_HD_SUBGROUPS + hg


def _energy(w, a_prev, a_cur, p_prev, p_cur, c: MacEnergyCoeffs):
    prod_t = _popcount(((w * a_prev) ^ (w * a_cur)) & MASK16).astype(jnp.float32)
    pp_t = (_popcount((a_prev ^ a_cur) & 0xFF)
            * _popcount(w & 0xFF)).astype(jnp.float32)
    dp = (p_prev ^ p_cur) & MASK22
    acc_t = _popcount(dp).astype(jnp.float32)
    carry = (_msb22(dp) + 1).astype(jnp.float32)
    active = c.c_prod * prod_t + c.c_pp * pp_t + c.c_acc * acc_t + c.c_carry * carry
    gated = c.c_zero * acc_t
    return jnp.where(w == 0, gated, active) + jnp.float32(c.c_base)


def _onehot(idx, n):
    """One-hot of an int array along a new minor (lane) axis: (..., n)."""
    bins = jax.lax.broadcasted_iota(jnp.int32, (1,) * idx.ndim + (n,),
                                    idx.ndim)
    return idx[..., None] == bins


def _column(a_blk, t):
    """Column ``t`` of a (K, T) block as (K, 1), by a masked lane sum.

    Mosaic cannot slice a lane at a dynamic offset; the sum picks exactly
    one element per row, so it is exact."""
    lane = jax.lax.broadcasted_iota(jnp.int32, a_blk.shape, 1)
    return jnp.sum(jnp.where(lane == t, a_blk, 0), axis=1, keepdims=True)


def _prefix_sum_rows(x):
    """Inclusive prefix sum over axis 0 of a (K, M) int32 tile, |x| < 2**16.

    Mosaic has no cumsum, so a lower-triangular 0/1 matmul runs it on the
    MXU. bf16 holds integers up to 256 exactly, so x goes in as its high
    and low bytes; each part sums in f32, which is exact below 2**24."""
    k = x.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    tril = (rows >= cols).astype(jnp.bfloat16)

    def part(v):
        return jnp.dot(tril, v.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32).astype(jnp.int32)

    return part(x >> 8) * 256 + part(x & 0xFF)


_CONTRACT_ROWS = (((0,), (0,)), ((), ()))   # A^T @ B without a transpose


def _pair_hist(oh_prev, oh_cur):
    """(n, n) counts of (prev, cur) bin pairs from two (rows, n) one-hots.

    0/1 one-hots are exact in bf16 and the counts are exact in f32."""
    return jax.lax.dot_general(oh_prev.astype(jnp.bfloat16),
                               oh_cur.astype(jnp.bfloat16), _CONTRACT_ROWS,
                               preferred_element_type=jnp.float32)


def _accumulate(w, a_prev, a_cur, scale, esum_ref, cnt_ref, ghist_ref,
                ahist_ref, coeffs: MacEnergyCoeffs):
    """Accumulate one streaming transition of one tile into the output refs.

    w: (K, M) int32 stationary weights; a_prev/a_cur: (K, 1) int32
    activation columns; scale: f32 weighting (1 for real tiles, 0 for batch
    padding). Histograms go over the MAC grid as 3-D one-hots whose leading
    two dims merge into rows; a (K, M) -> (K*M,) reshape is not legal in
    Mosaic.
    """
    # systolic column prefix sums at t and t+1
    p_prev = _prefix_sum_rows(w * a_prev)                # (K, M)
    p_cur = _prefix_sum_rows(w * a_cur)

    e = _energy(w, a_prev, a_cur, p_prev, p_cur, coeffs)

    oh_w = _onehot(w + 128, N_WVALS)                     # (K, M, 256)
    e_w = jnp.where(oh_w, e[:, :, None], 0.0).reshape(-1, N_WVALS)
    esum_ref[...] += scale * jnp.sum(e_w, axis=0, keepdims=True)
    cnt_ref[...] += scale * jnp.sum(
        oh_w.astype(jnp.float32).reshape(-1, N_WVALS), axis=0, keepdims=True)

    ghist_ref[...] += scale * _pair_hist(
        _onehot(_group_id(p_prev), N_GROUPS).reshape(-1, N_GROUPS),
        _onehot(_group_id(p_cur), N_GROUPS).reshape(-1, N_GROUPS))
    act_bins = jax.lax.broadcasted_iota(jnp.int32, (1, N_WVALS), 1)
    ahist_ref[...] += scale * _pair_hist(a_prev + 128 == act_bins,
                                         a_cur + 128 == act_bins)


def _batched_kernel(mask_ref, w_ref, a_ref, esum_ref, cnt_ref, ghist_ref,
                    ahist_ref, *, coeffs: MacEnergyCoeffs):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when((b == 0) & (t == 0))
    def _init():
        esum_ref[...] = jnp.zeros_like(esum_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        ghist_ref[...] = jnp.zeros_like(ghist_ref)
        ahist_ref[...] = jnp.zeros_like(ahist_ref)

    w = w_ref[0]                                         # (K, M) of tile b
    a_blk = a_ref[0]                                     # (K, T) of tile b
    _accumulate(w, _column(a_blk, t), _column(a_blk, t + 1), mask_ref[b],
                esum_ref, cnt_ref, ghist_ref, ahist_ref, coeffs)


def transition_stats_batched_pallas(
    w_tiles: jax.Array,      # (n_tiles, 64, 64) int32 stationary tiles (K x M)
    a_blocks: jax.Array,     # (n_tiles, 64, T) int32 streamed activations
    coeffs: MacEnergyCoeffs,
    *,
    mask: jax.Array | None = None,   # (n_tiles,) f32; 0 disables a pad tile
    interpret: bool = False,
):
    """One fused device program over a whole stacked tile batch.

    Grid is (n_tiles, T-1): the tile index is the leading block dimension, so
    every sampled tile of a layer streams through one `pallas_call` instead of
    one kernel dispatch per tile. Each tile's weights and whole activation
    block are fetched once and stay in VMEM across its T-1 steps; step t
    reads columns t and t+1 from the block. All four outputs live in the same
    VMEM blocks across the entire grid (accumulation pattern, initialised at
    (b, t) == (0, 0)); `mask`, held in scalar memory, lets callers pad
    `n_tiles` up to a convenient multiple (e.g. the device count) with
    zero-weight tiles that contribute nothing. Weights and activations must
    be int8-valued (the prefix sum relies on it for exactness).
    """
    n_tiles, k, m = w_tiles.shape
    assert (k, m) == (TILE, TILE), (k, m)
    assert a_blocks.shape[:2] == (n_tiles, TILE), a_blocks.shape
    t_len = a_blocks.shape[2]
    assert t_len >= 2
    if mask is None:
        mask = jnp.ones((n_tiles,), jnp.float32)

    kernel = functools.partial(_batched_kernel, coeffs=coeffs)
    out_shapes = (
        jax.ShapeDtypeStruct((1, N_WVALS), jnp.float32),
        jax.ShapeDtypeStruct((1, N_WVALS), jnp.float32),
        jax.ShapeDtypeStruct((N_GROUPS, N_GROUPS), jnp.float32),
        jax.ShapeDtypeStruct((N_WVALS, N_WVALS), jnp.float32),
    )
    esum, cnt, ghist, ahist = pl.pallas_call(
        kernel,
        grid=(n_tiles, t_len - 1),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, TILE, TILE), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, TILE, t_len), lambda b, t: (b, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, N_WVALS), lambda b, t: (0, 0)),
            pl.BlockSpec((1, N_WVALS), lambda b, t: (0, 0)),
            pl.BlockSpec((N_GROUPS, N_GROUPS), lambda b, t: (0, 0)),
            pl.BlockSpec((N_WVALS, N_WVALS), lambda b, t: (0, 0)),
        ),
        out_shape=out_shapes,
        interpret=interpret,
    )(jnp.asarray(mask, jnp.float32), w_tiles.astype(jnp.int32),
      a_blocks.astype(jnp.int32))
    return esum[0], cnt[0], ghist, ahist


def transition_stats_pallas(
    w_tile: jax.Array,       # (64, 64) int32 (K rows x M cols, stationary)
    a_block: jax.Array,      # (64, T) int32 streamed activations
    coeffs: MacEnergyCoeffs,
    *,
    interpret: bool = False,
):
    """Single-tile statistics: the batched kernel over a batch of one."""
    return transition_stats_batched_pallas(w_tile[None], a_block[None],
                                           coeffs, interpret=interpret)
