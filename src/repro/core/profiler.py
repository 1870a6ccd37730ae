"""Batched whole-layer systolic profiling (paper 3.1.2, fused).

The seed implementation of `collect_layer_stats` dispatched the per-tile
trace one (64, 64) tile at a time from a Python loop — profiling a model was
serialized on kernel-launch overhead exactly where the paper's flow is
serialized on gate-level simulation. This module replaces the loop:

  1. ``gather_layer_tiles`` — all sampled (mi, ki, ni) tiles of a layer are
     gathered into stacked (n_tiles, 64, 64) weight / (n_tiles, 64, T)
     activation batches with ONE take per operand (a reshape/transpose view
     of the padded matrices plus a leading-axis gather).
  2. ``batched_layer_stats`` — the whole batch runs as one device program:
     either the batched Pallas kernel (grid (n_tiles, T-1), tile index as
     the leading block dimension) or a vmapped `tile_transition_stats`
     oracle reduced over the batch (the CPU / interpret fallback).
  3. ``profile_layer`` — sampling + gather + trace + `LayerStats` assembly;
     with more than one device (or an explicit mesh) the tile batch is
     sharded over the 1-D profiling mesh of `repro.distributed.sharding`
     via `shard_map`, each device tracing its slice and psum-reducing the
     four fixed-size statistics outputs.

Padding semantics are inherited from `pad_to_tiles`: partial tiles are
zero-padded and the padded MACs *do* count (w = 0 still clocks, matching
`weight_value_counts`). Batch padding up to the device count, by contrast,
is masked out and contributes nothing.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from repro.core.grouping import N_GROUPS
from repro.core.mac_model import DEFAULT_COEFFS, MacEnergyCoeffs
from repro.core.stats import (
    N_WVALS,
    TILE,
    LayerStats,
    pad_to_tiles,
)
from repro.distributed.sharding import TILE_AXIS, tile_mesh
from repro.kernels import resolve_interpret

StatsTuple = Tuple[jax.Array, jax.Array, jax.Array, jax.Array]


def gather_layer_tiles(
    w_pad: jax.Array,
    x_pad: jax.Array,
    tile_idx: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Stack sampled tiles: (n, 64, 64) stationary (K x M) + (n, 64, T) blocks.

    ``tile_idx`` holds flat (mi, ki, ni) indices in mi-major order, i.e.
    ``idx = (mi * kt + ki) * nt + ni`` — the same enumeration the seed loop
    used. One gather per operand; no per-tile host round-trips.
    """
    mp, kp = w_pad.shape
    kp2, np_ = x_pad.shape
    assert kp == kp2, (kp, kp2)
    mt, kt, nt = mp // TILE, kp // TILE, np_ // TILE

    idx = jnp.asarray(tile_idx, jnp.int32)
    mi = idx // (kt * nt)
    rest = idx % (kt * nt)
    ki = rest // nt
    ni = rest % nt

    # (mt*kt, K_t, M_t): w_pad[mi*T:(mi+1)T, ki*T:(ki+1)T].T for every (mi, ki)
    w_all = w_pad.reshape(mt, TILE, kt, TILE).transpose(0, 2, 3, 1)
    w_all = w_all.reshape(mt * kt, TILE, TILE)
    # (kt*nt, K_t, T): x_pad[ki*T:(ki+1)T, ni*T:(ni+1)T] for every (ki, ni)
    a_all = x_pad.reshape(kt, TILE, nt, TILE).transpose(0, 2, 1, 3)
    a_all = a_all.reshape(kt * nt, TILE, TILE)

    w_tiles = jnp.take(w_all, mi * kt + ki, axis=0)
    a_blocks = jnp.take(a_all, ki * nt + ni, axis=0)
    return w_tiles, a_blocks


def _pair_hist(bins: jax.Array, host_hist: bool) -> jax.Array:
    """Unweighted histogram of (g_prev*50 + g_cur) codes, shape (2500,).

    XLA's CPU scatter runs ~80 ns/update single-threaded, which would leave
    the group histogram as the profiler's dominant cost; `np.bincount` via
    `pure_callback` counts the same bins ~5x faster and is exact (integer
    counts). Non-CPU backends keep the native scatter (fast there, and the
    Pallas kernel path is the production route anyway). ``host_hist=False``
    forces the scatter — required inside `shard_map`, where concurrent
    callbacks from per-device executors deadlock on CPU. The callback is
    also skipped on single-core hosts: with a 1-thread intra-op pool the
    executor thread that must service the callback is the one blocked on
    the surrounding computation, and the dispatch deadlocks."""
    if host_hist and jax.default_backend() == "cpu" \
            and (os.cpu_count() or 1) > 1:
        def cb(b):
            import numpy as np

            return np.bincount(
                np.asarray(b).ravel(), minlength=N_GROUPS * N_GROUPS
            ).astype(np.float32)

        return jax.pure_callback(
            cb, jax.ShapeDtypeStruct((N_GROUPS * N_GROUPS,), jnp.float32),
            bins)
    return jax.ops.segment_sum(
        jnp.ones((bins.size,), jnp.float32), bins.reshape(-1),
        num_segments=N_GROUPS * N_GROUPS)


@functools.partial(jax.jit, static_argnames=("coeffs", "host_hist"))
def batched_stats_oracle(
    w_tiles: jax.Array,
    a_blocks: jax.Array,
    mask: jax.Array,
    coeffs: MacEnergyCoeffs = DEFAULT_COEFFS,
    *,
    host_hist: bool = True,
) -> StatsTuple:
    """Pure-jnp trace of the whole tile batch, reduced to layer sums.

    Bin-for-bin identical to summing `tile_transition_stats` per tile (the
    histogram bins are exact integer counts; only fp32 summation order
    differs). Three things make this >5x the seed per-tile loop on CPU:

      * an `optimization_barrier` between the trace producers and the
        histogram scatters — without it XLA CPU fuses the bit-level energy
        computation *into* each scatter and re-evaluates it per update,
        which is what made the seed's per-tile path ~25x slower than the
        sum of its parts;
      * the weight bin of a MAC is constant along the streaming axis, so
        energy_sum / count pre-reduce over T and scatter n*K*M elements
        instead of n*K*M*(T-1) (62x fewer updates);
      * the group histogram (whose bins DO vary per transition) goes
        through `_pair_hist` instead of a scatter.

    ``mask`` zeroes the contribution of batch-padding tiles. Masked tiles'
    inputs are zeroed before tracing, which makes their trace analytic —
    every transition is (w=0, 0 -> 0), group (0, 0), energy c_base — so
    their share of the unweighted group histogram is subtracted in closed
    form rather than weighting all E elements. This holds for ANY caller
    mask, not just the internal all-zero padding.
    """
    from repro.core.grouping import group_id
    from repro.core.mac_model import mac_transition_energy

    mask_i = jnp.asarray(mask != 0, jnp.int32)
    w_tiles = jnp.asarray(w_tiles, jnp.int32) * mask_i[:, None, None]
    a_blocks = jnp.asarray(a_blocks, jnp.int32) * mask_i[:, None, None]
    n, k_t, m_t = w_tiles.shape
    t_len = a_blocks.shape[2]
    trans_per_mac = t_len - 1

    w = w_tiles[:, :, :, None]                                # (n, K, M, 1)
    prods = w * a_blocks[:, :, None, :]                       # (n, K, M, T)
    psums = jnp.cumsum(prods, axis=1)
    p_prev, p_cur = psums[..., :-1], psums[..., 1:]
    a_prev = a_blocks[:, :, None, :-1]
    a_cur = a_blocks[:, :, None, 1:]

    energy = mac_transition_energy(w, a_prev, a_cur, p_prev, p_cur, coeffs)
    e_red = jnp.sum(energy, axis=-1)                          # (n, K, M)
    groups = group_id(psums)                                  # (n, K, M, T)
    g_bins = groups[..., :-1] * N_GROUPS + groups[..., 1:]
    e_red, g_bins = jax.lax.optimization_barrier((e_red, g_bins))

    m_tile = mask[:, None, None]                              # (n, 1, 1)
    w_bins = (w_tiles + 128).reshape(-1)                      # (n*K*M,)
    energy_sum = jax.ops.segment_sum(
        (e_red * m_tile).reshape(-1), w_bins, num_segments=N_WVALS)
    count = jax.ops.segment_sum(
        jnp.broadcast_to(m_tile * trans_per_mac, e_red.shape).reshape(-1),
        w_bins, num_segments=N_WVALS)

    # unweighted pair histogram, minus the analytic all-zero-tile padding
    n_pad = jnp.float32(n) - jnp.sum(mask)
    group_hist = _pair_hist(g_bins, host_hist).reshape(N_GROUPS, N_GROUPS)
    group_hist = group_hist.at[0, 0].add(
        -n_pad * (k_t * m_t * trans_per_mac))

    ap = (a_blocks[:, :, :-1] + 128).reshape(-1)              # (n*K*(T-1),)
    ac = (a_blocks[:, :, 1:] + 128).reshape(-1)
    m_act = jnp.broadcast_to(
        mask[:, None, None], a_blocks[:, :, 1:].shape).reshape(-1)
    act_hist = jax.ops.segment_sum(
        m_act, ap * N_WVALS + ac, num_segments=N_WVALS * N_WVALS
    ).reshape(N_WVALS, N_WVALS)

    return energy_sum, count, group_hist, act_hist


def batched_layer_stats(
    w_tiles: jax.Array,
    a_blocks: jax.Array,
    coeffs: MacEnergyCoeffs = DEFAULT_COEFFS,
    *,
    mask: Optional[jax.Array] = None,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
    host_hist: bool = True,
) -> StatsTuple:
    """One batched trace invocation: Pallas kernel or vectorized oracle."""
    if mask is None:
        mask = jnp.ones((w_tiles.shape[0],), jnp.float32)
    if use_kernel:
        from repro.kernels.transition_energy import ops as te_ops

        return te_ops.batched_transition_stats(
            w_tiles, a_blocks, coeffs, mask=mask, interpret=interpret)
    return batched_stats_oracle(w_tiles, a_blocks, mask, coeffs,
                                host_hist=host_hist)


def sharded_layer_stats(
    w_tiles: jax.Array,
    a_blocks: jax.Array,
    coeffs: MacEnergyCoeffs = DEFAULT_COEFFS,
    *,
    mask: Optional[jax.Array] = None,
    mesh: Optional[Mesh] = None,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
) -> StatsTuple:
    """Shard the tile batch over a 1-D device mesh and psum the statistics.

    The batch is zero-padded (masked) up to a multiple of the mesh size, each
    device traces its local slice with `batched_layer_stats`, and the four
    outputs — (256,), (256,), (50, 50), (256, 256), a few hundred KiB total —
    are psum-reduced, so multi-chip profiling costs one small all-reduce.
    """
    mesh = tile_mesh() if mesh is None else mesh
    n_dev = mesh.shape[TILE_AXIS]
    if use_kernel and resolve_interpret(interpret):
        # Pallas interpret mode inside shard_map deadlocks on host devices;
        # interpret is a CPU-only correctness tool anyway, so the sharded
        # path falls back to the vectorized oracle (identical statistics).
        use_kernel = False
    n = w_tiles.shape[0]
    if mask is None:
        mask = jnp.ones((n,), jnp.float32)
    pad = (-n) % n_dev
    if pad:
        w_tiles = jnp.pad(w_tiles, ((0, pad), (0, 0), (0, 0)))
        a_blocks = jnp.pad(a_blocks, ((0, pad), (0, 0), (0, 0)))
        mask = jnp.pad(mask, (0, pad))

    def local(w, a, m):
        out = batched_layer_stats(w, a, coeffs, mask=m,
                                  use_kernel=use_kernel, interpret=interpret,
                                  host_hist=False)
        return jax.tree.map(lambda x: jax.lax.psum(x, TILE_AXIS), out)

    spec = PartitionSpec(TILE_AXIS)
    # check_vma=False: the Pallas kernel's out_shape carries no varying-axes
    # annotation, which the checker would require inside shard_map
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=PartitionSpec(),
                         check_vma=False)(w_tiles, a_blocks, mask)


def profile_layer(
    w_mat: jax.Array,
    x_cols: jax.Array,
    *,
    max_tiles: int = 48,
    key: jax.Array | None = None,
    coeffs: MacEnergyCoeffs = DEFAULT_COEFFS,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
    mesh: Optional[Mesh] = None,
) -> LayerStats:
    """Trace a layer's matmul on the 64x64 array — batched, loop-free.

    Drop-in replacement for the seed `collect_layer_stats` body: identical
    sampling (same key -> same tiles) and identical accumulated statistics
    up to fp32 summation order. ``mesh`` (or >1 visible device) routes the
    batch through `sharded_layer_stats`.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    w_pad, x_pad = pad_to_tiles(jnp.asarray(w_mat, jnp.int32),
                                jnp.asarray(x_cols, jnp.int32))
    mt = w_pad.shape[0] // TILE
    kt = w_pad.shape[1] // TILE
    nt = x_pad.shape[1] // TILE
    total_tiles = mt * kt * nt

    n_sample = min(max_tiles, total_tiles)
    choice = jax.random.choice(key, total_tiles, (n_sample,), replace=False)
    w_tiles, a_blocks = gather_layer_tiles(w_pad, x_pad, choice)

    if mesh is not None or jax.device_count() > 1:
        es, cnt, gh, ah = sharded_layer_stats(
            w_tiles, a_blocks, coeffs, mesh=mesh, use_kernel=use_kernel,
            interpret=interpret)
    else:
        es, cnt, gh, ah = batched_layer_stats(
            w_tiles, a_blocks, coeffs, use_kernel=use_kernel,
            interpret=interpret)

    t_len = a_blocks.shape[2]
    return LayerStats(
        act_hist=ah, group_hist=gh, energy_sum=es, count=cnt,
        n_transitions=n_sample * TILE * TILE * (t_len - 1),
    )
