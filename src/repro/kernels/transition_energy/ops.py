"""jit'd wrapper matching the `repro.core.stats.tile_transition_stats` API."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.core.mac_model import DEFAULT_COEFFS, MacEnergyCoeffs
from repro.kernels import resolve_interpret
from repro.kernels.transition_energy.transition_energy import (
    transition_stats_batched_pallas,
    transition_stats_pallas,
)


@functools.partial(jax.jit, static_argnames=("coeffs", "interpret"))
def tile_transition_stats(
    w_tile: jax.Array,
    a_block: jax.Array,
    coeffs: MacEnergyCoeffs = DEFAULT_COEFFS,
    *,
    interpret: Optional[bool] = None,
):
    """Returns (energy_sum[256], count[256], group_hist[50,50],
    act_hist[256,256]) — drop-in for the pure-jnp oracle."""
    return transition_stats_pallas(w_tile, a_block, coeffs,
                                   interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("coeffs", "interpret"))
def batched_transition_stats(
    w_tiles: jax.Array,
    a_blocks: jax.Array,
    coeffs: MacEnergyCoeffs = DEFAULT_COEFFS,
    *,
    mask: jax.Array | None = None,
    interpret: Optional[bool] = None,
):
    """Whole-tile-batch stats in ONE `pallas_call` (grid (n_tiles, T-1)).

    Same four outputs as `tile_transition_stats`, already summed over the
    batch. `mask` (n_tiles,) zeroes the contribution of padding tiles.
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    return transition_stats_batched_pallas(
        w_tiles, a_blocks, coeffs, mask=mask,
        interpret=resolve_interpret(interpret))
