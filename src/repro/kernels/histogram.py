"""Radix-histogram Pallas kernel.

Grid: sequential row-blocks of the digit array (viewed as (rows, 128) lanes).
Each step expands every 128-lane row of its tile into a (num_bins, 128)
one-hot (bins along sublanes: dense VPU work — the TPU replacement for
shared-memory atomics, DESIGN.md §2) and adds it into the single
(num_bins, 128) output block, which stays VMEM-resident across the whole
grid. The wrapper folds the 128 lane partials into the final counts.

The one-hot core (`common.lane_onehot`) is shared with the per-block
histogram and rank kernels in radix_partition.py; padding slots carry
PAD_DIGIT and are excluded from the counts by construction.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

from .common import LANES, digit_lane_blocks, resolve_interpret, tile_onehot_sum


def _hist_kernel(num_bins: int, x_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] = tile_onehot_sum(x_ref, num_bins, o_ref[...])


def histogram_pallas(
    digits: jax.Array,
    num_bins: int,
    *,
    block_rows: int = 64,
    interpret: bool | None = None,
) -> jax.Array:
    """Counts per digit. digits int32; padding/pad rows (PAD_DIGIT or any
    negative digit) are excluded by construction. Returns (num_bins,)
    int32."""
    d2 = digit_lane_blocks(digits, block_rows)
    grid = d2.shape[0] // block_rows
    out = pl.pallas_call(
        functools.partial(_hist_kernel, num_bins),
        name="histogram",
        grid=(grid,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((num_bins, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_bins, LANES), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(d2)
    return out.sum(axis=1)
