"""Shared helpers for the Pallas TPU kernels.

Conventions (see DESIGN.md §2):
  * Every block's last two dims obey the TPU (8, 128) tiling rule. 1-D
    relational arrays are padded and viewed as (rows, 128) lane tiles with
    row-block sizes a multiple of 8; per-grid-step vectors (a probe
    sub-block, a gather window) travel as (steps, 1, width) arrays whose
    (1, width) trailing dims equal the array's, so the kernel sees one
    (1, width) row.
  * Kernels stay 2-D end to end: a row is never flattened to 1-D, and a
    row that must meet another row elementwise is transposed into a
    (width, 1) column, so pairwise work is one (width_a, width_b) tile.
  * Selections ("the value at the matching slot") are masked sums over an
    axis, exact for any dtype; float reductions that go through the MXU ask
    for `Precision.HIGHEST` so the chip keeps full f32.
  * Padding rows in digit arrays always carry PAD_DIGIT (< 0) and are
    excluded from histograms/ranks by construction (`digit_onehot` and
    `lane_onehot` mask them), never by relying on a fill value happening to
    miss a bin.
  * Kernels run compiled on TPU and in interpret mode elsewhere
    (`default_interpret`; override with REPRO_PALLAS_INTERPRET=0/1).
"""
from __future__ import annotations

import os

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

LANES = 128
SUBLANES = 8

# The single fill value for padded digit slots. Kernels exclude pad rows by
# construction: `digit_onehot` masks x < 0 out of every histogram/rank
# one-hot, so a pad row can never be counted or ranked into a bin.
PAD_DIGIT = -1


_INTERPRET_TRUE = ("1", "true", "yes", "on")
_INTERPRET_FALSE = ("0", "false", "no", "off")


def default_interpret() -> bool:
    """Pallas execution mode: compiled kernels on TPU, interpret elsewhere.

    REPRO_PALLAS_INTERPRET=1/0 (also true/false/yes/on/off...) overrides the
    backend detection — e.g. force interpret on a TPU host while debugging,
    or force compilation off-TPU to surface lowering errors. Unknown values
    raise instead of silently picking a mode: a typo'd override must not
    flip which compiler ran the kernels."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        val = env.strip().lower()
        if val in _INTERPRET_TRUE:
            return True
        if val in _INTERPRET_FALSE:
            return False
        raise ValueError(
            f"REPRO_PALLAS_INTERPRET={env!r} is not a recognized value; "
            f"allowed: {'/'.join(_INTERPRET_TRUE)} (interpret) or "
            f"{'/'.join(_INTERPRET_FALSE)} (compiled)")
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Kernel-entry helper: an explicit interpret flag wins, None defers to
    the backend detection (+ env override) above."""
    return default_interpret() if interpret is None else bool(interpret)


def digit_onehot(x: jax.Array, num_bins: int) -> jax.Array:
    """(T,) int digits -> (T, num_bins) 0/1 int32 one-hot.

    The shared core of every histogram/rank kernel (and of their dense
    interpret-mode twins): bin membership is an equality against a bin iota,
    and pad rows (PAD_DIGIT, or any negative digit) are masked out
    explicitly — excluded by construction, not by -1 never matching."""
    bins = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], num_bins), 1)
    return ((x[:, None] == bins) & (x[:, None] >= 0)).astype(jnp.int32)


def lane_onehot(row: jax.Array, num_bins: int) -> jax.Array:
    """(1, L) int digits -> (num_bins, L) 0/1 int32 one-hot: `digit_onehot`
    transposed, with bins along sublanes so a lane tile of digits expands
    without leaving 2-D. Negative digits (padding) get an all-zero column."""
    bins = jax.lax.broadcasted_iota(jnp.int32, (num_bins, row.shape[-1]), 0)
    return ((row == bins) & (row >= 0)).astype(jnp.int32)


def pad_to(x: jax.Array, multiple: int, fill=0) -> jax.Array:
    n = x.shape[0]
    pad = -n % multiple
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])


def as_lanes(x: jax.Array, fill=0) -> jax.Array:
    """(n,) -> (ceil(n/128), 128)."""
    xp = pad_to(x, LANES, fill)
    return xp.reshape(-1, LANES)


def digit_lane_blocks(digits: jax.Array, block_rows: int) -> jax.Array:
    """The one pad-and-tile path for digit arrays entering histogram/rank
    kernels: (n,) -> (grid*block_rows, 128) with every padding slot —
    lane padding and grid padding alike — filled with PAD_DIGIT. Pairs with
    `lane_onehot`, which drops those slots by construction."""
    d2 = as_lanes(digits, fill=PAD_DIGIT)
    rows = d2.shape[0]
    grid = ceil_div(rows, block_rows)
    return jnp.pad(d2, ((0, grid * block_rows - rows), (0, 0)),
                   constant_values=PAD_DIGIT)


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def tile_onehot_sum(x_ref, num_bins: int, acc: jax.Array) -> jax.Array:
    """acc + the lane_onehot of every row of the (rows, 128) digit tile in
    `x_ref`: a (num_bins, 128) matrix whose lane-sum is the tile's
    histogram. The histogram kernels' shared inner loop."""
    def row(r, a):
        return a + lane_onehot(x_ref[pl.ds(r, 1), :], num_bins)

    return jax.lax.fori_loop(0, x_ref.shape[0], row, acc)
